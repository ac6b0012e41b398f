"""Shared platform detection for the Pallas kernel entry points.

Every raw kernel wrapper defaults ``interpret=None`` → "interpret unless we
are actually on a TPU", and every :mod:`repro.kernels.ops` wrapper defaults
``use_kernel=None`` → "the Pallas kernel on a TPU, the jnp oracle
elsewhere".  On a TPU neither can be turned off: the interpreter or the
oracle there would hide the device behind a correct but unmeasured path.
``interpret`` stays a jit-static argument, so ``None`` is resolved here
exactly once per trace.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → auto-detect: native lowering on TPU, interpreter elsewhere."""
    if interpret is None:
        return not on_tpu()
    if interpret and on_tpu():
        raise ValueError(
            "interpret=True on a TPU: the kernel would run in the Pallas "
            "interpreter instead of on the device"
        )
    return interpret


def resolve_use_kernel(use_kernel: bool | None) -> bool:
    """``None`` → auto-detect: the Pallas kernel on TPU, the jnp oracle
    elsewhere."""
    if use_kernel is None:
        return on_tpu()
    if not use_kernel and on_tpu():
        raise ValueError(
            "use_kernel=False on a TPU: the jnp oracle would stand in for "
            "the Pallas kernel on the device"
        )
    return use_kernel
