"""ConvBackend registry: the single dispatch point for Hyena's long
causal convolution (see DESIGN.md §2–3, §7).

Every backend implements the same contract —
``fn(u, h, skip, gate=None) -> y`` with ``u: (B, L, D)``, ``h: (D, L)``,
``skip: (D,) | None``, ``gate: (B, L, D) | None`` — plus capability
metadata used for *early* validation (at config/context construction, not
mid-forward) and for tooling (benchmarks iterate the registry instead of
hard-coding imports).

``gate`` is the Hyena recurrence's data-controlled multiplier
``xⁿ ⊙ conv(v)``: backends with ``supports_gate`` fuse it into the conv
itself (at the Pallas kernel's finalize, or in the single post-iFFT
elementwise pass), eliminating one full-tensor HBM write+read per order.
Fusion is bit-identical to the two-pass schedule ``gate * fn(u, h, skip)``
— a pure memory-traffic optimization that can never change model outputs
(DESIGN.md §7).  Backends without the flag still honor the argument — the
registry applies the gate as a separate multiply — so callers can use the
gated entry point unconditionally.

Adding a backend is one module + one ``register_conv_backend`` call; no
dispatch site anywhere else changes.  Backend resolution — including the
``REPRO_CONV_BACKEND`` environment override used by the launch layer — goes
through :func:`resolve_conv_backend`, the only place that env var is read.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional

import jax

ENV_VAR = "REPRO_CONV_BACKEND"
DEFAULT_BACKEND = "fft"


@dataclasses.dataclass(frozen=True)
class ConvBackend:
    """A registered long-conv implementation with capability flags.

    ``fn(u, h, skip, gate=None)``: depthwise causal conv of ``u (B, L, D)``
    with per-channel length-L filters ``h (D, L)``, optional residual gain
    ``skip (D,)``, and (when ``supports_gate``) a fused elementwise output
    gate ``gate (B, L, D)``.
    """

    name: str
    fn: Callable
    description: str = ""
    tag: str = ""  # short stable identifier for benchmark/report rows
    requires_pallas: bool = False  # Pallas lowering (interpret-mode off-TPU)
    mesh_aware: bool = False  # runs collective-free under a sharded mesh
    oracle: bool = False  # O(L²) reference — tests/tiny L only
    max_len: int = 0  # 0 = unconstrained; else largest supported L
    supports_gate: bool = False  # fn fuses the elementwise output gate

    def validate_len(self, L: int) -> None:
        if self.max_len and L > self.max_len:
            raise ValueError(
                f"conv backend '{self.name}' supports L <= {self.max_len}, "
                f"got {L}"
            )

    @jax.named_scope("long_conv")
    def __call__(self, u, h, skip=None, gate=None):
        if gate is None:
            return self.fn(u, h, skip)
        if self.supports_gate:
            return self.fn(u, h, skip, gate)
        # unfused fallback: same semantics, one extra full-tensor pass —
        # external registrations work before they learn the gate protocol
        return (gate * self.fn(u, h, skip).astype(gate.dtype)).astype(u.dtype)


_BACKENDS: Dict[str, ConvBackend] = {}


def register_conv_backend(backend: ConvBackend) -> ConvBackend:
    """Duplicate names raise unless the registration is identical — silent
    shadowing of e.g. 'fft' would swap the conv under every model."""
    prev = _BACKENDS.get(backend.name)
    if prev is not None and prev != backend:
        raise ValueError(f"conv backend '{backend.name}' already registered")
    _BACKENDS[backend.name] = backend
    return backend


def conv_backend_names() -> tuple:
    return tuple(sorted(_BACKENDS))


def registered_conv_backends() -> Dict[str, ConvBackend]:
    return dict(_BACKENDS)


def get_conv_backend(name: Optional[str]) -> ConvBackend:
    """Look up a backend; ``None`` means the registry default — the
    None-means-default rule lives here, not at dispatch sites."""
    name = name or DEFAULT_BACKEND
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown conv backend '{name}'; registered: "
            f"{list(conv_backend_names())}"
        )
    return _BACKENDS[name]


def resolve_conv_backend(
    override: Optional[str] = None, *, default: str = DEFAULT_BACKEND
) -> str:
    """One resolution point for the long-conv backend name.

    Priority: explicit ``override`` > ``$REPRO_CONV_BACKEND`` > ``default``.
    The resolved name is validated against the registry — unknown names
    raise immediately (config/launch time), naming the source of the bad
    name (a typo'd env var should not read like a code bug) and the sorted
    registered list.
    """
    env = os.environ.get(ENV_VAR)
    if override:
        name, source = override, "override"
    elif env:
        name, source = env, f"${ENV_VAR}"
    else:
        name, source = default, "default"
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown conv backend '{name}' (from {source}); registered "
            f"backends: {sorted(_BACKENDS)}"
        )
    return name


# --------------------------------------------------------------- built-ins
#
# The wrappers import lazily so that e.g. the Pallas toolchain is only
# touched when the 'toeplitz' backend is actually selected.

def _fft(u, h, skip=None, gate=None):
    from repro.core.fftconv import fft_causal_conv_sharded

    return fft_causal_conv_sharded(u, h, skip, gate)


def _fft_local(u, h, skip=None, gate=None):
    from repro.core.fftconv import fft_causal_conv

    return fft_causal_conv(u, h, skip, gate)


def _direct(u, h, skip=None, gate=None):
    from repro.core.fftconv import direct_causal_conv

    return direct_causal_conv(u, h, skip, gate)


def _blockfft(u, h, skip=None, gate=None):
    from repro.core import autotune
    from repro.core.blockfft import blockfft_causal_conv, factor_candidates
    from repro.core.fftconv import next_fast_len

    factors = None
    if autotune.mode() != "off":
        N = next_fast_len(2 * u.shape[1] - 1)

        def run(factors):
            import jax.numpy as jnp

            uu = jnp.ones(u.shape, u.dtype)
            hh = jnp.ones((u.shape[2], u.shape[1]), jnp.float32)
            return blockfft_causal_conv(uu, hh, factors=tuple(factors))

        plan = autotune.plan_for(
            "blockfft", u.shape, u.dtype,
            candidates=[{"factors": list(p)} for p in factor_candidates(N)],
            run=run,
        )
        if plan:
            factors = tuple(plan["factors"])
    return blockfft_causal_conv(u, h, skip, gate, factors=factors)


def _blockfft_overlap(u, h, skip=None, gate=None):
    from repro.core import autotune
    from repro.kernels.twolevel_fft import twolevel_candidates, twolevel_fft_conv

    kw = {}
    if autotune.mode() != "off":

        def run(factors=None, overlap=2, block_d=None):
            import jax.numpy as jnp

            uu = jnp.ones(u.shape, u.dtype)
            hh = jnp.ones((u.shape[2], u.shape[1]), jnp.float32)
            return twolevel_fft_conv(
                uu, hh,
                factors=tuple(factors) if factors else None,
                overlap=overlap, block_d=block_d,
            )

        plan = autotune.plan_for(
            "twolevel", u.shape, u.dtype,
            candidates=twolevel_candidates(u.shape),
            run=run,
        )
        if plan:
            kw = dict(plan)
            if "factors" in kw:
                kw["factors"] = tuple(kw["factors"])
    return twolevel_fft_conv(u, h, skip, gate, **kw)


def _toeplitz(u, h, skip=None, gate=None):
    from repro.kernels import ops as kops

    return kops.toeplitz_conv(u, h, skip, gate)


_FFT_SP_WARNED = False


def _fft_sp(u, h, skip=None, gate=None):
    # Sequence-parallel (context-parallel) FFT conv: L sharded over the cp
    # axis ('model' unless an ExecutionContext cp_axis scope names another),
    # two all-to-alls instead of an L-sized all-gather.  Non-divisible L is
    # padded to the next multiple inside sp_fft_causal_conv and the output
    # truncated (exact by causality) — it must NOT fall back to a
    # single-device full-L FFT, which is precisely the OOM this backend
    # exists to prevent.  Off-mesh (no ambient mesh / 1-way axis) it
    # degrades to the local FFT with a one-time warning, so the parity
    # sweep can still run it on one device.  Gate+skip are fused into the
    # post-conv elementwise inside the shard_map body (supports_gate=True).
    from repro.core.fftconv import fft_causal_conv
    from repro.distributed.ctx import current_cp_axis, current_mesh
    from repro.distributed.spconv import sp_fft_causal_conv

    mesh = current_mesh()
    axis = current_cp_axis() or "model"
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        global _FFT_SP_WARNED
        if not _FFT_SP_WARNED:
            _FFT_SP_WARNED = True
            import warnings

            warnings.warn(
                "conv backend 'fft_sp' selected without a sequence-parallel "
                f"mesh axis '{axis}' — running the single-device local FFT "
                "instead (full L per chip).",
                stacklevel=2,
            )
        return fft_causal_conv(u, h, skip, gate)
    return sp_fft_causal_conv(u, h, skip, mesh, axis=axis, gate=gate)


register_conv_backend(ConvBackend(
    name="fft", tag="shard_map_fft", fn=_fft, mesh_aware=True,
    supports_gate=True,
    description="O(L log L) DFT conv on fast-composite >= 2L-1 points: "
    "XLA's real FFT off TPU, the MXU four-step DFT (blockfft) on TPU; "
    "shard_map-forced per-chip execution under a mesh; gate+skip fused "
    "into the post-inverse elementwise pass.",
))
register_conv_backend(ConvBackend(
    name="fft_local", tag="xla_fft", fn=_fft_local, supports_gate=True,
    description="the fft backend's single-device path (no shard_map), "
    "used as the oracle for the sharded variant.",
))
register_conv_backend(ConvBackend(
    name="direct", tag="toeplitz_oracle", fn=_direct, oracle=True,
    max_len=4096, supports_gate=True,
    description="O(L²) materialized lower-triangular Toeplitz matmul — "
    "the correctness oracle for tiny L.",
))
register_conv_backend(ConvBackend(
    name="blockfft", tag="matmul_dft", fn=_blockfft, supports_gate=True,
    description="four-step (Bailey) DFT with the small DFTs as "
    "fp32-exact (HIGHEST) real matmuls on the MXU and its own VJP — the "
    "fft backend's transform on TPU, here on every platform; factor "
    "split autotunable (core.autotune).",
))
register_conv_backend(ConvBackend(
    name="blockfft_overlap", tag="twolevel_overlap", fn=_blockfft_overlap,
    supports_gate=True, requires_pallas=True,
    description="overlapped two-level (inner R / outer S) FFT conv: one "
    "Pallas call pipelines the inner-block DFTs against HBM streaming "
    "and finalizes twiddle/outer-DFT/pointwise/inverse + the fused gate "
    "in VMEM (kernels/twolevel_fft.py); (R,S)/overlap/block_d "
    "autotunable as the 'twolevel' plan kind; interpret-mode off-TPU.",
))
register_conv_backend(ConvBackend(
    name="toeplitz", tag="pallas_mxu", fn=_toeplitz, requires_pallas=True,
    supports_gate=True,
    description="chunked block-Toeplitz Pallas MXU kernel (DESIGN.md §2); "
    "gate fused at kernel finalize in VMEM; interpret-mode off-TPU, jnp "
    "oracle on CPU.",
))
register_conv_backend(ConvBackend(
    name="fft_sp", tag="seqpar_fft", fn=_fft_sp, mesh_aware=True,
    supports_gate=True,
    description="sequence-parallel Cooley-Tukey FFT conv (context "
    "parallelism for 500K-token prefill AND training — differentiable via "
    "a custom VJP with the same two-all-to-all comm footprint): L sharded "
    "over the cp axis, padded to the next divisible length when needed; "
    "gate+skip fused in the shard_map epilogue; local-FFT fallback "
    "(warn-once) only when no mesh axis is available.",
))
