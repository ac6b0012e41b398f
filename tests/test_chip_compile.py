"""Every Pallas kernel compiles for a TPU v5e at hyena-153m widths.

The TPU compiler is installed without the chip: ``jax.experimental.
topologies`` describes a ``v5e:2x2`` host, and each kernel is lowered
natively (``interpret=False``) against one of its devices and compiled.
That catches what interpret mode cannot — blocks off the (8, 128) tiling,
scratch past the scoped VMEM limit, ops Mosaic cannot lower — at no chip
time.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# hyena-153m: B=8 sequences of L=2048 at d_model 864, bf16 activations
B, L, D = 8, 2048, 864
HEADS = 8  # attention kernels at the same width: 8 heads of 108


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache without one; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_cases():
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.short_conv import short_conv_gate
    from repro.kernels.toeplitz_conv import toeplitz_conv
    from repro.kernels.twolevel_fft import twolevel_fft_conv

    act, f32 = (B, L, D), jnp.float32
    qkv = (B, HEADS, L, D // HEADS)
    return {
        "short_conv_gate": (
            lambda u, w, g: short_conv_gate(u, w, g, interpret=False),
            [(act,), ((D, 3), f32), (act,)],
        ),
        "rmsnorm": (
            lambda x, g: rmsnorm(x, g, interpret=False),
            [(act,), ((D,), f32)],
        ),
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            [(qkv,), (qkv,), (qkv,)],
        ),
        "toeplitz_conv": (
            lambda u, h, s, g: toeplitz_conv(u, h, s, g, interpret=False),
            [(act,), ((D, L), f32), ((D,), f32), (act,)],
        ),
        "twolevel_fft_conv": (
            lambda u, h, s, g: twolevel_fft_conv(u, h, s, g,
                                                 interpret=False),
            [(act,), ((D, L), f32), ((D,), f32), (act,)],
        ),
    }


KERNELS = ("short_conv_gate", "rmsnorm", "flash_attention",
           "toeplitz_conv", "twolevel_fft_conv")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, arg_specs = _kernel_cases()[name]
    args = [_spec(one_chip, *a) for a in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    # the Pallas body really lowered to a Mosaic kernel (no XLA fallback)
    assert "tpu_custom_call" in compiled.as_text(), name


def _mlir_ops(module, name):
    """Every operation called ``name`` in an MLIR module, nested ones too."""
    found = []

    def walk(op):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    if inner.operation.name == name:
                        found.append(inner.operation)
                    walk(inner.operation)

    walk(module.operation)
    return found


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_long_conv_lowers_to_highest_dft_matmuls_for_v5e(one_chip, dtype,
                                                          monkeypatch):
    """The default ``fft`` backend's gated conv under remat, value and
    gradients, at hyena-153m widths.  Traced for a TPU and lowered for the
    described chip, its forward, recomputed forward and backward
    transforms are the four-step DFT's matmuls, each at HIGHEST even under
    an ambient bf16 matmul precision, with no XLA FFT op; traced for this
    CPU it is XLA's FFT.  Lowering only: nothing is compiled."""
    from repro.core import fftconv
    from repro.core.blockfft import SCOPE
    from repro.core.conv_api import get_conv_backend

    conv = get_conv_backend("fft")
    dt = getattr(jnp, dtype)
    shapes = [(B, L, D), (D, L), (D,), (B, L, D)]

    def lower(specs):  # a fresh function each time: traced anew
        def loss(u, h, skip, gate):
            return jnp.sum(conv(u, h, skip, gate).astype(jnp.float32))

        return jax.jit(jax.value_and_grad(
            jax.checkpoint(loss), argnums=(0, 1, 2, 3))).lower(*specs)

    cpu = lower([jax.ShapeDtypeStruct(s, dt) for s in shapes])
    assert "stablehlo.fft" in cpu.as_text()
    # the conv reads the platform when it is traced; this process's is
    # the CPU, so the test stands in the chip's answer
    monkeypatch.setattr(fftconv, "on_tpu", lambda: True)
    with jax.default_matmul_precision("bfloat16"):
        tpu = lower([_spec(one_chip, s, dt) for s in shapes])
    assert "stablehlo.fft" not in tpu.as_text()
    dots = _mlir_ops(tpu.compiler_ir("stablehlo"), "stablehlo.dot_general")
    # two stages each for u's and h's spectra and the inverse, in the
    # forward and again in the recomputed forward; in the backward, for
    # dc's spectrum, du's inverse and dh's inverse
    assert len(dots) == 18, len(dots)
    for op in dots:
        assert SCOPE in str(op.location), str(op.location)
        assert "HIGHEST" in str(op.attributes["precision_config"]), op
