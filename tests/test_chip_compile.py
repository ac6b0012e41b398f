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
