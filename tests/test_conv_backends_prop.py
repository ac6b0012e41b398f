"""Cross-backend property tests: every registered ConvBackend computes the
same depthwise causal convolution, within dtype tolerance, on random
``(B, L, D)`` — including non-power-of-two and prime ``L`` (the FFT-family
backends pad to a fast composite >= 2L-1 internally; blockfft additionally
factors that length for the four-step transform, so odd/prime lengths
exercise its worst-case path).

The oracle is the O(L²) materialized Toeplitz matmul ("direct").

Gated parity (DESIGN.md §7): for every backend, the fused gated entry point
``backend(u, h, skip, gate)`` must equal the two-pass schedule
``gate * backend(u, h, skip)`` — including the padded/tail-block edges of
the Pallas kernels, which see the gate through an extra BlockSpec and must
not gate the padding rows into the live output.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import prop
from repro.core.conv_api import get_conv_backend, registered_conv_backends

# primes, odd composites, powers of two, and off-by-one straddles
LENGTHS = (1, 2, 3, 5, 7, 13, 16, 31, 33, 37, 48, 61, 64, 97, 127, 128)


def _run_all_backends(B, L, D, seed, with_skip, with_gate=False,
                      dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    # bf16 inputs round identically for every backend, but each backend
    # reassociates its fp32 internals differently before the downcast
    tol = 5e-3 if dtype == jnp.float32 else 4e-2
    u = jnp.asarray(rng.standard_normal((B, L, D)), dtype)
    h = jnp.asarray(rng.standard_normal((D, L)) / max(L, 1), jnp.float32)
    skip = (
        jnp.asarray(rng.standard_normal((D,)), jnp.float32)
        if with_skip else None
    )
    gate = (
        jnp.asarray(rng.standard_normal((B, L, D)), dtype)
        if with_gate else None
    )
    want = np.asarray(get_conv_backend("direct")(u, h, skip, gate),
                      np.float32)
    for name, backend in sorted(registered_conv_backends().items()):
        if backend.max_len and L > backend.max_len:
            continue
        got = np.asarray(backend(u, h, skip, gate), np.float32)
        np.testing.assert_allclose(
            got, want, rtol=tol, atol=tol,
            err_msg=f"backend '{name}' diverges at (B={B}, L={L}, D={D}, "
            f"seed={seed}, skip={with_skip}, gate={with_gate}, "
            f"dtype={jnp.dtype(dtype).name})",
        )
        if with_gate:
            # fused == gate * unfused, per backend (not just vs the oracle)
            two_pass = np.asarray(gate * backend(u, h, skip), np.float32)
            np.testing.assert_allclose(
                got, two_pass, rtol=tol, atol=tol,
                err_msg=f"backend '{name}' gated fusion diverges from its "
                f"own two-pass schedule at (B={B}, L={L}, D={D}, "
                f"seed={seed}, skip={with_skip}, "
                f"dtype={jnp.dtype(dtype).name})",
            )


@prop.given(
    B=prop.integers(1, 3),
    L=prop.sampled_from(LENGTHS),
    D=prop.sampled_from((1, 2, 4, 5)),
    seed=prop.integers(0, 1 << 30),
    with_skip=prop.sampled_from((True, False)),
)
def test_conv_backends_agree_random_shapes(B, L, D, seed, with_skip):
    _run_all_backends(B, L, D, seed, with_skip)


test_conv_backends_agree_random_shapes = pytest.mark.slow(
    test_conv_backends_agree_random_shapes
)


@prop.given(
    B=prop.integers(1, 3),
    L=prop.sampled_from(LENGTHS),
    D=prop.sampled_from((1, 2, 4, 5)),
    seed=prop.integers(0, 1 << 30),
    with_skip=prop.sampled_from((True, False)),
)
def test_conv_backends_gated_parity(B, L, D, seed, with_skip):
    _run_all_backends(B, L, D, seed, with_skip, with_gate=True)


test_conv_backends_gated_parity = pytest.mark.slow(
    test_conv_backends_gated_parity
)


@pytest.mark.parametrize("L", [7, 37, 61, 97])
def test_conv_backends_agree_prime_lengths(L):
    """Fast-tier pin on the prime lengths (the historically risky cases for
    padded-FFT and factored-FFT implementations)."""
    _run_all_backends(2, L, 4, seed=L, with_skip=True)


@pytest.mark.parametrize("L", [7, 33, 61, 128])
def test_conv_backends_gated_parity_fast(L):
    """Fast-tier pin of the gated-parity property (odd, straddle, prime,
    and exact-block lengths)."""
    _run_all_backends(2, L, 4, seed=1000 + L, with_skip=True, with_gate=True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("L", [13, 37, 100])
def test_conv_backends_gated_parity_dtypes(L, dtype):
    """Gated-parity grid across dtypes × odd/prime lengths (D=5 so every
    tiled backend sees a padded channel tail).  The bf16 rows pin the §7
    downcast-then-gate policy for every backend, including the two-level
    overlapped registration."""
    _run_all_backends(2, L, 5, seed=31 * L, with_skip=True, with_gate=True,
                      dtype=getattr(jnp, dtype))


def test_fft_sp_registered_with_contract():
    """The sequence-parallel conv is a first-class registry citizen: mesh
    aware, gate fused inside the shard_map epilogue (bit-identical to the
    unfused registry fallback — DESIGN.md §7/§12), and — with no ambient
    mesh — included in every sweep above via its local-FFT fallback."""
    from repro.core.conv_api import get_conv_backend

    b = get_conv_backend("fft_sp")
    assert b.mesh_aware and b.supports_gate and not b.oracle


def test_fft_sp_sharded_gated_parity_subprocess():
    """fft_sp on a REAL 8-way model mesh (subprocess, forced host devices):
    the sharded two-stage Cooley-Tukey path — not the fallback — must match
    the fft backend, gated and ungated, including an odd batch and a skip.
    This is the mesh half of the registry parity sweep (the in-process
    sweep only ever sees the meshless fallback)."""
    import os
    import subprocess
    import sys
    import textwrap

    SRC = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.conv_api import get_conv_backend
        from repro.distributed import ctx

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        fft_sp = get_conv_backend("fft_sp")
        fft = get_conv_backend("fft_local")
        B, L, D = 3, 64, 4
        rng = np.random.default_rng(0)
        u = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
        h = jnp.asarray(rng.standard_normal((D, L)) / L, jnp.float32)
        skip = jnp.asarray(rng.standard_normal((D,)), jnp.float32)
        gate = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
        with ctx.use_mesh(mesh):
            got = np.asarray(fft_sp(u, h, skip, gate))
            got_plain = np.asarray(fft_sp(u, h, skip))
        want = np.asarray(fft(u, h, skip, gate))
        want_plain = np.asarray(fft(u, h, skip))
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(got_plain, want_plain,
                                   rtol=2e-3, atol=2e-3)
        # L % 8 != 0 must fall back, not crash
        u2, h2 = u[:, :61], h[:, :61] * 0.0 + h[:, :61]
        with ctx.use_mesh(mesh):
            np.asarray(fft_sp(u2, h2, skip))
        print("OK")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-4000:]}"
    assert "OK" in proc.stdout


@pytest.mark.parametrize(
    "B,L,D,C,bd",
    [(2, 100, 33, 32, 32), (1, 96, 8, 32, 8), (2, 65, 5, 16, 4)],
)
def test_toeplitz_pallas_gated_tail_blocks(B, L, D, C, bd):
    """The gated Pallas kernel body (interpret mode) on shapes whose L / D
    pad up to the tile grid: the gate BlockSpec must track the output chunk
    through the padded tail blocks."""
    from repro.kernels import ref
    from repro.kernels.toeplitz_conv import toeplitz_conv

    rng = np.random.default_rng(L * 31 + D)
    u = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((D, L)) / L, jnp.float32)
    skip = jnp.asarray(rng.standard_normal((D,)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
    got = toeplitz_conv(
        u, h, skip, gate, chunk=C, block_d=bd, interpret=True
    )
    want = ref.toeplitz_conv(u, h, skip, gate)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_blockfft_overlap_registered_with_contract():
    """The overlapped two-level conv is a first-class registry citizen:
    gate fused at the kernel's finalize (DESIGN.md §14), never the oracle,
    and requires_pallas=True — off-TPU the kernel body runs in the Pallas
    interpreter (never another schedule), so every CPU sweep above
    exercises the real registered kernel."""
    b = get_conv_backend("blockfft_overlap")
    assert b.supports_gate and not b.oracle and b.requires_pallas
    assert b.tag == "twolevel_overlap"


@pytest.mark.parametrize(
    "B,L,D,bd,ov",
    [(2, 100, 5, 4, 2), (1, 37, 3, 2, 4), (2, 64, 4, 4, 2)],
)
def test_twolevel_pallas_gated_tail_blocks(B, L, D, bd, ov):
    """The overlapped two-level kernel BODY (interpret mode, not the CPU
    degrade path) on shapes whose D pads up to the channel tile: the
    spectrum accumulation across overlap chunks, the VMEM finalize, and
    the gate/skip BlockSpecs must all track the padded tail blocks."""
    from repro.core.blockfft import factor_candidates
    from repro.core.fftconv import next_fast_len
    from repro.kernels.twolevel_fft import twolevel_fft_conv

    N = next_fast_len(2 * L - 1)
    factors = factor_candidates(N, limit=2)[0]
    rng = np.random.default_rng(L * 7 + D)
    u = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((D, L)) / L, jnp.float32)
    skip = jnp.asarray(rng.standard_normal((D,)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
    want = np.asarray(get_conv_backend("direct")(u, h, skip, gate))
    got = twolevel_fft_conv(
        u, h, skip, gate, factors=factors, block_d=bd, overlap=ov,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=2e-4, atol=2e-4,
        err_msg=f"gated twolevel kernel (factors={factors})",
    )
    # ungated + skipless: the dummy gate row / zero skip paths
    want0 = np.asarray(get_conv_backend("direct")(u, h, None, None))
    got0 = twolevel_fft_conv(
        u, h, None, None, factors=factors, block_d=bd, overlap=ov,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got0), want0, rtol=2e-4, atol=2e-4,
        err_msg=f"ungated twolevel kernel (factors={factors})",
    )
