"""Kernel micro-bench (§4.4 supplement): interpret-mode correctness-path
timing of each Pallas kernel vs its jnp oracle, plus the conv-backend
comparison (fft vs blockfft vs toeplitz) that drives the §Perf iteration.

The gated rows measure the tentpole fusion directly: ``*_gated_fused`` runs
``backend(u, h, skip, gate)`` (gate inside the conv's elementwise epilogue /
Pallas accumulator), ``*_gated_unfused`` runs the pre-fusion schedule
``gate * backend(u, h, skip)`` — one extra full-tensor elementwise pass per
call, i.e. per Hyena order.  The delta is the acceptance artifact written to
``BENCH_conv.json`` by ``benchmarks/run.py --json`` (interpret/CPU numbers
in CI; re-run on TPU for real ones).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


# headline row for the artifact's schema-2 summary block (benchmarks/run.py);
# blockfft is the one timed backend present on every platform (fft/fft_sp
# skip without a mesh, toeplitz skips off-TPU)
HEADLINE = "kernels/conv_blockfft_gated_fused_L2048"


def _time(fn, *args, iters=5):
    jax.block_until_ready(fn(*args))  # compile + warm-up
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # min-of-iters: microbench noise floor, not mean


def run(rows):
    from repro.core.conv_api import registered_conv_backends
    from repro.kernels import ref

    B, L, D = 2, 2048, 64
    u = jax.random.normal(jax.random.PRNGKey(0), (B, L, D))
    h = jax.random.normal(jax.random.PRNGKey(1), (D, L)) / L
    skip = jax.random.normal(jax.random.PRNGKey(4), (D,)) * 0.1
    gate = jax.random.normal(jax.random.PRNGKey(5), (B, L, D))
    # conv-backend comparison straight off the registry: new backends show
    # up here (and in the §Perf iteration) with zero bench edits.
    from repro.distributed.ctx import current_mesh

    for name, backend in sorted(registered_conv_backends().items()):
        if backend.oracle or (backend.max_len and L > backend.max_len):
            continue  # O(L²) references are not a timing row at L=2048
        if backend.requires_pallas and jax.default_backend() != "tpu":
            continue  # interpret-mode timing is meaningless
        if backend.mesh_aware and current_mesh() is None:
            continue  # would fall back to the local path — duplicate row
        t = _time(jax.jit(backend.fn), u, h)
        rows.append((f"kernels/conv_{name}_L{L}", t, backend.tag or name))
        # fused gate (inside the backend) vs the pre-fusion two-pass
        # schedule; the delta == one eliminated full-tensor pass per order
        fused = jax.jit(lambda u, h, s, g, b=backend: b(u, h, s, g))
        unfused = jax.jit(
            lambda u, h, s, g, b=backend: g * b(u, h, s).astype(g.dtype)
        )
        t_f = _time(fused, u, h, skip, gate)
        t_u = _time(unfused, u, h, skip, gate)
        rows.append((
            f"kernels/conv_{name}_gated_fused_L{L}", t_f,
            f"unfused_us={t_u:.0f};saved_passes_per_order=1",
        ))
        rows.append((
            f"kernels/conv_{name}_gated_unfused_L{L}", t_u,
            backend.tag or name,
        ))

    # fusion accounting for the artifact: the gated contract removes one
    # full-tensor (B, L, D) write+read per order per layer vs the
    # pre-fusion operator (gate applied as a standalone multiply).  Inside
    # ONE xla jit the compiler fuses that multiply anyway (CPU deltas above
    # hover near zero — that is the point: bit-identical, never slower);
    # the hard win is the Pallas toeplitz kernel, where pallas_call is a
    # fusion barrier and the standalone gate multiply is a real extra HBM
    # round-trip — only measurable on TPU.
    rows.append((
        "kernels/conv_gated_fusion_accounting", 0.0,
        "eliminated_full_tensor_passes_per_forward=order*n_layers;"
        "pallas_measured_on=tpu_only",
    ))

    # overlapped two-level FFT vs the staged blockfft at Hyena training
    # lengths.  Narrow D keeps the CPU run cheap; the schedule comparison
    # is per-channel so the ratio transfers.  On CPU blockfft_overlap runs
    # its kernel body in the Pallas interpreter — the rows exist to pin
    # the artifact shape; the overlap win itself (HBM streaming hidden
    # behind the inner-DFT matmuls inside one pallas_call) is only
    # measurable on TPU.
    from repro.core.conv_api import get_conv_backend

    bf = get_conv_backend("blockfft")
    ov = get_conv_backend("blockfft_overlap")
    for Lx in (8192, 32768):
        Bx, Dx = 1, 4
        ux = jax.random.normal(jax.random.PRNGKey(6), (Bx, Lx, Dx))
        hx = jax.random.normal(jax.random.PRNGKey(7), (Dx, Lx)) / Lx
        t_bf = _time(jax.jit(bf.fn), ux, hx, iters=2)
        t_ov = _time(jax.jit(ov.fn), ux, hx, iters=2)
        rows.append((
            f"kernels/conv_blockfft_L{Lx}", t_bf,
            f"vs_overlap_us={t_ov:.0f}",
        ))
        rows.append((
            f"kernels/conv_blockfft_overlap_L{Lx}", t_ov,
            f"vs_blockfft_us={t_bf:.0f}",
        ))
    # accounting row for the two-level overlapped schedule (CI-asserted):
    # what the single-pallas_call pipeline removes relative to the staged
    # blockfft lowering, and where the numbers are real.
    rows.append((
        "kernels/conv_twolevel_overlap_accounting", 0.0,
        "pipelined_stages=inner_fft,pointwise,outer_combine;"
        "hbm_roundtrips_staged=5;hbm_roundtrips_overlapped=1;"
        "plan_kind=twolevel;cpu=pallas_interpret;"
        "measured_on=tpu_only",
    ))

    g = jax.random.normal(jax.random.PRNGKey(2), (D,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(3), (B * L, D))
    rn_t = _time(jax.jit(lambda x, g: ref.rmsnorm(x, g)), x, g)
    rows.append(("kernels/rmsnorm_ref", rn_t, "oracle"))
    return rows
