"""Context-parallel training throughput + memory: cp=1 vs cp=P at fixed
per-chip tokens (weak scaling — the whole point of cp is that the *global*
sequence grows with the mesh while per-chip activation bytes stay flat).

Two measured rows plus one analysis row:

  * ``train/cp1``  — single-device train step at L = tokens_per_chip.
  * ``train/cpP``  — the same model, L = tokens_per_chip · P, sequence
    sharded over the cp axis of a (1, P) mesh through the full
    ``make_train_step`` path (fft_sp conv VJP, ring attention if the
    pattern has any, halo-exchanged targets).
  * ``train/unsharded_at_cpP_len`` — NOT executed: the single-device step
    *lowered* at the cp=P length, so the artifact records the estimated
    peak (temp) bytes the cp run avoids.  At real lengths this is the
    configuration that OOMs; here it documents the ratio.

Plus the reversible-substrate pair (DESIGN.md §15), lowered-only at a
deeper stack (``--rev-depth``, default 16 — the regime where depth-resident
activations dominate):

  * ``train/standard_deep``   — remat'd single-stream scan at depth D.
  * ``train/reversible_deep`` — the same model with ``reversible=True``:
    the coupling custom_vjp's residuals are O(1) in depth, so
    ``peak_bytes`` must come out *below* the standard row (asserted by the
    CI fast tier), and ``compile_s`` records what the reconstruct-and-
    recompute backward costs at trace/compile time.

The deep pair uses its own ``--rev-pattern`` (default ``hyena``, the
paper's operator): attention rows would dominate the peak with
depth-independent L^2 score temps and mask the depth-resident carry the
pair exists to measure; hyena's O(L log L) FFT temps keep it visible
(standard grows linearly in depth, reversible stays flat).

Peak-memory numbers come from ``compiled.memory_analysis()`` (XLA's
buffer-assignment peak; ``temp_size_in_bytes``); every row also carries
``compile_s`` (wall seconds for ``lowered.compile()``).  CPU-to-CPU
comparable only — rerun on TPU for real numbers, like the other BENCH
artifacts.

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/bench_train.py \
        --json BENCH_train.json
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="cp axis size; with JAX_PLATFORMS=cpu, the forced "
                         "host device count")
    ap.add_argument("--tokens-per-chip", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--pattern", default="hyena",
                    help="comma-separated mixer pattern")
    ap.add_argument("--steps", type=int, default=3,
                    help="timed steps after the compile step")
    ap.add_argument("--rev-depth", type=int, default=16,
                    help="layer count for the reversible-vs-standard pair")
    ap.add_argument("--rev-seq-len", type=int, default=2048,
                    help="sequence length for the reversible-vs-standard pair")
    ap.add_argument("--rev-pattern", default="hyena",
                    help="mixer pattern for the reversible-vs-standard pair")
    ap.add_argument("--json", default=None, metavar="PATH")
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # the CPU record runs the cp mesh on virtual host devices; on an
        # accelerator the mesh is made of the real chips
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}",
        )
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.policy import FP32
    from repro.configs.base import ModelConfig
    from repro.launch.mesh import make_mesh
    from repro.train import optim as O
    from repro.train.trainer import (
        TrainConfig, init_train_state, make_train_step,
    )

    P_sz = args.devices
    if len(jax.devices()) < P_sz:
        raise SystemExit(
            f"--devices {P_sz} needs {P_sz} devices; "
            f"{jax.default_backend()} has {len(jax.devices())}"
        )
    pattern = tuple(args.pattern.split(","))
    cfg = ModelConfig(
        name="bench-cp", family="bench",
        n_layers=args.layers, d_model=args.d_model,
        n_heads=4, n_kv_heads=2, head_dim=args.d_model // 4,
        d_ff=2 * args.d_model, vocab_size=256, pattern=pattern,
        local_window=256, ssm_state=16, ssd_head_dim=16,
        rnn_width=args.d_model, hyena_filter_width=16, hyena_pos_dim=9,
    )
    opt = O.AdamWConfig(lr=1e-3, warmup_steps=0)
    rows = []
    errors = []

    def run_case(name, tcfg, L, mesh=None, execute=True, model_cfg=None):
        mcfg = model_cfg or cfg
        ectx = tcfg.apply_context(mesh=mesh)
        state, axes = init_train_state(jax.random.PRNGKey(0), mcfg, tcfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, L), 0, mcfg.vocab_size
        )
        # no labels on purpose: exercises the in-step halo-exchanged
        # next-token targets under cp
        batch = {"tokens": tokens}
        if mesh is not None:
            state = ectx.place(state, ectx.train_state_shardings(axes, state))
            batch = {
                k: jax.device_put(
                    v, ectx.data_sharding(v.ndim, v.shape[0], v.shape[1])
                )
                for k, v in batch.items()
            }
        step = jax.jit(make_train_step(mcfg, tcfg))
        with ectx.scope():
            lowered = step.lower(state, batch)
            tc0 = time.perf_counter()
            compiled = lowered.compile()
            compile_s = time.perf_counter() - tc0
            mem = compiled.memory_analysis()
            peak = int(getattr(mem, "temp_size_in_bytes", 0)) if mem else None
            if not execute:
                return {
                    "name": name, "seq_len": L, "cp": P_sz if mesh else 1,
                    "tok_s": None, "peak_bytes": peak,
                    "compile_s": round(compile_s, 3), "executed": False,
                }
            state, m = compiled(state, batch)  # compile+warm
            jax.block_until_ready(m["loss"])
            t0 = time.perf_counter()
            for _ in range(args.steps):
                state, m = compiled(state, batch)
            jax.block_until_ready(m["loss"])
            dt = (time.perf_counter() - t0) / args.steps
        toks = args.batch * L
        return {
            "name": name, "seq_len": L, "cp": P_sz if mesh else 1,
            "tok_s": toks / dt, "step_ms": dt * 1e3,
            "peak_bytes": peak, "compile_s": round(compile_s, 3),
            "loss": float(m["loss"]), "executed": True,
        }

    base = TrainConfig(optimizer=opt, remat=False, policy=FP32)
    L1 = args.tokens_per_chip
    Lbig = args.tokens_per_chip * P_sz
    try:
        rows.append(run_case("train/cp1", base, L1))
    except Exception as e:  # pragma: no cover
        errors.append(f"train/cp1: {e!r}")
    try:
        mesh = make_mesh((1, P_sz), ("data", "model"))
        cp = dataclasses.replace(base, cp_axis="model")
        rows.append(run_case("train/cpP", cp, Lbig, mesh=mesh))
    except Exception as e:  # pragma: no cover
        errors.append(f"train/cpP: {e!r}")
    try:
        rows.append(
            run_case("train/unsharded_at_cpP_len", base, Lbig, execute=False)
        )
    except Exception as e:  # pragma: no cover
        errors.append(f"train/unsharded_at_cpP_len: {e!r}")

    # reversible-vs-standard at depth where activations dominate: lowered
    # only (the numbers of record are peak temp bytes + compile seconds)
    deep_cfg = dataclasses.replace(
        cfg, n_layers=args.rev_depth,
        pattern=tuple(args.rev_pattern.split(",")),
    )
    std_deep = TrainConfig(optimizer=opt, remat=True, policy=FP32)
    rev_deep = dataclasses.replace(std_deep, reversible=True)
    try:
        rows.append(run_case("train/standard_deep", std_deep,
                             args.rev_seq_len, execute=False,
                             model_cfg=deep_cfg))
    except Exception as e:  # pragma: no cover
        errors.append(f"train/standard_deep: {e!r}")
    try:
        rows.append(run_case("train/reversible_deep", rev_deep,
                             args.rev_seq_len, execute=False,
                             model_cfg=deep_cfg))
    except Exception as e:  # pragma: no cover
        errors.append(f"train/reversible_deep: {e!r}")

    for r in rows:
        tok = "-" if r["tok_s"] is None else f"{r['tok_s']:12.0f}"
        pk = "-" if r["peak_bytes"] is None else f"{r['peak_bytes']:>14d}"
        print(f"{r['name']:28s} L={r['seq_len']:>7d} cp={r['cp']} "
              f"tok/s={tok} peak_bytes={pk}")
    if args.json:
        # schema 2: one scalar headline (the executed context-parallel
        # step's throughput) for perf-trajectory tooling
        cpP = next((r for r in rows if r["name"] == "train/cpP"), None)
        std = next((r for r in rows if r["name"] == "train/standard_deep"),
                   None)
        rev = next((r for r in rows if r["name"] == "train/reversible_deep"),
                   None)
        rev_ratio = (
            None if not (std and rev and std.get("peak_bytes")
                         and rev.get("peak_bytes") is not None)
            else round(rev["peak_bytes"] / std["peak_bytes"], 4)
        )
        artifact = {
            "schema": 2,
            "summary": {
                "train": {
                    "metric": "train/cpP",
                    "value": (
                        None if cpP is None or cpP["tok_s"] is None
                        else round(cpP["tok_s"], 1)
                    ),
                    "unit": "tok_s",
                },
                "reversible": {
                    "metric": "train/reversible_deep peak over standard",
                    "value": rev_ratio,
                    "unit": "peak_bytes_ratio",
                },
            },
            "rev_depth": args.rev_depth,
            "rev_pattern": args.rev_pattern.split(","),
            "device": jax.devices()[0].platform,
            "devices": P_sz,
            "tokens_per_chip": args.tokens_per_chip,
            "pattern": list(pattern),
            "note": "CPU forced-host-device numbers; CI-to-CI comparable "
                    "only. peak_bytes = XLA buffer-assignment temp size.",
            "rows": rows,
            "errors": errors,
        }
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json} ({len(rows)} rows)")
    if errors:
        raise SystemExit("bench_train rows failed:\n" + "\n".join(errors))


if __name__ == "__main__":
    main()
