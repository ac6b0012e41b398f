#!/usr/bin/env python3
"""Chip smoke: hyena-153m training and serving end to end on one TPU.

    python chip_smoke.py                  # one chip: train, serve, kernels
    python chip_smoke.py --chips 4        # context-parallel train step only
    python chip_smoke.py --cpu-rehearsal  # tiny sizes on the CPU (tests)

One process drives every phase; nothing here starts a child process.  The
model is the registry's ``hyena-153m`` at its published widths (18 layers,
d_model 864, FFN 1728, order 2, vocab 50257) with random weights from
``--seed``:

  * train   — ``jit_train_step`` with the default ``TrainConfig`` (bf16
              policy, remat) for 5 steps on one fixed batch of B=8,
              L=2048; every loss finite and the last below the first.
  * serve   — a ``ServeEngine`` (4 slots, max_len 1024) answers 4 greedy
              requests of 128–512 prompt tokens and 32 new tokens; its
              tokens must equal the static-batch ``generate()`` reference
              (up to a genuine bf16 near-tie, as tests/serve_parity.py).
  * kernels — every Pallas kernel compiled and run natively at hyena-153m
              widths, each against an independent reference: its jnp
              oracle (``repro.kernels.ref``), or the XLA FFT conv for the
              two long-conv kernels.
  * cp      — (``--chips 4`` only) ``TrainConfig(cp_axis="model")`` on a
              (1, 4) mesh, B=2, L=4×2048, hyena convs through ``fft_sp``;
              loss and grad norm match the same step on one device.

Every failed check raises, so the exit code is non-zero and no result line
is printed.  Without a TPU the script exits 2 before any phase, unless
``--cpu-rehearsal`` is given: that mode runs the same phases at tiny sizes
on whatever JAX finds and never reports ``"platform": "tpu"``.  The last
line of a successful run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.

The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when it is set and
``<checkout>/.jax_cache`` otherwise (``repro.common.compile_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# ServeEngine tokens vs generate(): a mismatch is accepted only where the
# reference's top-2 logit gap is a genuine near-tie that a different
# program order can flip — under bf16, within two bf16 ulps of the top
# logit; under fp32 with exact matmuls, below tests/serve_parity's TIE_TOL
TIE_ULPS = 2
BF16_EPS = 2.0 ** -8
TIE_TOL_FP32 = 1e-4
# --chips 4: cp step vs the one-device step, both under the bf16 policy:
# loss within one bf16 ulp, grad norm within four (relative)
CP_LOSS_RTOL = BF16_EPS
CP_GNORM_RTOL = 4 * BF16_EPS


@dataclasses.dataclass(frozen=True)
class Sizes:
    arch_reduced: bool  # True: the registry config's .reduced() form
    vocab: int  # 0 = the config's own
    batch: int
    seq: int
    steps: int
    max_len: int
    prompt_lens: tuple
    new_tokens: int
    kernel_shape: tuple  # (B, L, D) for the kernel phase
    cp_batch: int
    cp_seq_per_chip: int


FULL = Sizes(
    arch_reduced=False, vocab=0, batch=8, seq=2048, steps=5, max_len=1024,
    prompt_lens=(128, 512, 128, 512), new_tokens=32,
    kernel_shape=(8, 2048, 864), cp_batch=2, cp_seq_per_chip=2048,
)
TINY = Sizes(
    arch_reduced=True, vocab=64, batch=2, seq=32, steps=3, max_len=32,
    prompt_lens=(5, 12, 5, 12), new_tokens=4,
    kernel_shape=(2, 64, 16), cp_batch=2, cp_seq_per_chip=16,
)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def peak_bytes(dev):
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    return None if not stats else stats.get("peak_bytes_in_use")


def model_config(sz: Sizes):
    from repro.configs import get_config

    cfg = get_config("hyena-153m")
    if sz.arch_reduced:
        cfg = cfg.reduced()
    if sz.vocab:
        cfg = dataclasses.replace(cfg, vocab_size=sz.vocab)
    return cfg


def fixed_batch(seed: int, B: int, L: int, vocab: int):
    import jax.numpy as jnp
    import numpy as np

    toks = np.random.default_rng(seed).integers(0, vocab, (B, L + 1))
    return {
        "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
        "labels": jnp.asarray(toks[:, 1:], jnp.int32),
    }


def init_state(cfg, tcfg, seed: int):
    import jax

    from repro.train.trainer import init_train_state

    return jax.jit(lambda k: init_train_state(k, cfg, tcfg)[0])(
        jax.random.PRNGKey(seed)
    )


# ------------------------------------------------------------------ train

def train_phase(cfg, sz: Sizes, seed: int, dev) -> None:
    import jax
    import numpy as np

    from repro.train import optim as O
    from repro.train.trainer import TrainConfig, jit_train_step

    tcfg = TrainConfig(
        optimizer=O.AdamWConfig(warmup_steps=0, total_steps=sz.steps),
    )
    log(f"[train] {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"d_ff={cfg.d_ff} order={cfg.hyena_order} vocab={cfg.vocab_size} "
        f"B={sz.batch} L={sz.seq} policy=bf16 remat={tcfg.remat}")
    t0 = time.perf_counter()
    state = init_state(cfg, tcfg, seed)
    batch = fixed_batch(seed, sz.batch, sz.seq, cfg.vocab_size)
    jax.block_until_ready(state)
    log(f"[train] init_s={time.perf_counter() - t0:.3f}")

    t0 = time.perf_counter()
    step = jit_train_step(cfg, tcfg).lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    mem = step.memory_analysis()
    log(f"[train] compile_s={compile_s:.3f} "
        f"argument_bytes={getattr(mem, 'argument_size_in_bytes', None)} "
        f"temp_bytes={getattr(mem, 'temp_size_in_bytes', None)}")

    losses, times = [], []
    for i in range(sz.steps):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])  # waits for the step
        times.append(time.perf_counter() - t0)
        losses.append(loss)
        log(f"[train] step={i} loss={loss:.6f} "
            f"grad_norm={float(metrics['grad_norm']):.6f} "
            f"step_s={times[-1]:.6f}")
    tok = sz.batch * sz.seq
    steady = times[1:] or times
    log(f"[train] tokens_per_step={tok} "
        f"steady_step_s={sum(steady) / len(steady):.6f} "
        f"tokens_per_s={tok * len(steady) / sum(steady):.1f} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    del state, step


# ---------------------------------------------------------------- kernels

def kernels_phase(sz: Sizes, seed: int) -> None:
    """Every Pallas kernel, natively lowered, against a reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.fftconv import fft_causal_conv
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.short_conv import short_conv_gate
    from repro.kernels.toeplitz_conv import toeplitz_conv
    from repro.kernels.twolevel_fft import twolevel_fft_conv

    B, L, D = sz.kernel_shape
    H = 8 if D % 8 == 0 else 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    u = jax.random.normal(ks[0], (B, L, D), jnp.float32)
    g = jax.random.normal(ks[1], (B, L, D), jnp.float32)
    h = jax.random.normal(ks[2], (D, L), jnp.float32) / L
    skip = jax.random.normal(ks[3], (D,), jnp.float32)
    w = jax.random.normal(ks[4], (D, 3), jnp.float32)
    scale = jax.random.normal(ks[5], (D,), jnp.float32)
    q, k, v = (
        jax.random.normal(kk, (B, H, L, D // H), jnp.float32)
        for kk in jax.random.split(ks[6], 3)
    )
    cases = {
        "short_conv_gate": (short_conv_gate, ref.short_conv_gate,
                            (u, w, g)),
        "rmsnorm": (rmsnorm, ref.rmsnorm, (u, scale)),
        "flash_attention": (flash_attention, ref.flash_attention, (q, k, v)),
        # the long convs against the plain XLA FFT conv (the dense
        # Toeplitz oracle would hold a (D, L, L) operator)
        "toeplitz_conv": (toeplitz_conv, fft_causal_conv, (u, h, skip, g)),
        "twolevel_fft_conv": (twolevel_fft_conv, fft_causal_conv,
                              (u, h, skip, g)),
    }
    for name, (kernel, oracle, args) in cases.items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(kernel(*args))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(kernel(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("float32"):
            oracle = jax.jit(oracle)
            want = np.asarray(oracle(*args), np.float32)
            t0 = time.perf_counter()
            jax.block_until_ready(oracle(*args))
            ref_s = time.perf_counter() - t0
        got = np.asarray(got, np.float32)
        err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                     1e-30))
        log(f"[kernels] {name} shape={tuple(args[0].shape)} "
            f"compile_and_first_s={first_s:.3f} run_s={run_s:.6f} "
            f"reference_run_s={ref_s:.6f} "
            f"max_err_over_max_ref={err:.3e}")
        check(np.all(np.isfinite(got)), f"{name}: non-finite output")
        check(err < 2e-2, f"{name}: max error {err:.3e} vs the oracle")


# ------------------------------------------------------------------ serve

def _near_tie_ok(got, want, gaps, tie_tol, label) -> int:
    """Token-identical up to the first reference near-tie, after which the
    histories legitimately diverge (tests/serve_parity.compare_request).
    Returns the number of leading tokens that matched."""
    for i, tok in enumerate(got):
        check(i < len(want), f"{label}: {len(got)} tokens > {len(want)}")
        if tok != want[i]:
            check(gaps[i] < tie_tol[i],
                  f"{label}: token {i} diverged ({tok} != {want[i]}) with a "
                  f"decision margin {gaps[i]:.4e} >= {tie_tol[i]:.4e}")
            log(f"[serve] {label}: near-tie at token {i} (margin "
                f"{gaps[i]:.4e} < {tie_tol[i]:.4e}); comparison stops there")
            return i
    check(len(got) == len(want), f"{label}: {len(got)} != {len(want)}")
    return len(got)


def serve_phase(cfg, sz: Sizes, seed: int, dev) -> None:
    """The engine against generate() twice: under the bf16 serving policy,
    where random weights leave many one-ulp near-ties, and under fp32 with
    exact matmuls, where a divergence past a 1e-4 margin is a real bug."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.common.param import split_params
    from repro.models import lm

    t0 = time.perf_counter()
    params, _ = split_params(
        jax.jit(lambda k: lm.init_lm(k, cfg))(jax.random.PRNGKey(seed))
    )
    jax.block_until_ready(params)
    log(f"[serve] init_s={time.perf_counter() - t0:.3f} n_slots=4 "
        f"max_len={sz.max_len} new_tokens={sz.new_tokens} "
        f"prompt_lens={list(sz.prompt_lens)}")
    rng = np.random.default_rng(seed + 1)
    prompts = [
        rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        for n in sz.prompt_lens
    ]
    _serve_compare(params, cfg, sz, seed, dev, prompts, "bf16",
                   jnp.bfloat16, None)
    with jax.default_matmul_precision("highest"):
        _serve_compare(params, cfg, sz, seed, dev, prompts, "fp32",
                       jnp.float32, TIE_TOL_FP32)


def _serve_compare(params, cfg, sz, seed, dev, prompts, tag, dtype,
                   fixed_tol) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.engine import ServeConfig, ServeEngine, generate

    scfg = ServeConfig(max_len=sz.max_len, n_slots=4, temperature=0.0,
                       cache_dtype=dtype)

    def run_engine():
        eng = ServeEngine(params, cfg, scfg, seed=seed)
        rids = [eng.submit(p, max_new_tokens=sz.new_tokens) for p in prompts]
        t0 = time.perf_counter()
        out = eng.drain()
        wall = time.perf_counter() - t0
        for rid in rids:
            res = eng.result(rid)
            check(res is not None and res.status == "completed",
                  f"request {rid}: {res}")
        return [[int(t) for t in out[r]] for r in rids], wall

    toks_cold, cold_s = run_engine()  # compiles every prefill length
    toks, warm_s = run_engine()
    n_tok = sum(len(t) for t in toks)
    log(f"[serve] {tag} engine cold_s={cold_s:.3f} (compile included) "
        f"warm_s={warm_s:.3f} tokens={n_tok} "
        f"tokens_per_s={n_tok / warm_s:.1f} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    check(toks == toks_cold, f"{tag}: engine tokens changed between runs")

    ref = jax.jit(lambda p, x: generate(
        p, cfg, x, scfg=scfg, max_new_tokens=sz.new_tokens,
    ))
    t0 = time.perf_counter()
    want = [
        [int(t) for t in np.asarray(ref(params, jnp.asarray(p[None])))[0]]
        for p in prompts
    ]
    log(f"[serve] {tag} generate() reference_s="
        f"{time.perf_counter() - t0:.3f} (compile included)")
    n_same = sum(a == b for a, b in zip(toks, want))
    log(f"[serve] {tag} requests token-identical to generate(): "
        f"{n_same}/{len(prompts)}")
    matched = []
    for i, (got, exp) in enumerate(zip(toks, want)):
        if got == exp:
            matched.append(len(got))
            continue
        gaps, tops = _reference_margins(params, cfg, scfg, prompts[i],
                                        sz.new_tokens)
        tol = [
            fixed_tol if fixed_tol is not None
            else TIE_ULPS * BF16_EPS * max(1.0, abs(t)) for t in tops
        ]
        matched.append(_near_tie_ok(got, exp, gaps, tol,
                                    f"{tag} request {i}"))
    log(f"[serve] {tag} leading tokens matched per request: {matched}")


def _reference_margins(params, cfg, scfg, prompt, n):
    """Per-step (top-2 gap, top logit) of generate()'s own program."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    @jax.jit
    def run(params, prompt):
        ctx = scfg.apply_context()
        p = ctx.cast_compute(params)
        compute = ctx.compute_dtype or scfg.cache_dtype
        logits, caches = lm.prefill(
            p, cfg, prompt, scfg.max_len, dtype=scfg.cache_dtype,
            compute_dtype=compute, ctx=ctx,
        )

        def body(carry, _):
            lg, caches = carry
            lg = lg.astype(jnp.float32)
            top2 = jax.lax.top_k(lg, 2)[0]
            tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            nxt, caches = lm.decode_step(
                p, cfg, tok, caches, compute_dtype=compute, ctx=ctx,
            )
            return (nxt, caches), (top2[:, 0] - top2[:, 1], top2[:, 0])

        _, (gaps, tops) = jax.lax.scan(
            body, (logits[:, -1], caches), None, length=n,
        )
        return gaps[:, 0], tops[:, 0]

    gaps, tops = run(params, jnp.asarray(prompt[None]))
    return [float(x) for x in gaps], [float(x) for x in tops]


# --------------------------------------------------------------------- cp

def cp_phase(cfg, sz: Sizes, seed: int, n_chips: int) -> None:
    """The context-parallel train step on an (1, n) Auto mesh vs the same
    step on one device of the host."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.train import optim as O
    from repro.train.trainer import (
        TrainConfig, abstract_train_state, make_train_step,
    )

    devs = jax.devices()[:n_chips]
    mesh = make_mesh((1, n_chips), ("data", "model"), devices=devs)
    L = sz.cp_seq_per_chip * n_chips
    base = TrainConfig(optimizer=O.AdamWConfig(warmup_steps=0))
    cp = dataclasses.replace(base, cp_axis="model")
    log(f"[cp] mesh=(1,{n_chips}) ('data','model') B={sz.cp_batch} L={L} "
        f"tokens_per_chip={sz.cp_batch * sz.cp_seq_per_chip} policy=bf16 "
        f"remat={cp.remat}")
    state = init_state(cfg, base, seed)
    batch = fixed_batch(seed, sz.cp_batch, L, cfg.vocab_size)

    # one device: plain jit, everything on device 0
    t0 = time.perf_counter()
    one = jax.jit(make_train_step(cfg, base)).lower(state, batch).compile()
    log(f"[cp] one_device compile_s={time.perf_counter() - t0:.3f}")
    t0 = time.perf_counter()
    _, m1 = one(state, batch)
    loss1, gn1 = float(m1["loss"]), float(m1["grad_norm"])
    log(f"[cp] one_device loss={loss1:.6f} grad_norm={gn1:.6f} "
        f"step_s={time.perf_counter() - t0:.6f}")
    del one, m1

    ectx = cp.apply_context(mesh=mesh)
    _, axes = abstract_train_state(cfg, cp)
    state_cp = ectx.place(state, ectx.train_state_shardings(axes, state))
    batch_cp = {
        k: jax.device_put(v, ectx.data_sharding(v.ndim, v.shape[0],
                                                v.shape[1]))
        for k, v in batch.items()
    }
    everyone = set(mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves((state_cp, batch_cp)):
        check(leaf.sharding.device_set == everyone,
              f"leaf {leaf.shape} placed on {leaf.sharding.device_set}")
    for k, v in batch_cp.items():
        per_chip = v.sharding.shard_shape(v.shape)
        check(per_chip[1] == sz.cp_seq_per_chip,
              f"batch '{k}' not sequence-sharded: {per_chip}")
    sharded = sum(
        1 for leaf in jax.tree_util.tree_leaves(state_cp)
        if leaf.sharding.shard_shape(leaf.shape) != leaf.shape
    )
    log(f"[cp] state leaves split across chips: {sharded}; batch per chip "
        f"{batch_cp['tokens'].sharding.shard_shape(batch['tokens'].shape)}")
    check(sharded > 0, "no train-state leaf is split across the mesh")

    with ectx.scope():
        t0 = time.perf_counter()
        step = jax.jit(make_train_step(cfg, cp)).lower(
            state_cp, batch_cp).compile()
        log(f"[cp] cp compile_s={time.perf_counter() - t0:.3f}")
        hlo = step.as_text()
        t0 = time.perf_counter()
        _, m = step(state_cp, batch_cp)
        loss, gn = float(m["loss"]), float(m["grad_norm"])
        log(f"[cp] cp loss={loss:.6f} grad_norm={gn:.6f} "
            f"step_s={time.perf_counter() - t0:.6f}")
    n_coll = sum(hlo.count(op) for op in (
        "all-reduce", "all-gather", "collective-permute", "all-to-all",
        "reduce-scatter"))
    log(f"[cp] collectives in the compiled step: {n_coll}")
    check(n_coll > 0, "the cp step compiled without collectives")
    dl = abs(loss - loss1) / abs(loss1)
    dg = abs(gn - gn1) / abs(gn1)
    log(f"[cp] rel_dloss={dl:.3e} (tol {CP_LOSS_RTOL:g}) "
        f"rel_dgrad_norm={dg:.3e} (tol {CP_GNORM_RTOL:g})")
    check(np.isfinite(loss) and np.isfinite(gn), "non-finite cp step")
    check(dl < CP_LOSS_RTOL, f"cp loss {loss} vs one device {loss1}")
    check(dg < CP_GNORM_RTOL, f"cp grad norm {gn} vs one device {gn1}")


# ------------------------------------------------------------------- main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the context-parallel train step")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on any backend; never reports a TPU")
    args = ap.parse_args()

    from repro.common import compile_cache

    cache_dir = compile_cache.enable()
    import jax

    devs = jax.devices()
    dev = devs[0]
    platform = dev.platform
    if args.cpu_rehearsal:
        if platform == "tpu":
            sys.exit("chip_smoke: --cpu-rehearsal is for hosts without a TPU")
        sz = TINY
    else:
        if platform != "tpu":
            print(f"chip_smoke: no TPU found (JAX platform {platform!r}); "
                  f"nothing was run", file=sys.stderr)
            return 2
        sz = FULL
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devs)}")
    log(f"[device] platform={platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__} compile_cache={cache_dir}")

    cfg = model_config(sz)
    t_all = time.perf_counter()
    if args.chips == 4:
        cp_phase(cfg, sz, args.seed, 4)
    else:
        for name, run in (
            ("train", lambda: train_phase(cfg, sz, args.seed, dev)),
            ("serve", lambda: serve_phase(cfg, sz, args.seed, dev)),
            ("kernels", lambda: kernels_phase(sz, args.seed)),
        ):
            t0 = time.perf_counter()
            run()
            log(f"[{name}] phase_wall_s={time.perf_counter() - t0:.3f}")
    log(f"[all] wall_s={time.perf_counter() - t_all:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind, "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
