"""Training cell: the program's jitted train step (``jit_train_step`` with
the default ``TrainConfig``: remat, AdamW, and the precision policy and
matmul precision that the traffic file states, bf16 by default) fed by
``lm_data.TokenStream`` through ``lm_data.Prefetcher`` over a corpus made
from the seed.

Set-up makes the weights, compiles the step, and drives it through its
first ``checked_steps`` steps through the same call and feed the window
uses; their losses, the first step's gradient (read back from the Adam
state) and the parameters' change are kept.  The window then runs whole
steps until ``--seconds`` have passed, one step in flight.  After it the
device is freed and the float32 reference repeats the checked steps from
the same weights and batches.

Numbers compared:
  * ``loss_gap``: the largest relative gap of a checked step's loss;
  * ``grad_gap``: over leaves, the largest gap between the program's and
    the reference's first-gradient norms, over the larger of the
    reference's norm of that leaf and of the median leaf;
  * ``update_gap``: the same for the norm of the parameters' change over
    the checked steps, leaving out leaves whose reference gradient is
    under a thousandth of the median leaf's (they move by round-off).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List

import numpy as np

from bench import drivers as D
from bench import reference as ref

SMALL_LEAF = 1e-3


def make_corpus(seed: int, n_tokens: int, vocab: int,
                zipf_a: float) -> np.ndarray:
    """Tokens drawn from a Zipf law over a seed-permuted vocabulary."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1) ** zipf_a
    cdf = np.cumsum(p / p.sum())
    ranks = np.minimum(np.searchsorted(cdf, rng.random(n_tokens)), vocab - 1)
    return rng.permutation(vocab).astype(np.int32)[ranks]


def build_step(cfg, tcfg, state, batch) -> Callable:
    """The program's compiled train step (the timed path)."""
    from repro.train.trainer import jit_train_step

    return jit_train_step(cfg, tcfg).lower(state, batch).compile()


def feed(batch: Dict[str, np.ndarray]):
    import jax

    return {k: jax.device_put(v) for k, v in batch.items()}


def gaps(prog, want, include=None) -> float:
    """Worst leaf: |prog - want| / max(want, median(want))."""
    prog, want = np.asarray(prog, np.float64), np.asarray(want, np.float64)
    if include is not None:
        prog, want = prog[include], want[include]
    floor = np.median(want)
    return float(np.max(np.abs(prog - want) / np.maximum(want, floor)))


def program_readings(run: D.Run, cfg, d: ref.Dims, tr: Dict[str, Any],
                     corpus: np.ndarray, on_window=None) -> Dict[str, Any]:
    """Set up, drive the checked steps, run the window (``on_window``),
    and return the program's readings; frees the device."""
    from repro.train.trainer import TrainConfig

    with D.program_precision(tr):
        return _program_readings(run, cfg, d, tr, corpus, on_window,
                                 TrainConfig(policy=D.policy(tr)))


def _program_readings(run, cfg, d, tr, corpus, on_window, tcfg):
    import jax

    from repro.data.lm_data import Prefetcher, TokenStream
    from repro.train import optim as O
    from repro.train.trainer import abstract_train_state

    init, params = D.init_weights(d, run.seed)
    D.check_layout(params, abstract_train_state(cfg, tcfg)[0]["params"])
    state = jax.jit(lambda p: {"params": p, "opt": O.init_adamw(p)},
                    donate_argnums=0)(params)
    del params
    B, L = tr["batch"], tr["seq_len"]
    stream = TokenStream(corpus, global_batch=B, seq_len=L, seed=run.seed)
    pf = Prefetcher(stream, depth=2)
    try:
        first = pf.next()
        step = build_step(cfg, tcfg, state, feed(first))
        norms = jax.jit(ref.leaf_norms)
        diffs = jax.jit(ref.diff_norms)
        batches, losses = [], []
        batch = first
        for i in range(tr["checked_steps"]):
            if i:
                batch = pf.next()
            batches.append(batch)
            state, m = step(state, feed(batch))
            losses.append(float(m["loss"]))
            if i == 0:
                gnorm = float(m["grad_norm"])
                m1 = np.asarray(norms(state["opt"]["m"]))
        p0 = init(ref.seed_words(run.seed))
        dp = np.asarray(diffs(state["params"], p0))
        del p0
        opt = tcfg.optimizer
        scale = min(1.0, opt.clip_norm / (gnorm + 1e-9))
        g1 = m1 / (1.0 - opt.b1) / scale
        out = {"losses": losses, "grad1": g1, "update": dp,
               "batches": batches, "optimizer": opt}
        if on_window is not None:
            out["window"] = on_window(step, state, pf)
    finally:
        pf.close()
    del state, step
    D.free_device()
    return out


def window(run: D.Run, B: int, L: int):
    """The measured window, as a callback of ``program_readings``."""

    def go(step, state, pf):
        setup_s = time.perf_counter() - run.t_start
        losses: List[float] = []
        with D.traced(run.trace) as tr, D.count_compiles() as cc:
            with D.span("window"):
                t0 = time.perf_counter()
                pending = None
                n = 0
                while True:
                    with D.span("data"):
                        batch = feed(pf.next())
                    with D.span("train_step"):
                        state, m = step(state, batch)
                    n += 1
                    if pending is not None:
                        with D.span("sync"):
                            losses.append(float(pending))
                    pending = m["loss"]
                    if time.perf_counter() - t0 >= run.seconds:
                        break
                with D.span("sync"):
                    losses.append(float(pending))
                t1 = time.perf_counter()
        from bench.run import device_info

        return {"setup_s": setup_s, "steps": n, "window_s": t1 - t0,
                "tokens": n * B * L, "losses": losses,
                "device": device_info(run.chips), "reduced": tr.reduced,
                "compiles": cc.n}

    return go


def reference_readings(d: ref.Dims, opt, batches, seed: int,
                       prec: ref.Precision = ref.FP32,
                       matmul: str = "highest") -> Dict[str, Any]:
    """The reference's checked steps from the same weights and batches."""
    import jax
    import jax.numpy as jnp

    ropt = ref.AdamW(lr=opt.lr, b1=opt.b1, b2=opt.b2, eps=opt.eps,
                     weight_decay=opt.weight_decay, clip_norm=opt.clip_norm,
                     warmup_steps=opt.warmup_steps,
                     total_steps=opt.total_steps,
                     min_lr_ratio=opt.min_lr_ratio)
    if opt.schedule != "cosine":
        raise ValueError(f"reference has no {opt.schedule!r} schedule")
    with jax.default_matmul_precision(matmul):
        init, p = D.init_weights(d, seed)
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
        m, v = zeros(p), zeros(p)
        step = jax.jit(ref.make_train_step(d, ropt, prec),
                       donate_argnums=(0, 1, 2))
        losses, g1 = [], None
        for i, b in enumerate(batches):
            t = i + 1
            p, m, v, ce, gn = step(p, m, v, jnp.asarray(b["tokens"]),
                                   jnp.asarray(b["labels"]),
                                   jnp.float32(t), jnp.float32(ropt.lr_at(t)))
            losses.append(float(ce))
            if g1 is None:
                g1 = np.asarray(gn)
        del m, v
        dp = np.asarray(jax.jit(ref.diff_norms)(p, init(ref.seed_words(seed))))
    del p
    D.free_device()
    return {"losses": losses, "grad1": g1, "update": dp}


def compare(prog: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    lp, lr = np.asarray(prog["losses"]), np.asarray(want["losses"])
    moved = want["grad1"] >= SMALL_LEAF * np.median(want["grad1"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": gaps(prog["grad1"], want["grad1"]),
        "update_gap": gaps(prog["update"], want["update"], moved),
    }


def sizes(run: D.Run):
    cfg, d = D.model(run)
    tr = run.sizes("traffic")
    B, L = tr["batch"], tr["seq_len"]
    corpus = make_corpus(run.seed, tr["corpus_windows"] * L + 1,
                         d.vocab_size, tr["zipf_a"])
    return cfg, d, tr, corpus


def run(run: D.Run) -> D.Result:
    cfg, d, tr, corpus = sizes(run)
    B, L = tr["batch"], tr["seq_len"]
    prog = program_readings(run, cfg, d, tr, corpus, window(run, B, L))
    w = prog["window"]
    want = reference_readings(d, prog["optimizer"], prog["batches"],
                              run.seed)
    got = compare(prog, want)
    compared = [(k, v, run.limit(k)) for k, v in got.items()]
    failed = sum(1 for x in w["losses"] if not np.isfinite(x))
    metrics = {"train_tokens_per_s": w["tokens"] / w["window_s"],
               "setup_s": w["setup_s"]}
    layer = None
    if run.trace:
        layer = D.LayerCtx(w["reduced"], d, {
            "kind": "train", "batch": B, "seq_len": L, "chips": run.chips,
            "steps": w["steps"], "window_s": w["window_s"],
        }, {})
    return D.Result(attempted=w["steps"], failed=failed, metrics=metrics,
                    compared=compared, device=w["device"], layer_ctx=layer,
                    compiles_in_window=w["compiles"])
