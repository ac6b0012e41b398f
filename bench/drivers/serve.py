"""Serving cell: the dense ``ServeEngine`` (continuous batching over a
slot pool, greedy decoding) under open-loop arrivals on the wall clock.

The traffic file fixes the rate, the prompt lengths with their weights,
the bounded-Pareto output lengths and the engine's slots and ``max_len``.
Every seed gets the same multiset of arrival gaps (quantiles of the
exponential law), prompt lengths and output lengths, in its own order, and
its own prompt tokens; so seeds change the order of the work and not its
amount.

Set-up makes the weights, builds the engine and serves one request of
every prompt length (the engine compiles one prefill per length).  The
window submits each request when it is due and steps the engine whenever
it has work; after ``--seconds`` it keeps stepping until every request due
in the window has its first token (a minute at most).  Each request is
timed from when it was due.

Number compared: ``logit_gap``, the widest gap by which a served token's
logit lies below the float32 reference's best at its position, over a
seeded sample of finished requests that includes the longest one; the
reference runs once over each prompt and its served tokens.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import drivers as D
from bench import reference as ref
from bench.drivers.train import make_corpus

LATE_S = 60.0  # how long past the window a due request may still answer


@dataclasses.dataclass
class Req:
    due: float
    prompt: np.ndarray
    out_len: int
    rid: int = -1
    submitted: float = math.nan
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)


def schedule(seed: int, tr: Dict[str, Any], seconds: float,
             vocab: int) -> List[Req]:
    n = max(1, int(round(tr["rate_per_s"] * seconds)))
    rng = np.random.default_rng(seed)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    due = (np.cumsum(gaps) - gaps[0]) / gaps.sum() * seconds
    w = np.asarray(tr["prompt_weights"], np.float64)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    counts[np.argsort(share - counts)[::-1][: n - counts.sum()]] += 1
    plens = rng.permutation(np.repeat(tr["prompt_lens"], counts))
    o = tr["output"]
    lo, hi, a = o["min"], o["max"], o["pareto_alpha"]
    outs = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
    outs = rng.permutation(np.clip(np.round(outs), lo, hi).astype(int))
    corpus = make_corpus(seed, int(plens.sum()), vocab, tr["zipf_a"])
    starts = np.concatenate([[0], np.cumsum(plens)[:-1]])
    reqs = [Req(float(t), corpus[s:s + p], int(m))
            for t, s, p, m in zip(due, starts, plens, outs)]
    for r in reqs:
        if r.prompt.size + r.out_len > tr["max_len"]:
            raise ValueError(f"prompt {r.prompt.size} + output {r.out_len} "
                             f"exceeds max_len {tr['max_len']}")
    return reqs


def make_engine(cfg, tr, params, seed: int):
    """The program's serving engine (the timed path)."""
    from repro.serve.engine import ServeConfig, ServeEngine

    import jax.numpy as jnp

    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[
        tr.get("policy", "bf16")]
    scfg = ServeConfig(max_len=tr["max_len"], n_slots=tr["n_slots"],
                       temperature=0.0, cache_dtype=dtype)
    return ServeEngine(params, cfg, scfg, seed=seed % (2 ** 31))


def warm_up(engine, tr, vocab: int) -> None:
    """One request of every prompt length: compiles each prefill, the
    pooled decode, and the slot insert and reset."""
    for n in tr["prompt_lens"]:
        engine.submit(np.arange(n, dtype=np.int32) % vocab,
                      max_new_tokens=2)
    engine.drain()


def serve(engine, reqs: List[Req], seconds: float,
          counters: Optional[Dict[str, list]] = None) -> Dict[str, Any]:
    """The open-loop window.  Returns the end time (seconds from the
    window's start); fills each request's record."""
    by_rid: Dict[int, Req] = {}
    i, n = 0, len(reqs)
    t0 = time.perf_counter()
    waiting = 0  # due requests without a first token
    with D.span("window"):
        while True:
            now = time.perf_counter() - t0
            if i < n and reqs[i].due <= now:
                with D.span("generator"):
                    while i < n and reqs[i].due <= now:
                        r = reqs[i]
                        r.rid = engine.submit(r.prompt,
                                              max_new_tokens=r.out_len)
                        r.submitted = time.perf_counter() - t0
                        by_rid[r.rid] = r
                        waiting += 1
                        i += 1
            idle = engine.scheduler.idle
            if now >= seconds and i >= n and (
                    waiting == 0 or idle or now >= seconds + LATE_S):
                break
            if idle:
                nxt = reqs[i].due if i < n else seconds
                time.sleep(max(0.0, nxt - now))
                continue
            with D.span("engine_step"):
                events = engine.step()
            t = time.perf_counter() - t0
            for ev in events:
                r = by_rid.get(ev.rid)
                if r is None:
                    continue
                if not r.times:
                    waiting -= 1
                r.times.append(t)
                r.tokens.append(int(ev.token))
            if counters is not None:
                step_cursors(events, by_rid, counters)
    return {"end": time.perf_counter() - t0, "t0": t0}


def step_cursors(events, by_rid, counters) -> None:
    """Per engine step: prompt lengths prefilled, and the cursor (tokens
    already in the cache) of each request the pooled decode advanced."""
    seen = {}
    for ev in events:
        seen[ev.rid] = seen.get(ev.rid, 0) + 1
    pre, cur = [], []
    for rid, k in seen.items():
        r = by_rid.get(rid)
        if r is None:
            continue
        if len(r.tokens) == k:  # its first token came from a prefill
            pre.append(int(r.prompt.size))
            k -= 1
        if k:
            cur.append(int(r.prompt.size + len(r.tokens) - 2))
    counters["prefill_lens"].append(pre)
    counters["decode_cursors"].append(cur)


def e2e(reqs: List[Req], seconds: float, end: float) -> Dict[str, float]:
    ttft, itl, n_tok = [], [], 0
    for r in reqs:
        first = r.times[0] if r.times else end
        ttft.append(first - r.due)
        itl.extend(np.diff(r.times).tolist())
        n_tok += sum(1 for t in r.times if t <= seconds)
    return {
        "ttft_p95_ms": 1e3 * D.quantile(ttft, 0.95),
        "itl_p95_ms": 1e3 * D.quantile(itl, 0.95) if itl else math.nan,
        "serve_tokens_per_s": n_tok / seconds,
    }


def sample(reqs: List[Req], engine_results, seed: int, k: int) -> List[Req]:
    """Finished requests to check: the longest and a seeded draw."""
    done = [r for r in reqs if engine_results.get(r.rid) is not None
            and engine_results[r.rid].status == "completed"]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def reference_logits(d: ref.Dims, seed: int, seqs: np.ndarray,
                     prec: ref.Precision = ref.FP32, matmul: str = "highest"):
    """(1, max_len, V) logits of the reference over each padded sequence;
    the taps are on the ``max_len`` grid, as served."""
    import jax

    with jax.default_matmul_precision(matmul):
        _, p = D.init_weights(d, seed)
        fn = jax.jit(lambda p, t: ref.logits(p, d, t, prec))
        out = [fn(p, seqs[i:i + 1]) for i in range(seqs.shape[0])]
    return out


def gap_readings(d: ref.Dims, seed: int, checked: List[Req], max_len: int,
                 control: Optional[str] = None, alter: bool = False):
    """Widest gaps below the reference's best, for the served tokens
    (``program``) and, with ``control`` named, for the first choices of
    the reference computed one step below (``control``)."""
    import jax.numpy as jnp

    seqs = np.zeros((len(checked), max_len), np.int32)
    spans = []
    for i, r in enumerate(checked):
        toks = list(r.tokens)
        if alter and i == 0:
            toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % d.vocab_size
        full = np.concatenate([r.prompt, np.asarray(toks[:-1], np.int32)])
        seqs[i, :full.size] = full
        spans.append((r.prompt.size - 1, np.asarray(toks, np.int32)))
    want = reference_logits(d, seed, seqs)
    out: Dict[str, float] = {}
    prog, n = 0.0, 0
    for (p0, toks), lg in zip(spans, want):
        lg = lg[0, p0:p0 + toks.size]
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], -1)[:, 0]
        prog = max(prog, float(jnp.max(best - got)))
        n += toks.size
    out["program"] = prog
    if control is not None:
        low = reference_logits(d, seed, seqs, *ref.CONTROLS[control])
        ctl = 0.0
        for (p0, toks), lg, lg8 in zip(spans, want, low):
            lg = lg[0, p0:p0 + toks.size]
            pick = jnp.argmax(lg8[0, p0:p0 + toks.size], axis=-1)
            gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, pick[:, None],
                                                        -1)[:, 0]
            ctl = max(ctl, float(jnp.max(gap)))
        out["control"] = ctl
    del want
    D.free_device()
    return out, n


def program_window(run: D.Run, cfg, d, tr, counters=None):
    """Set up, serve the window, free the device; returns the records."""
    reqs = schedule(run.seed, tr, run.seconds, d.vocab_size)
    with D.program_precision(tr):
        _, params = D.init_weights(d, run.seed)
        engine = make_engine(cfg, tr, params, run.seed)
        del params
        warm_up(engine, tr, d.vocab_size)
        setup_s = time.perf_counter() - run.t_start
        with D.traced(run.trace) as trc, D.count_compiles() as cc:
            w = serve(engine, reqs, run.seconds, counters)
    from bench.run import device_info

    device = device_info(run.chips)
    results = engine.request_results()
    del engine
    D.free_device()
    return reqs, results, {"setup_s": setup_s, "end": w["end"],
                           "device": device, "reduced": trc.reduced,
                           "compiles": cc.n}


def readings(run: D.Run, variants) -> Dict[str, float]:
    """Calibration: the gaps of one seed's short window."""
    cfg, d = D.model(run)
    tr = run.sizes("traffic")
    reqs, results, _ = program_window(run, cfg, d, tr)
    checked = sample(reqs, results, run.seed, tr["checked_requests"])
    control = run.cell.limits.get("control") if "control" in variants \
        else None
    got, n = gap_readings(d, run.seed, checked, tr["max_len"], control)
    out = {"program": {"logit_gap": got["program"]}, "checked_tokens": n}
    if control is not None:
        out["control"] = {"logit_gap": got["control"]}
    if "token" in variants:
        alt, _ = gap_readings(d, run.seed, checked, tr["max_len"],
                              alter=True)
        out["token"] = {"logit_gap": alt["program"]}
    return out


def run(run: D.Run) -> D.Result:
    cfg, d = D.model(run)
    tr = run.sizes("traffic")
    counters = {"prefill_lens": [], "decode_cursors": []} if run.trace \
        else None
    reqs, results, w = program_window(run, cfg, d, tr, counters)
    failed = sum(
        1 for r in reqs if not r.times or (
            results.get(r.rid) is not None
            and results[r.rid].status != "completed"))
    checked = sample(reqs, results, run.seed, tr["checked_requests"])
    # no finished request to check reads as no number: not correct
    gap = gap_readings(d, run.seed, checked, tr["max_len"])[0]["program"] \
        if checked else None
    compared = [("logit_gap", gap, run.limit("logit_gap"))]
    metrics = e2e(reqs, run.seconds, w["end"])
    metrics["setup_s"] = w["setup_s"]
    layer = None
    if run.trace:
        lags = [r.submitted - r.due for r in reqs]
        layer = D.LayerCtx(w["reduced"], d, {
            "kind": "serve", "max_len": tr["max_len"], "chips": run.chips,
        }, dict(counters, generator_lag_s=lags))
    return D.Result(attempted=len(reqs), failed=failed, metrics=metrics,
                    compared=compared, device=w["device"], layer_ctx=layer,
                    compiles_in_window=w["compiles"])
