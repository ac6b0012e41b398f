"""Continuous-batching serving demo: submit prompts with *different*
lengths, horizons, and sampling params to a ``ServeEngine`` slot pool and
stream tokens as they are emitted.

Each request owns its slot only while it is generating — a finished
request's slot is reset and immediately refilled from the admission queue,
so mixed traffic never pays for its slowest member (compare
``benchmarks/bench_serving.py`` against the old padded static batch).

``--mesh DxM`` serves mesh-native (DESIGN.md §9): the slot pool shards by
the rule engine and the decode quantum runs tensor-parallel — force host
devices to try it on CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/serve_batched.py --mesh 2x4

``--paged`` swaps in the block-paged engine (DESIGN.md §11): the prompts
below share a common prefix, so the radix prefix cache prefills it once
and later requests fork it copy-on-write — watch the per-request
``prefix_cached_tokens`` in the summary line.

    PYTHONPATH=src python examples/serve_batched.py --arch hyena-153m --paged

Lifecycle guards (DESIGN.md §13): the demo also cancels one request
mid-decode and submits one with a tick ``deadline`` — both finalize with
a structured ``RequestResult`` (``engine.result(rid)``; status one of
completed / failed / deadline_exceeded / cancelled / shed, always
carrying the partial tokens) instead of vanishing or wedging the pool.
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.common import compile_cache
from repro.common.param import split_params
from repro.configs import get_config
from repro.data import tokenizer
from repro.models import lm
from repro.serve.engine import ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hyena-153m")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve on a (data, model) debug mesh, e.g. 2x4 "
                    "(needs that many devices)")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged engine: block-paged "
                    "caches, radix prefix reuse, chunked prefill")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = dataclasses.replace(
        get_config(args.arch).reduced(),
        vocab_size=tokenizer.VOCAB_SIZE, frontend=None, frontend_len=0,
    )
    params, axes = split_params(lm.init_lm(jax.random.PRNGKey(0), cfg))
    ectx = None
    if args.mesh:
        from repro.distributed.execution import ExecutionContext
        from repro.launch.mesh import parse_mesh_arg

        ectx = ExecutionContext(mesh=parse_mesh_arg(args.mesh))
    prompts = [
        "long convolutions are all you need",
        "long convolutions are not enough",
        "long convolutions beat attention",
        "subquadratic models",
    ]
    max_prompt = max(len(tokenizer.encode(p, add_bos=False)) for p in prompts)
    scfg = ServeConfig(
        max_len=max_prompt + args.new_tokens + 1, n_slots=args.slots,
        temperature=args.temperature, top_k=8,
    )
    if args.paged:
        from repro.serve.paged import PagedConfig, PagedServeEngine

        eng = PagedServeEngine(params, cfg, scfg, PagedConfig(page_size=4),
                               seed=7, ectx=ectx, param_axes=axes)
    else:
        eng = ServeEngine(params, cfg, scfg, seed=7, ectx=ectx,
                          param_axes=axes)

    streamed = {}

    def on_token(rid, token, done):
        streamed.setdefault(rid, []).append(token)

    t0 = time.time()
    rids = {}
    # lifecycle guards (DESIGN.md §13): one request is cancelled
    # mid-decode and one carries a tick deadline it cannot meet — both
    # finalize with a structured RequestResult (partial tokens kept)
    # and release their slot back to the pool immediately
    enc0 = np.asarray(tokenizer.encode(prompts[0], add_bos=False))
    doomed = eng.submit(enc0, max_new_tokens=args.new_tokens,
                        stream=on_token)
    dated = eng.submit(enc0, max_new_tokens=args.new_tokens, deadline=2)
    eng.step()  # both resident now
    eng.cancel(doomed)
    for i, p in enumerate(prompts):
        enc = np.asarray(tokenizer.encode(p, add_bos=False))
        # per-request params: even requests greedy, odd ones sampled
        rids[eng.submit(
            enc, max_new_tokens=args.new_tokens,
            temperature=0.0 if i % 2 == 0 else args.temperature,
            stream=on_token,
        )] = p
    out = eng.drain()
    dt = time.time() - t0

    toks = 0
    for rid, p in rids.items():
        assert streamed[rid] == [int(t) for t in out[rid]]  # stream == drain
        toks += len(out[rid])
        cached = ""
        if args.paged:
            n = eng.request_metrics[rid]["prefix_cached_tokens"]
            cached = f"  [prefix_cached_tokens={n}]"
        print(f"  {p!r} -> {tokenizer.decode(np.asarray(out[rid]))!r}{cached}")
    for rid, why in ((doomed, "cancel()"), (dated, "deadline=2")):
        res = eng.result(rid)
        print(f"  lifecycle[{why}]: status={res.status} after "
              f"{len(res.tokens)} partial tokens")
        assert not res.ok
    print(f"{toks} tokens in {dt:.1f}s ({toks / dt:.1f} tok/s, "
          f"slots={args.slots}, requests={len(prompts)})")
    print("OK")


if __name__ == "__main__":
    main()
