"""Quickstart: build a small Hyena LM, train it briefly on byte-level text,
and sample a continuation.

    PYTHONPATH=src python examples/quickstart.py [--steps 30]

The single static ``generate()`` call below is the simplest serving path.
For concurrent requests with mixed lengths, per-request sampling params,
and per-token streaming callbacks, use the continuous-batching API —
``repro.serve.engine.ServeEngine.submit()/step()/drain()`` — shown in
``examples/serve_batched.py`` (architecture in DESIGN.md §4).  That API
also carries the request lifecycle guards (DESIGN.md §13): ``cancel()``,
tick deadlines, and load shedding, each finalizing a structured
``RequestResult`` (status completed / failed / deadline_exceeded /
cancelled / shed, with partial tokens preserved).
"""
import argparse
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import compile_cache
from repro.configs import get_config
from repro.data import lm_data, tokenizer
from repro.models import lm
from repro.serve.engine import ServeConfig, generate
from repro.train import optim as O
from repro.train.trainer import TrainConfig, init_train_state, make_train_step

TEXT = (
    "the hyena hierarchy is a subquadratic drop-in replacement for attention "
    "built from implicitly parametrized long convolutions and data-controlled "
    "gating. "
) * 400


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = dataclasses.replace(
        get_config("hyena-153m").reduced(),
        vocab_size=tokenizer.VOCAB_SIZE, n_layers=2, d_model=96,
    )
    corpus = tokenizer.encode(TEXT, add_bos=False)
    stream = lm_data.TokenStream(
        corpus, global_batch=16, seq_len=args.seq, seed=0
    )
    tcfg = TrainConfig(
        optimizer=O.AdamWConfig(lr=3e-3, warmup_steps=10,
                                total_steps=args.steps, weight_decay=0.01),
        remat=False,
    )
    state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in stream.next_batch().items()}
        state, metrics = step(state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:3d} loss {float(metrics['loss']):.3f} "
                  f"lr {float(metrics['lr']):.2e}")

    prompt = tokenizer.encode("the hyena ", add_bos=False)[None, :]
    out = generate(
        state["params"], cfg, jnp.asarray(prompt),
        scfg=ServeConfig(max_len=args.seq + 32, temperature=0.0),
        max_new_tokens=24,
    )
    print("prompt + continuation:", "the hyena " + tokenizer.decode(np.asarray(out[0])))
    print("OK")


if __name__ == "__main__":
    main()
