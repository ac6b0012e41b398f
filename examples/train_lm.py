"""End-to-end LM training driver (the paper's §4.2 pipeline): any registered
arch (default: the paper's hyena-153m), byte-level corpus, resumable
sharded data loader — all lifecycle (async checkpointing, preemption
draining, straggler/heartbeat telemetry, resume-from-latest-committed) owned
by the shared ``repro.train.loop.TrainLoop`` (DESIGN.md §10).  This is the
single-host entry point; on a real pod the same step function is lowered by
launch/dryrun.py onto the production mesh.

Full-size run (needs a TPU pod):
    python examples/train_lm.py --arch hyena-153m --seq 2048 --batch 256
Container-scale smoke (default): a reduced config, a few hundred steps on
the in-repo corpus.  Kill and re-run with the same --ckpt to resume
bit-exactly.
"""
import argparse
import dataclasses
import os

import jax
import numpy as np

from repro.common import compile_cache
from repro.configs import get_config
from repro.data import lm_data, tokenizer
from repro.train import optim as O
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.trainer import TrainConfig


def build_corpus() -> np.ndarray:
    """Byte corpus from this repository's own sources (offline container)."""
    chunks = []
    root = os.path.join(os.path.dirname(__file__), "..", "src")
    for dirpath, _, files in os.walk(root):
        for f in sorted(files):
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f), "rb") as fh:
                    chunks.append(np.frombuffer(fh.read(), dtype=np.uint8))
    return np.concatenate(chunks).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hyena-153m")
    ap.add_argument("--full", action="store_true",
                    help="use the full (un-reduced) architecture config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compression", default=None,
                    choices=["int8_ef"],
                    help="int8 error-feedback compression of the gradient "
                         "all-reduce (cross-pod bandwidth)")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = dataclasses.replace(
            cfg.reduced(), d_model=128, n_layers=4,
            vocab_size=tokenizer.VOCAB_SIZE,
        )
    else:
        cfg = dataclasses.replace(cfg, vocab_size=tokenizer.VOCAB_SIZE)

    corpus = build_corpus()
    print(f"corpus: {len(corpus) / 1e6:.1f}M bytes; arch {cfg.name}")
    stream = lm_data.TokenStream(
        corpus, global_batch=args.batch, seq_len=args.seq, seed=0
    )
    tcfg = TrainConfig(
        optimizer=O.AdamWConfig(
            lr=args.lr, warmup_steps=min(50, args.steps // 10),
            total_steps=args.steps, weight_decay=0.1,
        ),
        microbatches=args.microbatches,
        remat=True,
        grad_compression=args.grad_compression,
    )
    lcfg = LoopConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt,
        ckpt_every=args.ckpt_every, keep_last=2,
    )
    loop = TrainLoop(cfg, tcfg, lcfg)
    result = loop.run(stream, key=jax.random.PRNGKey(0))
    if result.status == "preempted":
        print("preempted — checkpointed, exiting cleanly")
        return
    tokens_seen = result.step * args.batch * args.seq
    print(f"done: {tokens_seen / 1e6:.1f}M tokens, "
          f"stragglers={result.stragglers}")
    print("OK")


if __name__ == "__main__":
    main()
