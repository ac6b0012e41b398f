"""Paper §4.1 driver: train a 2-layer Hyena on associative recall and report
accuracy across vocabulary sizes (Fig. 4.1 / Table C.1 protocol, scaled to
this container).  Training runs on the shared resumable loop
(``repro.train.loop.TrainLoop`` — DESIGN.md §10): kill and re-run with the
same --ckpt to continue bit-exactly; pass --compress to train through the
int8 error-feedback gradient channel the multi-pod runs use.

    PYTHONPATH=src python examples/train_associative_recall.py \
        --vocab 20 --seq 64 --steps 80 --ckpt /tmp/recall_ckpt
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import compile_cache
from repro.configs import get_config
from repro.data import synthetic
from repro.models import lm
from repro.train import optim as O
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.trainer import TrainConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = dataclasses.replace(
        get_config("hyena-153m").reduced(),
        vocab_size=max(args.vocab + 2, 16), n_layers=2, d_model=64,
    )
    rng = np.random.default_rng(0)
    tokens, labels = synthetic.associative_recall(
        rng, n=256, seq_len=args.seq, vocab=args.vocab
    )
    test_tokens, test_labels = synthetic.associative_recall(
        rng, n=128, seq_len=args.seq, vocab=args.vocab
    )
    tcfg = TrainConfig(
        optimizer=O.AdamWConfig(lr=2e-3, warmup_steps=10,
                                total_steps=args.steps, weight_decay=0.0),
        remat=False,
        grad_compression="int8_ef" if args.compress else None,
    )
    lcfg = LoopConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt,
        ckpt_every=args.ckpt_every, heartbeat_interval=None,
    )
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    loop = TrainLoop(cfg, tcfg, lcfg)
    result = loop.run(lambda step, key: batch, key=jax.random.PRNGKey(0))
    if result.status == "preempted":
        print("preempted — checkpointed, exiting")
        return
    logits, _ = lm.forward(result.state["params"], cfg, jnp.asarray(test_tokens))
    acc = synthetic.eval_accuracy(np.asarray(logits, np.float32), test_labels)
    print(f"vocab={args.vocab} seq={args.seq} test recall accuracy: {acc:.2%}")
    if result.stragglers:
        print("straggler report:", loop.monitor.last_report)
    print("OK")


if __name__ == "__main__":
    main()
