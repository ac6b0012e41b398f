"""The program's host spans: the loader's wait lands in a profiler trace
on the calling thread, the training loop marks its data, sync and
checkpoint boundaries, and the loop times its steps between host syncs.

The profiler runs in a child process: XLA compiles that follow a
profiler session in the same process can crash on the CPU."""
import collections
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import lm_data
from repro.train import ft
from repro.train import optim as O
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.trainer import TrainConfig

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PREFETCH_CHILD = textwrap.dedent("""
    import gzip, json, sys, time
    from pathlib import Path
    import jax, numpy as np
    from repro.data import lm_data

    out = Path(sys.argv[1])
    corpus = np.arange(20_000, dtype=np.int32) % 31
    stream = lm_data.TokenStream(corpus, global_batch=4, seq_len=32, seed=7)
    slow = stream.next_batch

    def next_batch():
        time.sleep(0.02)
        return slow()

    stream.next_batch = next_batch
    pf = lm_data.Prefetcher(stream, depth=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # it would crowd the spans out
    try:
        pf.next()  # the queue then refills one batch per 20 ms
        with jax.profiler.trace(str(out), profiler_options=opts):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("outer.data"):
                    pf.next()
    finally:
        pf.close()
    (f,) = out.glob("plugins/profile/*/*.trace.json.gz")
    with gzip.open(f, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    print(json.dumps([[e["name"], e["ts"], e["dur"], e["tid"]]
                      for e in events if e.get("ph") == "X"
                      and e["name"] in ("repro.data.wait", "outer.data")]))
""")


def test_prefetcher_wait_span(tmp_path):
    """Each ``Prefetcher.next`` is one ``repro.data.wait`` span of a CPU
    profiler trace, on the caller's thread, inside the caller's own span,
    and it lasts as long as the caller waited for the loader."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _PREFETCH_CHILD, str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    spans = json.loads(proc.stdout.strip().splitlines()[-1])
    waits = sorted((s for s in spans if s[0] == "repro.data.wait"),
                   key=lambda s: s[1])
    outer = sorted((s for s in spans if s[0] == "outer.data"),
                   key=lambda s: s[1])
    assert len(waits) == len(outer) == 3
    for w, o in zip(waits, outer):
        assert w[3] == o[3]  # the caller's thread
        assert o[1] <= w[1] and w[1] + w[2] <= o[1] + o[2]
    # the loader sleeps 20 ms a batch: the caller waited most of it
    assert sum(w[2] for w in waits) > 20e3


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts the spans
    entered, by name."""

    def __init__(self):
        self.count = collections.Counter()

    def __call__(self, name, **kw):
        self.count[name] += 1
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _corpus_stream():
    corpus = np.arange(20_000, dtype=np.int32) % 31
    return lm_data.TokenStream(corpus, global_batch=4, seq_len=32, seed=7)


@pytest.mark.parametrize("hook", [False, True], ids=["no_hook", "on_step"])
def test_loop_times_steps_between_syncs(tmp_path, monkeypatch, hook):
    """Without a hook the loop syncs at its log and checkpoint boundaries,
    and the straggler monitor gets one mean step time per interval; a
    hook reads metrics every step, so every step is timed.  The loop's
    spans mark the same boundaries."""
    cfg = dataclasses.replace(get_config("hyena-153m").reduced(),
                              vocab_size=32, n_layers=2, d_model=64)
    tcfg = TrainConfig(optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0,
                                               total_steps=6), remat=False)
    lcfg = LoopConfig(total_steps=6, ckpt_dir=str(tmp_path / "ck"),
                      ckpt_every=3, log_every=3, heartbeat_interval=None)
    logs = []
    loop = TrainLoop(cfg, tcfg, lcfg, log=logs.append,
                     handler=ft.PreemptionHandler(signals=()))
    recorded, hooked = [], []
    record = loop.monitor.record
    loop.monitor.record = lambda step, s: (recorded.append((step, s)),
                                           record(step, s))[1]
    on_step = (lambda step, m, s: hooked.append(s)) if hook else None
    spans = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
    res = loop.run(_corpus_stream(), key=jax.random.PRNGKey(0),
                   on_step=on_step)
    assert res.status == "done" and len(res.history) == 6
    steps = [s for s, _ in recorded]
    assert steps == ([1, 2, 3, 4, 5, 6] if hook else [3, 6])
    assert all(s > 0 for _, s in recorded)
    if hook:
        assert [s for _, s in recorded] == hooked
    assert [line.split()[1] for line in logs] == ["3", "6"]
    assert all(line.endswith("tok/s") for line in logs)
    names = spans.count
    assert names["repro.train.data"] == names["repro.data.wait"] == 6
    assert names["repro.train.checkpoint"] == 2  # steps 3 and 6
    # the flushes before the checkpoint at 3 and the log at 6; the log at 3
    # and the end find nothing left to read
    assert names["repro.train.sync"] == (6 if hook else 0) + 2
