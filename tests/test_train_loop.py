"""Resilient-training-loop tests (DESIGN.md §10).

The contract under test: a run preempted mid-training and resumed via
``TrainLoop`` produces a step-for-step identical loss trajectory — and
bitwise-identical final params — to an uninterrupted run; the loop owns
the whole checkpoint/telemetry lifecycle (no caller wiring); the int8
error-feedback gradient channel trains associative recall to the same
accuracy as uncompressed; and a checkpoint written on one topology
restores onto another through the rule engine (elastic re-mesh).
"""
import dataclasses
import json
import os
import shutil
import signal
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import lm_data, synthetic
from repro.models import lm
from repro.train import checkpoint as ckpt
from repro.train import ft
from repro.train import optim as O
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.trainer import TrainConfig, abstract_train_state, init_train_state

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def tiny_cfg(vocab=32):
    cfg = get_config("hyena-153m").reduced()
    return dataclasses.replace(cfg, vocab_size=vocab, n_layers=2, d_model=64)


def tiny_tcfg(steps=10, compression=None):
    return TrainConfig(
        optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=steps),
        remat=False,
        grad_compression=compression,
    )


def corpus_stream(cursor=0):
    corpus = np.arange(20_000, dtype=np.int32) % 31
    return lm_data.TokenStream(
        corpus, global_batch=4, seq_len=32, seed=7, cursor=cursor
    )


# ------------------------------------------------------- resume parity

@pytest.mark.parametrize("compression", [None, "int8_ef"])
def test_preempt_resume_trajectory_identical(tmp_path, compression):
    """Kill at a step boundary, restart from the committed checkpoint, and
    the loss trajectory (and final params) must be bit-identical to an
    uninterrupted run — train state, loader cursor, RNG key, and step all
    round-trip.  Exercises the stateful TokenStream path (the loop owns
    the Prefetcher and checkpoints the consumed-batch cursor)."""
    cfg, steps = tiny_cfg(), 8
    tcfg = tiny_tcfg(steps, compression)

    # uninterrupted reference
    loop_a = TrainLoop(cfg, tcfg, LoopConfig(total_steps=steps, log_every=99),
                       handler=ft.PreemptionHandler(signals=()))
    res_a = loop_a.run(corpus_stream(), key=jax.random.PRNGKey(0))
    assert res_a.status == "done" and len(res_a.history) == steps

    # preempted at step 4 + resumed
    d = str(tmp_path / "ck")
    lcfg = LoopConfig(total_steps=steps, ckpt_dir=d, ckpt_every=3,
                      log_every=99, heartbeat_interval=None)
    h = ft.PreemptionHandler(signals=())
    loop_b = TrainLoop(cfg, tcfg, lcfg, handler=h)
    res_b = loop_b.run(
        corpus_stream(), key=jax.random.PRNGKey(0),
        on_step=lambda step, m, dt: h.trigger() if step == 4 else None,
    )
    assert res_b.status == "preempted" and res_b.step == 4
    assert ckpt.latest_step(d) == 4  # drained to a committed boundary

    loop_c = TrainLoop(cfg, tcfg, lcfg, handler=ft.PreemptionHandler(signals=()))
    # a different key on resume must NOT fork the trajectory — the
    # checkpointed base key wins
    res_c = loop_c.run(corpus_stream(), key=jax.random.PRNGKey(123))
    assert res_c.status == "done"

    assert res_b.history + res_c.history == res_a.history
    for a, b in zip(jax.tree_util.tree_leaves(res_a.state),
                    jax.tree_util.tree_leaves(res_c.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loop_retention_and_meta(tmp_path):
    """The loop's retention policy keeps exactly keep_last committed steps,
    and the checkpoint meta carries the loader cursor + step."""
    cfg, steps = tiny_cfg(), 7
    d = str(tmp_path / "ck")
    lcfg = LoopConfig(total_steps=steps, ckpt_dir=d, ckpt_every=2,
                      keep_last=2, log_every=99, heartbeat_interval=None)
    loop = TrainLoop(cfg, tiny_tcfg(steps), lcfg,
                     handler=ft.PreemptionHandler(signals=()))
    loop.run(corpus_stream(), key=jax.random.PRNGKey(0))
    kept = sorted(n for n in os.listdir(d) if n.startswith("step_"))
    assert kept == ["step_00000006", "step_00000007"]
    struct, _ = abstract_train_state(cfg, None)
    like = {"train": struct,
            "rng": jax.eval_shape(lambda: jax.random.PRNGKey(0))}
    _, meta, step = ckpt.restore(d, like)
    assert step == 7 and meta["step"] == 7
    assert meta["loader"]["cursor"] == 7  # consumed-batch cursor, not head


def test_stateless_source_rejects_stream_cursor(tmp_path):
    """A checkpoint written with a stream loader can't silently resume a
    stateless callable source (the cursor would be dropped)."""
    cfg = tiny_cfg()
    d = str(tmp_path / "ck")
    lcfg = LoopConfig(total_steps=4, ckpt_dir=d, ckpt_every=2, log_every=99,
                      heartbeat_interval=None)
    h = ft.PreemptionHandler(signals=())
    loop = TrainLoop(cfg, tiny_tcfg(4), lcfg, handler=h)
    loop.run(corpus_stream(), key=jax.random.PRNGKey(0),
             on_step=lambda step, m, dt: h.trigger() if step == 2 else None)
    batch = corpus_stream().next_batch()
    loop2 = TrainLoop(cfg, tiny_tcfg(4), lcfg,
                      handler=ft.PreemptionHandler(signals=()))
    with pytest.raises(ValueError, match="stateless"):
        loop2.run(lambda s, k: batch, key=jax.random.PRNGKey(0))


def test_stream_source_rejects_cursorless_checkpoint(tmp_path):
    """...and the opposite swap: a checkpoint written with a stateless
    source can't position a stream — resuming would replay from cursor 0."""
    cfg = tiny_cfg()
    d = str(tmp_path / "ck")
    lcfg = LoopConfig(total_steps=4, ckpt_dir=d, ckpt_every=2, log_every=99,
                      heartbeat_interval=None)
    h = ft.PreemptionHandler(signals=())
    loop = TrainLoop(cfg, tiny_tcfg(4), lcfg, handler=h)
    batch = corpus_stream().next_batch()
    loop.run(lambda s, k: batch, key=jax.random.PRNGKey(0),
             on_step=lambda step, m, dt: h.trigger() if step == 2 else None)
    loop2 = TrainLoop(cfg, tiny_tcfg(4), lcfg,
                      handler=ft.PreemptionHandler(signals=()))
    with pytest.raises(ValueError, match="cursor 0"):
        loop2.run(corpus_stream(), key=jax.random.PRNGKey(0))


# --------------------------------------------------------- compression

def test_compressed_step_carries_residuals():
    """grad_compression='int8_ef' is live: residuals appear in the train
    state (fp32, params-shaped), become nonzero after one step, checkpoint
    alongside everything else, and the step reports the channel error."""
    cfg = tiny_cfg()
    tcfg = tiny_tcfg(3, "int8_ef")
    state, _ = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    assert jax.tree_util.tree_structure(
        state["cgrad"]
    ) == jax.tree_util.tree_structure(state["params"])
    loop = TrainLoop(cfg, tcfg, LoopConfig(total_steps=3, log_every=99),
                     handler=ft.PreemptionHandler(signals=()))
    res = loop.run(corpus_stream(), key=jax.random.PRNGKey(0))
    assert "compression_abs_err" in res.metrics
    resid_max = max(
        float(np.abs(np.asarray(x)).max())
        for x in jax.tree_util.tree_leaves(res.state["cgrad"])
    )
    assert 0 < resid_max < 1.0  # error feedback carried, bounded
    assert res.history[-1] < res.history[0]


def test_invalid_grad_compression_rejected():
    with pytest.raises(ValueError, match="grad_compression"):
        TrainConfig(grad_compression="fp4")


@pytest.mark.slow
def test_compression_matches_uncompressed_recall_accuracy():
    """§4.1 convergence through the lossy channel: int8 error-feedback
    compression trains associative recall to the same accuracy threshold
    as uncompressed in the same budget.  (The bar is recall accuracy on
    the trained dictionaries — both modes saturate it at 1.0; held-out
    dictionary accuracy at this container scale sits near chance and is
    chaotic across compiled programs, so it is pinned by the system-level
    recall test, not here.)"""
    vocab = 12
    cfg = dataclasses.replace(
        get_config("hyena-153m").reduced(), vocab_size=16, n_layers=2
    )
    rng = np.random.default_rng(0)
    tokens, labels = synthetic.associative_recall(
        rng, n=256, seq_len=32, vocab=vocab
    )
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    accs, final_loss = {}, {}
    for comp in (None, "int8_ef"):
        tcfg = TrainConfig(
            optimizer=O.AdamWConfig(lr=2e-3, warmup_steps=10,
                                    total_steps=200, weight_decay=0.0),
            remat=False, grad_compression=comp,
        )
        loop = TrainLoop(cfg, tcfg, LoopConfig(total_steps=200, log_every=999),
                         handler=ft.PreemptionHandler(signals=()))
        res = loop.run(lambda s, k: batch, key=jax.random.PRNGKey(0))
        logits, _ = lm.forward(res.state["params"], cfg, jnp.asarray(tokens))
        accs[comp] = synthetic.eval_accuracy(
            np.asarray(logits, np.float32), labels
        )
        final_loss[comp] = res.history[-1]
    assert accs[None] >= 0.95, (accs, final_loss)
    assert accs["int8_ef"] >= 0.95, (accs, final_loss)  # same threshold
    assert final_loss["int8_ef"] < 0.05, final_loss


# ------------------------------------------------- kill-and-resume (OS)

_CHILD = """
import dataclasses, json, sys, time
import jax, numpy as np
from repro.configs import get_config
from repro.data import lm_data
from repro.train import optim as O
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.trainer import TrainConfig

ckpt_dir, hist_path, delay = sys.argv[1], sys.argv[2], float(sys.argv[3])
reversible = sys.argv[4] == "1"
cfg = dataclasses.replace(get_config("hyena-153m").reduced(),
                          vocab_size=32, n_layers=2, d_model=64)
tcfg = TrainConfig(optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0,
                                           total_steps=20),
                   remat=False, reversible=reversible)
lcfg = LoopConfig(total_steps=20, ckpt_dir=ckpt_dir, ckpt_every=2,
                  log_every=999, heartbeat_interval=None)
corpus = np.arange(20_000, dtype=np.int32) % 31
stream = lm_data.TokenStream(corpus, global_batch=4, seq_len=32, seed=7)

def on_step(step, metrics, dt):
    print(f"STEP {step}", flush=True)
    time.sleep(delay)

loop = TrainLoop(cfg, tcfg, lcfg)  # real SIGTERM handler
res = loop.run(stream, key=jax.random.PRNGKey(0), on_step=on_step)
json.dump({"status": res.status, "step": res.step, "history": res.history},
          open(hist_path, "w"))
print("EXIT", res.status, flush=True)
"""


def _spawn_child(ckpt_dir, hist_path, delay, reversible=False):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-c", _CHILD, ckpt_dir, hist_path, str(delay),
         "1" if reversible else "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )


@pytest.mark.slow
@pytest.mark.parametrize("reversible", [False, True])
def test_sigterm_kill_and_resume_matches_uninterrupted(tmp_path, reversible):
    """The real thing: SIGTERM a training process mid-run; it drains to a
    committed checkpoint and exits 0; a restarted process resumes and the
    combined loss trajectory is identical to a never-killed run.  Runs
    under both block substrates — the reversible dual-stream coupling
    checkpoints the same state tree, so kill/resume must be equally
    bit-stable with the flag on (DESIGN.md §15)."""
    ref_hist = str(tmp_path / "ref.json")
    proc = _spawn_child(str(tmp_path / "ck_ref"), ref_hist, 0.0, reversible)
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-3000:]
    ref = json.load(open(ref_hist))
    assert ref["status"] == "done" and len(ref["history"]) == 20

    kill_hist = str(tmp_path / "k1.json")
    ck = str(tmp_path / "ck_kill")
    proc = _spawn_child(ck, kill_hist, 0.3, reversible)
    deadline = time.time() + 300
    seen = 0
    for line in proc.stdout:
        if line.startswith("STEP "):
            seen = int(line.split()[1])
            if seen >= 5:
                proc.send_signal(signal.SIGTERM)
                break
        assert time.time() < deadline
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-3000:]
    first = json.load(open(kill_hist))
    assert first["status"] == "preempted"
    assert 0 < first["step"] < 20

    resume_hist = str(tmp_path / "k2.json")
    proc = _spawn_child(ck, resume_hist, 0.0, reversible)
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-3000:]
    second = json.load(open(resume_hist))
    assert second["status"] == "done"
    assert first["history"] + second["history"] == ref["history"]


# ---------------------------------------------------- elastic re-mesh

def test_checkpoint_restores_onto_mesh(tmp_path):
    """A checkpoint written on one device restores onto a 2x4 mesh through
    ctx.train_state_shardings (leaves placed by rule — including the
    compression residuals) and continues to the same losses."""
    d = str(tmp_path / "ck")
    cfg, steps = tiny_cfg(), 4
    tcfg = tiny_tcfg(steps, "int8_ef")
    lcfg = LoopConfig(total_steps=steps, ckpt_dir=d, ckpt_every=2,
                      log_every=99, heartbeat_interval=None)
    h = ft.PreemptionHandler(signals=())
    loop = TrainLoop(cfg, tcfg, lcfg, handler=h)
    res1 = loop.run(corpus_stream(), key=jax.random.PRNGKey(0),
                    on_step=lambda step, m, dt: h.trigger() if step == 2 else None)
    assert res1.status == "preempted" and ckpt.latest_step(d) == 2
    # the mesh run resumes from a copy — the single-device reference
    # continuation below writes its own later checkpoints into `d`
    d_mesh = str(tmp_path / "ck_mesh")
    shutil.copytree(d, d_mesh)
    loop2 = TrainLoop(cfg, tcfg, lcfg, handler=ft.PreemptionHandler(signals=()))
    res_ref = loop2.run(corpus_stream(), key=jax.random.PRNGKey(0))

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    code = f"""
import dataclasses, json
import jax, numpy as np
from repro.configs import get_config
from repro.data import lm_data
from repro.train import optim as O
from repro.train import ft
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.trainer import TrainConfig

cfg = dataclasses.replace(get_config("hyena-153m").reduced(),
                          vocab_size=32, n_layers=2, d_model=64)
tcfg = TrainConfig(optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0,
                                           total_steps={steps}),
                   remat=False, grad_compression="int8_ef")
lcfg = LoopConfig(total_steps={steps}, ckpt_dir={d_mesh!r}, ckpt_every=2,
                  log_every=99, heartbeat_interval=None)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
corpus = np.arange(20_000, dtype=np.int32) % 31
stream = lm_data.TokenStream(corpus, global_batch=4, seq_len=32, seed=7,
                             cursor=0)
loop = TrainLoop(cfg, tcfg, lcfg, mesh=mesh,
                 handler=ft.PreemptionHandler(signals=()))
res = loop.run(stream, key=jax.random.PRNGKey(0))
print("HIST", json.dumps(res.history))
print("OK")
"""
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    hist = json.loads(
        [l for l in proc.stdout.splitlines() if l.startswith("HIST ")][0][5:]
    )
    # same steps resumed on a different topology: losses agree to SPMD
    # reduction tolerance (not bitwise — the all-reduce order differs)
    np.testing.assert_allclose(hist, res_ref.history, atol=2e-2)


# ------------------------------------------------------- loop plumbing

def test_loop_config_validation():
    with pytest.raises(ValueError):
        LoopConfig(total_steps=0)
    with pytest.raises(ValueError):
        LoopConfig(total_steps=5, keep_last=0)
    with pytest.raises(ValueError):
        LoopConfig(total_steps=5, ckpt_every=0)


def test_completed_run_is_a_noop_on_rerun(tmp_path):
    cfg, steps = tiny_cfg(), 3
    d = str(tmp_path / "ck")
    lcfg = LoopConfig(total_steps=steps, ckpt_dir=d, ckpt_every=99,
                      log_every=99, heartbeat_interval=None)
    loop = TrainLoop(cfg, tiny_tcfg(steps), lcfg,
                     handler=ft.PreemptionHandler(signals=()))
    res = loop.run(corpus_stream(), key=jax.random.PRNGKey(0))
    assert res.status == "done"
    loop2 = TrainLoop(cfg, tiny_tcfg(steps), lcfg,
                      handler=ft.PreemptionHandler(signals=()))
    res2 = loop2.run(corpus_stream(), key=jax.random.PRNGKey(0))
    assert res2.status == "done" and res2.step == steps
    assert res2.history == []  # nothing re-run
