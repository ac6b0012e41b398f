"""The layers the program names itself: its named scopes reach the train
step's compiled HLO, ``bench/scopes.py`` finds each of them there, and the
readers built on them read a cut of a scoped chip trace as worked out by
hand, while the accepted readers read the unscoped cut as before."""
import json
import re
from pathlib import Path

import pytest

from bench import drivers as D
from bench import reference as ref
from bench import run as R
from bench import scopes as S
from bench import trace_reduce as TR
from bench import work

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).with_name("data")
CELL = "train.hyena-153m.b8-l2048.fp32"
# every scope the program puts on the training path
PROGRAM_SCOPES = ("token_embed", "rms_norm", "hyena_proj", "short_conv",
                  "implicit_filter", "long_conv", "mlp", "lm_head",
                  "optimizer")


@pytest.fixture(scope="module")
def op_names():
    """The op names of the cell's train step, compiled at its rehearsal
    sizes on the host."""
    import jax
    import jax.numpy as jnp

    from repro.train.trainer import (TrainConfig, abstract_train_state,
                                     jit_train_step)

    cell = R.load_cell(ROOT, CELL)
    run = D.Run(cell=cell, seed=1, seconds=1, trace=False, rehearsal=True,
                t_start=0.0)
    cfg, _ = D.model(run)
    tr = run.sizes("traffic")
    tcfg = TrainConfig(policy=D.policy(tr))
    state, _ = abstract_train_state(cfg, tcfg)
    tok = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"]), jnp.int32)
    hlo = jit_train_step(cfg, tcfg).lower(
        state, {"tokens": tok, "labels": tok}).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def test_every_scope_reaches_the_compiled_step(op_names):
    for name in PROGRAM_SCOPES:
        assert any(f"/{name}/" in n or f"({name})" in n
                   for n in op_names), name
    assert any(S.REMAT in n for n in op_names)


def test_every_scope_rule_matches_an_op(op_names):
    assert {name for _, name in S.SCOPES} == set(PROGRAM_SCOPES)
    for layer, _ in S.SCOPES:
        assert any(S.scope_layer(n) == layer for n in op_names), layer


def test_no_scope_reads_as_long_conv_or_a_parameter(op_names):
    rules = json.loads((ROOT / "bench/layers.json").read_text())["ops"]
    for _, name in S.SCOPES:
        assert "fft" not in name
        op = TR.Op(0.0, 0.0, "fusion", "loop fusion", f"jit(step)/{name}/x:",
                   "")
        assert TR.classify(op, rules) == "other"
    params = [n for n in op_names if n.startswith("state[")]
    assert any("mlp" in n for n in params)  # HLO text escapes the quotes
    assert all(S.scope_layer(n) is None for n in params)


def _ctx(reduced):
    cfg = json.loads((ROOT / "bench/configs/hyena-153m.json").read_text())
    return D.LayerCtx(reduced, ref.Dims.from_config(cfg), {
        "kind": "train", "batch": 8, "seq_len": 2048, "chips": 1,
        "steps": 1, "window_s": reduced.window_s}, {})


def _read(name, ctx):
    mod = R.load_module(ROOT / "bench/metrics" / f"{name}.py",
                        f"bench.metrics.{name}")
    return mod.read(ctx, work.peaks("TPU v5 lite"))


# the accepted readers on the unscoped cut, as the parent commit reads
# them: with the cut's own window (no step run inside it), and with one
# that holds the step run
ACCEPTED = {
    None: {"mfu.train": None, "idle_share.train": 0.04228827931459511,
           "long_conv_roofline.train": None, "long_conv_ms.train": None},
    (0.0, 2.0): {"mfu.train": 5.625398337462631,
                 "idle_share.train": 98.7705873596,
                 "long_conv_roofline.train": 57.339120838087695,
                 "long_conv_ms.train": 21.161778981999998},
}


@pytest.mark.parametrize("window", list(ACCEPTED), ids=["cut", "step"])
def test_accepted_readers_read_as_before(window):
    trace = TR.load_events(DATA / "train_step_cut.trace.json.gz")
    ctx = _ctx(TR.reduce_trace(trace, window=window))
    for name, want in ACCEPTED[window].items():
        got = _read(name, ctx)
        if want is None:
            assert got is None, name
        else:
            assert got == pytest.approx(want, rel=1e-12), name
    # no op of the unscoped program reads as one of the new layers
    assert _read("mlp_ms.train", ctx) is None


# the leaf ops of the scoped cut (µs), by what reads them; the cut's
# window holds one run of the step
HYENA_PROJ = [2948.949922, 2549.252344, 2549.261172, 2948.906328]
MLP = [1910.192422, 1910.139922, 1826.043672, 1826.052422]
LM_HEAD = [48310.0675, 47957.210156]
IMPLICIT_FILTER = [31.62375, 31.537344, 37.173828, 37.19625]
OPTIMIZER = [1884.227578, 1863.946094]
RMS_NORM = [171.0325, 5.292422, 171.04875, 5.291328]
TOKEN_EMBED = [267.167422, 1048.038672]
SHORT_CONV = [970.22375, 1736.907578, 970.221328, 1736.690078]
LONG_CONV_SCOPE = [281.14, 398.566172, 280.87375, 398.520078]
# the long conv's rule: its own jit(fft) ops, and the forward short conv
# (whose source is core/fftconv.py)
LONG_CONV = [717.941172, 718.65, 969.982422, 970.56375]
# other: copies of parameter and moment leaves, a scan slice
OTHER = [531.297656, 2.488672, 2.488672, 2.488672, 535.28625, 530.1975]
REMAT = [37.173828, 2948.949922, 2.488672, 281.14, 1826.043672, 2.488672,
         717.941172, 5.292422, 280.87375, 2.488672, 970.22375, 37.19625,
         970.221328, 1826.052422, 5.291328, 718.65, 2948.906328]


@pytest.fixture(scope="module")
def scoped():
    trace = TR.load_events(DATA / "train_step_scoped_cut.trace.json.gz")
    return trace, _ctx(TR.reduce_trace(trace))


@pytest.mark.parametrize("name, durations", [
    ("hyena_proj_ms.train", HYENA_PROJ),
    ("mlp_ms.train", MLP),
    ("lm_head_ms.train", LM_HEAD),
    ("implicit_filter_ms.train", IMPLICIT_FILTER),
    ("optimizer_ms.train", OPTIMIZER),
    ("recompute_ms.train", REMAT),
    ("long_conv_ms.train", LONG_CONV),
])
def test_readers_on_the_scoped_cut(scoped, name, durations):
    _, ctx = scoped
    assert len(ctx.reduced.program_runs("train_step")) == 1
    assert _read(name, ctx) == pytest.approx(1e-3 * sum(durations),
                                              rel=1e-9)


def test_unattributed_share_on_the_scoped_cut(scoped):
    _, ctx = scoped
    groups = [HYENA_PROJ, MLP, LM_HEAD, IMPLICIT_FILTER, OPTIMIZER, RMS_NORM,
              TOKEN_EMBED, SHORT_CONV, LONG_CONV_SCOPE, LONG_CONV, OTHER]
    total = sum(sum(g) for g in groups)
    assert sum(o.dur for o in ctx.reduced.ops) == pytest.approx(
        1e-6 * total, rel=1e-9)  # every leaf op of the cut is listed
    assert _read("unattributed_share.train", ctx) == pytest.approx(
        100.0 * sum(OTHER) / total, rel=1e-9)


def test_the_scoped_cut_shows_the_loader_wait(scoped):
    """On the chip, too, the loader's wait is a span inside the
    benchmark's data span, on the same thread."""
    trace, _ = scoped
    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"
             and e["name"] in ("bench.data", "repro.data.wait")]
    data = [e for e in spans if e["name"] == "bench.data"]
    waits = [e for e in spans if e["name"] == "repro.data.wait"]
    assert len(data) == len(waits) == 2
    for d, w in zip(data, waits):
        assert d["tid"] == w["tid"]
        assert d["ts"] <= w["ts"] and w["ts"] + w["dur"] <= d["ts"] + d["dur"]
