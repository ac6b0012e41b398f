"""What decides ``correct`` has to fail a broken timed path and the
control.  Each test drives a CPU rehearsal of a cell in-process with the
program's timed path broken underneath and checks that ``correct`` comes
out false; the honest runs beside them come out true.  The control (the
reference computed one step below the stated precision, in the program's
place) must read above a limit on one of the cell's numbers."""
import json

import pytest

from bench import drivers as D
from bench import run as R
from bench.drivers import serve as S
from bench.drivers import train as T

SEED = "2718281828459"
SERVE_CELL = "serve.hyena-153m.chat.fp32"  # the serve_checkout fixture's cell


def _train_cell():
    b = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    return next(w["name"] for w in b["workloads"]
                if w["name"].startswith("train."))


def _run(capsys, workload):
    rc = R.main(["--workload", workload, "--seed", SEED, "--seconds", "1",
                 "--trace", "0", "--cpu-rehearsal"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def serve_root(serve_checkout, monkeypatch):
    monkeypatch.setattr(R, "ROOT", serve_checkout)
    monkeypatch.setattr(R, "BENCH_DIR", serve_checkout / "bench")
    return serve_checkout


def test_honest_train_run_is_correct(capsys):
    assert _run(capsys, _train_cell())["correct"] is True


def test_honest_serve_run_is_correct(capsys, serve_root):
    assert _run(capsys, SERVE_CELL)["correct"] is True


def test_train_state_left_unchanged(monkeypatch, capsys):
    from repro.train.trainer import jit_train_step

    def build(cfg, tcfg, state, batch):
        step = jit_train_step(cfg, tcfg, donate=False).lower(
            state, batch).compile()

        def unchanged(state, batch):
            _, m = step(state, batch)
            return state, m
        return unchanged

    monkeypatch.setattr(T, "build_step", build)
    line = _run(capsys, _train_cell())
    assert line["correct"] is False
    gap = {c["name"]: c["value"] for c in line["compared"]}["update_gap"]
    assert gap == pytest.approx(1.0)


def test_train_half_the_batch(monkeypatch, capsys):
    full = T.feed
    monkeypatch.setattr(T, "feed", lambda b: full(
        {k: v[: v.shape[0] // 2] for k, v in b.items()}))
    assert _run(capsys, _train_cell())["correct"] is False


def test_served_token_altered(monkeypatch, capsys, serve_root):
    make = S.make_engine

    def broken(cfg, tr, params, seed):
        engine = make(cfg, tr, params, seed)
        decode = engine.decode_active

        def altered(requests):
            out = decode(requests)
            slot = min(out)
            out[slot] = [(out[slot][0] + 1) % cfg.vocab_size] + out[slot][1:]
            return out
        engine.decode_active = altered
        return engine

    monkeypatch.setattr(S, "make_engine", broken)
    assert _run(capsys, SERVE_CELL)["correct"] is False


def test_train_control_fails():
    from bench import calibrate

    cell = R.load_cell(R.ROOT, _train_cell())
    run = D.Run(cell=cell, seed=int(SEED), seconds=0, trace=False,
                rehearsal=True, t_start=0.0)
    got = calibrate.train_readings(run, {"program", "control"})
    lim = {k: v["limit"] for k, v in cell.limits["limits"].items()}
    assert all(got["program"][k] <= lim[k] for k in lim)
    assert any(got["control"][k] > lim[k] for k in lim)
