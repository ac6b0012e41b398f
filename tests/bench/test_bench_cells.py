"""Every cell resolves to its files by name, and a new cell or per-layer
metric comes in as new files and entries, editing no file of ``bench/``."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from bench import run as R

ROOT = Path(__file__).resolve().parents[2]


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in _bench()[
    "workloads"]])
def test_cell_resolves(workload):
    from bench import drivers

    cell = R.load_cell(ROOT, workload)
    assert cell.traffic["kind"] in ("train", "serve")
    assert (ROOT / "bench/drivers" / f"{cell.traffic['kind']}.py").is_file()
    assert cell.config["registry"] == cell.entry["config"]
    for name in cell.limits["limits"]:
        assert cell.limits["limits"][name]["limit"] > 0
    b = _bench()
    moved = {m["name"] for m in b["end_to_end"]
             if workload in m.get("workloads", [workload])}
    assert "setup_s" in moved and len(moved) >= 2
    assert cell.per_layer and all(m["moves"] in moved
                                  for m in cell.per_layer)
    # the configuration file states what the program's registry holds
    run = drivers.Run(cell=cell, seed=1, seconds=1, trace=False,
                      rehearsal=False, t_start=0.0)
    cfg, d = drivers.model(run)
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff) == (
        d.n_layers, d.d_model, d.d_ff)


def _digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_cell_and_metric_as_new_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path)
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    old = b["workloads"][0]
    traffic = json.loads(
        (tmp_path / f"bench/traffic/{old['traffic']}.json").read_text())
    traffic.update(batch=4, seq_len=4096)
    (tmp_path / "bench/traffic/train.b4-l4096.json").write_text(
        json.dumps(traffic))
    new = "train.hyena-153m.b4-l4096"
    (tmp_path / f"bench/limits/{new}.json").write_text(
        (tmp_path / f"bench/limits/{old['name']}.json").read_text())
    (tmp_path / "bench/metrics/step_ms.train.py").write_text(
        "def read(ctx, peaks):\n    return None\n")
    b["workloads"].append({"name": new, "config": old["config"],
                           "traffic": "train.b4-l4096", "chips": 1,
                           "why": "longer rows"})
    for m in b["end_to_end"] + b["per_layer"]:
        if old["name"] in m.get("workloads", []):
            m["workloads"].append(new)
    b["per_layer"].append({
        "name": "step_ms.train", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": [new]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = R.load_cell(tmp_path, new)
    assert (cell.traffic["batch"], cell.traffic["seq_len"]) == (4, 4096)
    names = {m["name"] for m in cell.per_layer}
    assert "step_ms.train" in names and "mfu.train" in names
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 3


def test_serving_cell_comes_in_by_files_and_entries(serve_checkout):
    SERVE_CELL = "serve.hyena-153m.chat.fp32"  # serve_checkout's cell

    cell = R.load_cell(serve_checkout, SERVE_CELL)
    assert cell.traffic["kind"] == "serve"
    assert {m["name"] for m in cell.per_layer} >= {"mfu.decode",
                                                    "idle_share.serve"}
    assert _digest(ROOT).keys() <= _digest(serve_checkout).keys()


def test_unknown_cell_and_missing_file_are_refused(tmp_path):
    with pytest.raises(R.CellError):
        R.load_cell(ROOT, "no.such.cell")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    w = _bench()["workloads"][0]
    (tmp_path / f"bench/limits/{w['name']}.json").unlink()
    with pytest.raises(R.CellError):
        R.load_cell(tmp_path, w["name"])
