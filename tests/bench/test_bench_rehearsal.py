"""The command end to end: a CPU rehearsal of a train and a serve cell
prints a contract-shaped last line that never names a TPU; without a chip,
or without the program beside the benchmark, it exits non-zero and prints
no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path


SERVE_CELL = "serve.hyena-153m.chat.fp32"  # the serve_checkout fixture's cell

ROOT = Path(__file__).resolve().parents[2]
SEED = str(2 ** 40 + 12345)  # more than 32 bits


def _run(args, cwd=ROOT, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout)


def _train_cell():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    return next(w["name"] for w in b["workloads"]
                if w["name"].startswith("train."))


def _check_line(p, metric):
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert line["correct"] is True
    assert line["device"]["platform"] != "tpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {metric, "setup_s"} <= set(line["metrics"])
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    # the numbers compared close standard error, each beside its limit
    tail = p.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)
    assert list(line)[-1] == "compared"


def test_cpu_rehearsal_train_cell():
    p = _run(["--workload", _train_cell(), "--seed", SEED, "--seconds", "1",
              "--trace", "0", "--cpu-rehearsal"])
    _check_line(p, "train_tokens_per_s")


def test_cpu_rehearsal_serve_cell(serve_checkout):
    p = _run(["--workload", SERVE_CELL, "--seed", SEED, "--seconds", "1",
              "--trace", "0", "--cpu-rehearsal"], cwd=serve_checkout)
    _check_line(p, "ttft_p95_ms")


def test_without_a_chip_exits_nonzero_and_prints_nothing():
    p = _run(["--workload", _train_cell(), "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for path in b["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(["--workload", _train_cell(), "--seed", "1",
              "--seconds", "1", "--trace", "0", "--cpu-rehearsal"],
             cwd=tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
