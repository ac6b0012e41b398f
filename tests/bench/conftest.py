"""Shared by the benchmark's tests: ``bench`` imports from the checkout
root, and ``serve_checkout`` builds a checkout whose ``BENCHMARK.json``
also holds a serving cell, so that the serving harness is driven end to
end although no serving cell is committed yet."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SERVE_CELL = "serve.hyena-153m.chat.fp32"


@pytest.fixture
def serve_checkout(tmp_path):
    """The serving mix on the program's float32 path (where it agrees with
    the reference), as a cell added by new files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = tmp_path / "bench"
    mix = json.loads((bench / "traffic/serve.chat.json").read_text())
    mix.update(policy="fp32", matmul_precision="highest")
    (bench / "traffic/serve.chat.fp32.json").write_text(json.dumps(mix))
    (bench / f"limits/{SERVE_CELL}.json").write_text(json.dumps(
        {"control": "high", "limits": {"logit_gap": {"limit": 1e-3}}}))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": SERVE_CELL, "config": "hyena-153m",
                           "traffic": "serve.chat.fp32", "chips": 1,
                           "why": "test"})
    for name, better in (("ttft_p95_ms", "lower"), ("itl_p95_ms", "lower"),
                         ("serve_tokens_per_s", "higher")):
        b["end_to_end"].append({
            "name": name, "unit": "tokens/s" if "tokens" in name else "ms",
            "better": better, "bound": 0.25, "source": "host_clock",
            "workloads": [SERVE_CELL]})
    for name in ("mfu.prefill", "long_conv_roofline.prefill", "mfu.decode",
                 "decode_step_ms.serve", "idle_share.serve",
                 "generator_lag_p95_ms.serve"):
        b["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "serve engine",
            "moves": "itl_p95_ms", "workloads": [SERVE_CELL]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp_path
