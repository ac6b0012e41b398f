"""``chip_smoke.py``: its CPU rehearsal runs every phase at tiny sizes, and
without a TPU (or without the rest of the repo) it fails and reports no
device.

Each case runs the script in a child process, as a user would: the child
owns its JAX runtime, and this process never initializes a backend for it.
"""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(args, *, cwd=ROOT, devices=None, script=SCRIPT):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    if devices:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _last_json(proc):
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-3000:]
    return json.loads(lines[-1])


def test_cpu_rehearsal_runs_every_phase():
    proc = _run(["--cpu-rehearsal"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    for phase in ("[train]", "[kernels]", "[serve]"):
        assert f"{phase} phase_wall_s=" in proc.stdout, proc.stdout
    for tag in ("bf16", "fp32"):
        assert f"{tag} requests token-identical to generate(): 4/4" in (
            proc.stdout)
    last = _last_json(proc)
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


def test_cpu_rehearsal_cp_step_on_four_devices():
    proc = _run(["--cpu-rehearsal", "--chips", "4"], devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "[cp] rel_dloss=" in proc.stdout, proc.stdout
    assert "[train]" not in proc.stdout  # the cp step and nothing else
    assert _last_json(proc)["device"]["count"] == 4


def test_refuses_to_report_a_tpu_without_one():
    proc = _run([])
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"platform"' not in proc.stdout
    assert '"ok"' not in proc.stdout


def test_fails_without_the_rest_of_the_repo(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = _run(["--cpu-rehearsal"], cwd=tmp_path, script=str(lone))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
