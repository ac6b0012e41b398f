#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <name> ... --cpu-rehearsal   # tests only

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything is
found by name: the configuration file the entry's config names, the
traffic mix ``bench/traffic/<traffic>.json`` (its ``kind`` picks the
driver ``bench/drivers/<kind>.py``), the correctness limits
``bench/limits/<workload>.json``, and each per-layer metric's reader
``bench/metrics/<metric>.py``.

The run sets up (weights from the seed, compilation, warm-up of every
shape the window uses), measures for ``--seconds``, then checks what the
timed path produced against the plain float32 reference
(``bench/reference.py``).  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window is traced and
the result carries its per-layer metrics.  The last line of standard
output is one JSON object; the last lines of standard error are the
numbers compared, each beside its limit.

Without a TPU, or with fewer chips than the cell asks for, the run exits
2 and prints no result.  ``--cpu-rehearsal`` runs the same code at the
tiny sizes that the configuration and traffic files name, on a host
without a TPU, and never reports ``"platform": "tpu"``.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH_DIR:
    sys.path[0] = str(ROOT)  # import bench.* as a package, shadow nothing


class CellError(Exception):
    """The cell cannot be run: a missing or inconsistent file, or no chip."""


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    entry: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    per_layer: List[Dict[str, Any]]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise CellError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise CellError(f"missing file {path}")
    return json.loads(path.read_text())


def load_cell(root: Path, workload: str) -> Cell:
    """Resolve a cell by name to its configuration, traffic, limits and
    per-layer metric files."""
    bench = _read_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise CellError(f"unknown workload {workload!r}; have "
                        f"{sorted(entries)}")
    entry = entries[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise CellError(f"cell {workload}: unknown config {entry['config']}")
    config = _read_json(root / configs[entry["config"]]["file"])
    bench_dir = root / "bench"
    traffic = _read_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    limits = _read_json(bench_dir / "limits" / f"{workload}.json")
    reported = {m["name"] for m in bench["end_to_end"]
                if workload in m.get("workloads", entries)}
    per_layer = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if (workload in cells) if cells is not None else (
                m["moves"] in reported):
            path = bench_dir / "metrics" / f"{m['name']}.py"
            if not path.is_file():
                raise CellError(f"metric {m['name']}: no reader at {path}")
            per_layer.append(m)
    for kind in (traffic["kind"],):
        if not (bench_dir / "drivers" / f"{kind}.py").is_file():
            raise CellError(f"traffic kind {kind!r} has no driver")
    return Cell(root, workload, entry, config, traffic, limits, per_layer)


def use_cache() -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, for every program the process compiles (the program's own
    cache helper takes the same variable)."""
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------- device

def device_info(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


# ------------------------------------------------------------------ main

def main(argv: Optional[List[str]] = None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on a host without a TPU (tests)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    try:
        cell = load_cell(ROOT, args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise CellError(f"the program (src/repro) is not in {ROOT}")
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # the TPU runtime would log under /tmp; the run writes nothing there
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if not args.cpu_rehearsal:
        use_cache()
    import jax

    chips = int(cell.entry["chips"])
    platform = jax.devices()[0].platform
    if args.cpu_rehearsal and platform == "tpu":
        print("bench: --cpu-rehearsal is for hosts without a TPU",
              file=sys.stderr)
        return 2
    if not args.cpu_rehearsal and platform != "tpu":
        print(f"bench: no TPU found (JAX platform {platform!r}); nothing "
              f"was run", file=sys.stderr)
        return 2
    if len(jax.devices()) < chips:
        print(f"bench: cell {cell.name} needs {chips} chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from bench import drivers

    driver = importlib.import_module(f"bench.drivers.{cell.traffic['kind']}")
    run = drivers.Run(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearsal=args.cpu_rehearsal,
        t_start=t_start, chips=chips,
    )
    res = driver.run(run)
    return report(cell, run, res)


def report(cell: Cell, run, res) -> int:
    """Print the numbers compared (stderr) and the result line (stdout)."""
    device = res.device
    checks = []
    ok = res.failed == 0 and res.attempted > 0
    for name, value, limit in res.compared:
        passed = _finite(value) and value <= limit
        ok = ok and passed
        checks.append({"name": name, "value": value, "limit": limit})
    metrics: Dict[str, Any] = {}
    if run.trace:
        from bench import work

        pk = work.peaks(device["kind"]) if device["platform"] == "tpu" \
            else None
        for m in cell.per_layer:
            reader = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py",
                                 f"bench.metrics.{m['name']}")
            value = reader.read(res.layer_ctx, pk)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=res.layer_ctx.reduced.busy_s,
                      window_s=res.layer_ctx.reduced.window_s)
    else:
        units = {m["name"]: m["unit"] for m in json.loads(
            (cell.root / "BENCHMARK.json").read_text())["end_to_end"]}
        for name, value in res.metrics.items():
            metrics[name] = {"value": value, "unit": units[name]}
    print(f"bench: {res.compiles_in_window} jit traces inside the window",
          file=sys.stderr)
    for c in checks:
        print(f"compared {c['name']}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    line = {"correct": bool(ok), "attempted": res.attempted,
            "failed": res.failed, "metrics": metrics, "device": device}
    if run.trace and res.layer_ctx is not None:
        from bench import trace_reduce

        line["breakdown"] = trace_reduce.breakdown(res.layer_ctx.reduced)
    line["compared"] = checks
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
