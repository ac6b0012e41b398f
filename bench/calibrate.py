#!/usr/bin/env python3
"""Readings that the correctness limits are set from, for many seeds in
one process (no measured window for training cells).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        --variants program,control,half --out chiprun_out/calib.jsonl

For a training cell each seed gives the numbers compared (see
``drivers/train.py``) for these variants:
  * ``program``: the timed path, as a run reads it (the lower reading);
  * ``control``: the reference in the program's place, computed one step
    below the cell's stated precision (the limits file's ``control``, one
    of ``reference.CONTROLS``);
  * ``half``: the program fed half of each batch's rows, so that the mean
    is taken over the rest (a fault the comparison has to catch).
A step that leaves its state unchanged reads 1 on ``update_gap`` by
construction and needs no run.

For a serving cell each seed runs a short window at the cell's load and
gives ``logit_gap`` for ``program``, ``control`` (the control's first
choice at each served position, read against the float32 reference) and
``token`` (one served token altered where it is produced).  ``--rates``
instead serves one window per offered rate, to find the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
if sys.path and Path(sys.path[0]).resolve() == BENCH_DIR:
    sys.path[0] = str(ROOT)


def train_readings(run, variants):
    from bench import reference as ref
    from bench.drivers import train as T

    cfg, d, tr, corpus = T.sizes(run)
    out = {}
    prog = T.program_readings(run, cfg, d, tr, corpus)
    want = T.reference_readings(d, prog["optimizer"], prog["batches"],
                                run.seed)
    if "program" in variants:
        out["program"] = T.compare(prog, want)
    if "control" in variants:
        ctl = T.reference_readings(d, prog["optimizer"], prog["batches"],
                                   run.seed,
                                   *ref.CONTROLS[run.cell.limits["control"]])
        out["control"] = T.compare(ctl, want)
    if "half" in variants:
        full = T.feed
        T.feed = lambda b: full({k: v[: v.shape[0] // 2]
                                 for k, v in b.items()})
        try:
            half = T.program_readings(run, cfg, d, tr, corpus)
        finally:
            T.feed = full
        out["half"] = T.compare(half, want)
    return out


def sweep(run, rates):
    """Serving: one window per offered rate (requests a second), for
    finding the knee; returns the end-to-end numbers of each."""
    from bench.drivers import serve as S
    from bench import drivers as D

    cfg, d = D.model(run)
    rows = []
    for rate in rates:
        tr = dict(run.sizes("traffic"), rate_per_s=rate)
        reqs, results, w = S.program_window(run, cfg, d, tr)
        late = sum(1 for r in reqs if not r.times
                   or r.times[0] > run.seconds)
        rows.append({"rate_per_s": rate, **S.e2e(reqs, run.seconds,
                                                 w["end"]),
                     "requests": len(reqs), "first_token_after_window": late,
                     "drain_s": w["end"] - run.seconds})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--variants", default="program,control")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="window of a serving cell's readings")
    ap.add_argument("--rates", default="",
                    help="serving: comma-separated offered rates to sweep "
                         "instead of the readings (seed: the first)")
    ap.add_argument("--out", required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import drivers
    from bench import run as R

    cell = R.load_cell(ROOT, args.workload)
    if not args.cpu_rehearsal:
        R.use_cache()
    variants = set(args.variants.split(","))
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    if args.rates:
        run = drivers.Run(cell=cell, seed=int(args.seeds.split(",")[0]),
                          seconds=args.seconds, trace=False,
                          rehearsal=args.cpu_rehearsal,
                          t_start=time.perf_counter())
        for row in sweep(run, [float(r) for r in args.rates.split(",")]):
            print(json.dumps(row), flush=True)
            with out.open("a") as f:
                f.write(json.dumps({"workload": cell.name, **row}) + "\n")
        return 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run = drivers.Run(cell=cell, seed=seed, seconds=args.seconds,
                          trace=False, rehearsal=args.cpu_rehearsal,
                          t_start=t0, chips=int(cell.entry["chips"]))
        if cell.traffic["kind"] == "train":
            got = train_readings(run, variants)
        else:
            from bench.drivers import serve as S

            got = S.readings(run, variants)
        row = {"workload": cell.name, "seed": seed, **got,
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        with out.open("a") as f:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
