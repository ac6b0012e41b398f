# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

  bench_recall    — Fig 4.1 / Table 4.2 (associative recall, implicit vs
                    explicit filter parameterization, vocab scaling)
  bench_lm_flops  — Table 4.4 (GPT vs Hyena total-FLOP accounting)
  bench_runtime   — Fig 4.3 (operator runtime crossover vs attention)
  bench_kernels   — §4.4 supplement (conv backend micro-bench)
  bench_roofline  — §Roofline terms from the multi-pod dry-run artifacts

``--json PATH`` additionally writes the rows as a machine-readable artifact.
Convention: perf-trajectory artifacts are committed as ``BENCH_<topic>.json``
at the repo root (``BENCH_conv.json`` = the conv-backend/gated-fusion rows
from ``--only kernels``), so successive PRs are held to a measured baseline.
Each artifact records the jax backend and device — CI writes interpret/CPU
numbers, which are comparable only to other CI runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from repro.common import compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single bench module")
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write rows to PATH as a BENCH_*.json artifact",
    )
    args = ap.parse_args()
    compile_cache.enable()

    from benchmarks import (
        bench_kernels,
        bench_lm_flops,
        bench_recall,
        bench_roofline,
        bench_runtime,
    )

    modules = {
        "recall": bench_recall,
        "lm_flops": bench_lm_flops,
        "runtime": bench_runtime,
        "kernels": bench_kernels,
        "roofline": bench_roofline,
    }
    if args.only:
        modules = {args.only: modules[args.only]}

    rows = []
    errors = []
    spans = {}  # module -> (start, end) row indices, for the summary block
    print("name,us_per_call,derived")
    for name, mod in modules.items():
        try:
            start = len(rows)
            mod.run(rows)
            spans[name] = (start, len(rows))
            for r in rows[start:]:
                print(f"{r[0]},{r[1]:.1f},{r[2]}")
                sys.stdout.flush()
        except Exception:
            err = traceback.format_exc(limit=1)
            errors.append({"module": name, "error": err})
            print(f"{name}/ERROR,0.0,{err!r}")

    if args.json:
        import jax

        # schema 2: one scalar headline metric per suite so perf-trajectory
        # tooling can plot the history without knowing each suite's row
        # vocabulary.  A module nominates its headline row via HEADLINE;
        # otherwise its first row stands in.
        summary = {}
        for name, mod in modules.items():
            start, end = spans.get(name, (0, 0))
            mod_rows = rows[start:end]
            if not mod_rows:
                continue
            headline = getattr(mod, "HEADLINE", None)
            pick = next(
                (r for r in mod_rows if r[0] == headline), mod_rows[0]
            )
            summary[name] = {
                "metric": pick[0],
                "value": round(float(pick[1]), 1),
                "unit": "us_per_call",
            }
        artifact = {
            "schema": 2,
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]).split(":")[0],
            "modules": sorted(modules),
            "summary": summary,
            "rows": [
                {"name": n, "us_per_call": round(t, 1), "derived": str(d)}
                for n, t, d in rows
            ],
            "errors": errors,
        }
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.json} ({len(rows)} rows)", file=sys.stderr)
    if errors:
        sys.exit(f"{len(errors)} bench module(s) failed: "
                 f"{', '.join(e['module'] for e in errors)}")


if __name__ == "__main__":
    main()
