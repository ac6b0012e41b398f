"""Reduce a profiler trace (the perfetto JSON that
``jax.profiler.trace(..., create_perfetto_trace=True)`` writes) to what
the per-layer metrics read.

* Device ops are the events of each device's "XLA Ops" line.  That line
  nests: a ``while`` (the layer scan) encloses its body's ops.  Only leaf
  ops count: an op that encloses another op of its line, or whose HLO
  category is a control-flow container, is dropped, so no time is summed
  twice.
* Busy time is the union of the leaf-op intervals inside the window,
  averaged over the devices; the idle share is one minus busy over window.
* Each leaf op is attributed to a layer by ``layers.json``: the first
  layer one of whose source files ends its ``source`` path, one of whose
  substrings is in its ``tf_op``, or one of whose name prefixes starts its
  name.  Unmatched ops are "other".
* Program runs are the events of the "XLA Modules" line, classed by the
  program names in ``layers.json``.
* Host spans are the benchmark's own ``TraceAnnotation`` events, named
  ``bench.*``; the ``bench.window`` span bounds the window.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

LAYERS_FILE = Path(__file__).with_name("layers.json")
CONTAINER_CATEGORIES = {"while", "conditional", "call"}
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    start: float  # seconds
    dur: float
    name: str
    category: str
    tf_op: str
    source: str
    layer: str = "other"
    device: int = 0

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Span:
    start: float
    dur: float
    name: str

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Reduced:
    ops: List[Op]  # leaf ops inside the window, all devices
    programs: List[Span]  # program runs (name = class), all devices
    spans: List[Span]  # host spans
    window: Tuple[float, float]
    n_devices: int
    busy_s: float  # mean over devices

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def layer_time(self, layer: str, within: Optional[str] = None) -> float:
        """Device seconds of ``layer``'s ops (summed over devices),
        optionally only those inside runs of program class ``within``."""
        ops = [o for o in self.ops if o.layer == layer]
        if within is not None:
            ops = _inside(ops, [p for p in self.programs if p.name == within])
        return sum(o.dur for o in ops)

    def program_runs(self, cls: str) -> List[Span]:
        return [p for p in self.programs if p.name == cls]


def load_events(path: Path) -> dict:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def _inside(ops: List[Op], runs: List[Span]) -> List[Op]:
    runs = sorted(runs, key=lambda r: r.start)
    starts = [r.start for r in runs]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i >= 0 and o.start < runs[i].end:
            out.append(o)
    return out


def leaf_ops(ops: List[Op]) -> List[Op]:
    """Drop containers: ops of a control-flow category, and any op that
    encloses another op of the same line."""
    ops = sorted(ops, key=lambda o: (o.start, -o.dur))
    container = [o.category in CONTAINER_CATEGORIES for o in ops]
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end + 1e-12:
            container[stack[-1]] = True
        stack.append(i)
    return [o for o, c in zip(ops, container) if not c]


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def classify(op: Op, rules: List[dict]) -> str:
    for r in rules:
        if op.source and any(
                op.source.split(":")[0].endswith(s) for s in r["sources"]):
            return r["layer"]
        if any(t in op.tf_op for t in r["tf_op"]):
            return r["layer"]
        if any(op.name.startswith(n) for n in r["names"]):
            return r["layer"]
    return "other"


def reduce_trace(trace: dict, layers_file: Path = LAYERS_FILE,
                 window: Optional[Tuple[float, float]] = None) -> Reduced:
    spec = json.loads(Path(layers_file).read_text())
    events = trace["traceEvents"]
    proc, thread = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            thread[(e["pid"], e.get("tid"))] = e["args"]["name"]
    dev_pids = sorted(p for p, n in proc.items()
                      if n.startswith("/device:") and "CUSTOM" not in n)
    ops_by_dev: Dict[int, List[Op]] = defaultdict(list)
    programs: List[Span] = []
    spans: List[Span] = []
    classes = spec.get("programs", {})
    for e in events:
        if e.get("ph") != "X":
            continue
        pid = e["pid"]
        line = thread.get((pid, e.get("tid")), "")
        start, dur = e["ts"] * 1e-6, e.get("dur", 0.0) * 1e-6
        if pid in dev_pids and line == "XLA Ops":
            a = e.get("args", {})
            ops_by_dev[pid].append(Op(
                start, dur, e["name"], a.get("hlo_category", ""),
                a.get("tf_op", ""), a.get("source", ""),
                device=dev_pids.index(pid)))
        elif pid in dev_pids and line == "XLA Modules":
            for cls, names in classes.items():
                if any(n in e["name"] for n in names):
                    programs.append(Span(start, dur, cls))
                    break
        elif e["name"].startswith(SPAN_PREFIX):
            spans.append(Span(start, dur, e["name"]))
    if window is None:
        w = [s for s in spans if s.name == WINDOW_SPAN]
        if not w:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        window = (w[0].start, w[0].end)
    lo, hi = window
    rules = spec["ops"]
    ops: List[Op] = []
    busy = []
    for pid in dev_pids:
        leaves = [o for o in leaf_ops(ops_by_dev[pid])
                  if o.start >= lo and o.end <= hi]
        for o in leaves:
            o.layer = classify(o, rules)
        ops.extend(leaves)
        busy.append(union_length([(o.start, o.end) for o in leaves]))
    n_dev = max(len(dev_pids), 1)
    programs = [p for p in programs if p.start >= lo and p.end <= hi]
    return Reduced(ops, programs, spans, window, n_dev,
                   sum(busy) / n_dev if busy else 0.0)


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps of
    the first device labelled by the innermost benchmark span open
    across them."""
    by_op: Dict[str, float] = defaultdict(float)
    for o in r.ops:
        by_op[f"{o.layer}: {o.name} ({o.category})"] += o.dur
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    dev0 = merged([(o.start, o.end) for o in r.ops if o.device == 0])
    lo, hi = r.window
    gaps, prev = [], lo
    for s, e in dev0:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    inner = [s for s in r.spans if s.name != WINDOW_SPAN]
    out = []
    for gs, ge in gaps:
        best, best_key = "host outside benchmark spans", None
        for s in inner:
            ov = min(ge, s.end) - max(gs, s.start)
            if ov <= 0:
                continue
            key = (ov, -s.dur)
            if best_key is None or key > best_key:
                best, best_key = s.name, key
        out.append([best, ge - gs])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": out}
