"""What every driver shares: the run's settings, its result, the cell's
model sizes, the benchmark's weights, and the traced window.

A driver is ``bench/drivers/<kind>.py`` with ``run(Run) -> Result``; the
kind comes from the traffic file.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import statistics
import tempfile
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from bench import reference as ref

# keys of a configuration file that are the model's sizes
MODEL_KEYS = (
    "n_layers", "d_model", "d_ff", "vocab_size", "hyena_order",
    "hyena_filter_width", "hyena_filter_depth", "hyena_pos_dim",
    "hyena_sine_freq", "hyena_decay", "mlp", "norm", "pattern",
    "tie_embeddings",
)


@dataclasses.dataclass
class Run:
    cell: Any  # run.Cell
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    t_start: float  # perf_counter at process start: set-up counts from here
    chips: int = 1

    def sizes(self, part: str) -> Dict[str, Any]:
        """The traffic (``part="traffic"``) or configuration file, with its
        ``rehearsal`` sizes applied in a rehearsal."""
        src = self.cell.traffic if part == "traffic" else self.cell.config
        out = {k: v for k, v in src.items() if k != "rehearsal"}
        if self.rehearsal:
            out.update(src.get("rehearsal", {}))
        return out

    def limit(self, name: str) -> float:
        return float(self.cell.limits["limits"][name]["limit"])


@dataclasses.dataclass
class LayerCtx:
    """What a per-layer metric reads."""

    reduced: Any  # trace_reduce.Reduced
    dims: ref.Dims
    shapes: Dict[str, Any]
    counters: Dict[str, Any]


@dataclasses.dataclass
class Result:
    attempted: int
    failed: int
    metrics: Dict[str, float]  # end-to-end, --trace 0
    compared: List[Tuple[str, float, float]]  # (name, value, limit)
    device: Dict[str, Any]
    layer_ctx: Optional[LayerCtx] = None
    compiles_in_window: int = 0


def model(run: Run):
    """(the program's ModelConfig, the reference's Dims) for the cell.
    Every size the configuration file states must be what the program's
    registry entry holds, except those the rehearsal shrinks."""
    from repro.configs import get_config

    c = run.sizes("config")
    cfg = get_config(c["registry"])
    if run.rehearsal:
        cfg = dataclasses.replace(cfg, **{
            k: v for k, v in run.cell.config.get("rehearsal", {}).items()})
    for k in MODEL_KEYS:
        have = getattr(cfg, k)
        want = c[k]
        if isinstance(have, tuple):
            want = tuple(want)
        if have != want:
            raise ValueError(f"config {c['registry']}: {k} is {have!r} in "
                             f"the program, {want!r} in the file")
    if cfg.pattern != ("hyena",) or cfg.mlp != "gelu" or cfg.norm != \
            "rmsnorm" or cfg.tie_embeddings:
        raise ValueError(f"the reference covers plain Hyena LMs, not "
                         f"{cfg.name}")
    return cfg, ref.Dims.from_config(c)


def init_weights(d: ref.Dims, seed: int):
    """The benchmark's weights from the seed: one jitted call on device."""
    import jax

    fn = jax.jit(lambda s: ref.init_params(s, d))
    return fn, fn(ref.seed_words(seed))


def check_layout(params, want) -> None:
    """The benchmark's weights must have the program's tree and shapes."""
    import jax

    a = jax.tree_util.tree_structure(params)
    b = jax.tree_util.tree_structure(want)
    if a != b:
        raise ValueError(f"weights tree differs from the program's:\n{a}\n{b}")
    for x, y in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(want)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"leaf {x.shape} {x.dtype} vs the program's "
                             f"{y.shape} {y.dtype}")


def policy(tr: Dict[str, Any]):
    """The program's precision policy that the traffic file states."""
    from repro.common.policy import get_policy

    return get_policy(tr.get("policy", "bf16"))


@contextlib.contextmanager
def program_precision(tr: Dict[str, Any]):
    """The program runs under the matmul precision that the traffic file
    states for its float32 matmuls (none stated: JAX's default)."""
    import jax

    mp = tr.get("matmul_precision")
    if mp is None:
        yield
        return
    with jax.default_matmul_precision(mp):
        yield


def free_device() -> None:
    """Drop the arrays nothing refers to any more."""
    gc.collect()


@contextlib.contextmanager
def traced(enabled: bool):
    """Trace the block with the profiler into a temporary directory;
    yields a holder whose ``reduced`` is set after the block."""
    holder = type("Trace", (), {"reduced": None})()
    if not enabled:
        yield holder
        return
    import jax

    from bench import trace_reduce

    tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    try:
        with jax.profiler.trace(str(tmp), create_perfetto_trace=True):
            yield holder
        files = sorted(tmp.glob("plugins/profile/*/*.trace.json.gz"))
        if not files:
            raise RuntimeError("the profiler wrote no perfetto trace")
        holder.reduced = trace_reduce.reduce_trace(
            trace_reduce.load_events(files[-1]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


@contextlib.contextmanager
def count_compiles():
    """Count the jit traces (new shapes, each a compile or a cache load)
    inside the block; the window should have none."""
    import jax.monitoring as mon

    holder = type("Compiles", (), {"n": 0})()

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            holder.n += 1

    mon.register_event_duration_secs_listener(listen)
    try:
        yield holder
    finally:
        mon.unregister_event_duration_listener(listen)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


def quantile(values, q: float) -> float:
    """The q-quantile (0 < q < 1) by ``statistics.quantiles`` (inclusive
    method), or the one value there is."""
    values = sorted(values)
    if len(values) == 1:
        return float(values[0])
    qs = statistics.quantiles(values, n=100, method="inclusive")
    return float(qs[int(round(q * 100)) - 1])
