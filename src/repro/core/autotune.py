"""Autotuned conv-plan cache (DESIGN.md §8).

Every Pallas conv kernel and the blockfft backend have shape-sensitive tile
parameters (``block_l``/``block_d`` for the short conv, ``chunk``/``block_d``
for the Toeplitz kernel, the (R, S) factor split for the four-step FFT).
Hand-picked defaults are wrong somewhere; this module replaces them with a
per-``(kind, B, L, D, dtype, platform)`` *plan*: a small dict of tile
parameters that was timed-searched once and persisted, so model code never
names a tile size (`repro.kernels.ops` consults the cache at dispatch).
The platform is part of the key so tiles timed on one device class (or the
CPU interpreter) are never served to another.

Mode is controlled by ``$REPRO_AUTOTUNE``:

  * ``off``   (default) — plans are never consulted; kernel defaults apply.
  * ``search`` — cache miss triggers a timed search over the caller's
    candidate list (synthetic inputs at the real shape, best wall-clock
    wins); the winner is persisted to the plan file and reused.
  * ``load``  — plans are read from the plan file; a missing entry falls
    back to kernel defaults (never searches — safe for serving, where a
    surprise multi-second search on the first request of a new shape is an
    outage, not an optimization).

The plan file (``$REPRO_AUTOTUNE_FILE``, default
``~/.cache/repro/conv_plans.json``) is a flat JSON object
``{plan_key: {param: value}}`` — human-diffable, written atomically
(temp file + rename), and tolerant of corruption (a bad file is treated as
empty rather than taking the model down).

Plans are *semantics-preserving by construction*: candidate lists only ever
contain parameter points that compute the identical convolution (tile sizes,
factor splits).  Approximation knobs — the Toeplitz kernel's banded
``n_chunk_diags`` — are part of the plan **key**, chosen by the caller, and
never searched over.
"""
from __future__ import annotations

import json
import logging
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp

ENV_MODE = "REPRO_AUTOTUNE"
ENV_FILE = "REPRO_AUTOTUNE_FILE"
MODES = ("off", "search", "load")
_DEFAULT_FILE = os.path.join("~", ".cache", "repro", "conv_plans.json")

_log = logging.getLogger(__name__)
_lock = threading.Lock()
# in-memory mirror of the plan file, keyed by resolved path (the env var can
# change between calls — tests point it at tmp dirs); each entry carries the
# file's (mtime_ns, size) signature so a plan file written by ANOTHER
# process after our first read (offline searcher feeding a load-mode
# server) is picked up without a restart
_mem: Dict[str, tuple] = {}


def mode() -> str:
    m = os.environ.get(ENV_MODE, "off") or "off"
    if m not in MODES:
        raise ValueError(
            f"${ENV_MODE}={m!r}; expected one of {MODES}"
        )
    return m


def plan_file() -> str:
    return os.path.expanduser(os.environ.get(ENV_FILE) or _DEFAULT_FILE)


def plan_key(kind: str, shape: Sequence[int], dtype) -> str:
    # the platform is part of the key: tiles timed on one device class
    # (worse: the Pallas *interpreter* on CPU) must never be served to
    # another — the shared default plan file makes that cross-talk easy
    B, L, D = shape
    return (
        f"{kind}:B{B}:L{L}:D{D}:{jnp.dtype(dtype).name}"
        f":{jax.default_backend()}"
    )


def reset_cache() -> None:
    """Drop the in-memory mirror (tests switch plan files mid-process)."""
    with _lock:
        _mem.clear()


def _file_sig(path: str):
    try:
        st = os.stat(path)
        return (st.st_mtime_ns, st.st_size)
    except OSError:
        return None  # missing file


def _load(path: str) -> Dict[str, Dict[str, Any]]:
    sig = _file_sig(path)
    hit = _mem.get(path)
    if hit is not None and hit[0] == sig:
        return hit[1]
    plans: Dict[str, Dict[str, Any]] = {}
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            plans = {
                k: dict(v) for k, v in raw.items() if isinstance(v, dict)
            }
    except (OSError, ValueError):
        pass  # missing or corrupt plan file == no plans
    _mem[path] = (sig, plans)
    return plans


def _persist(path: str, plans: Dict[str, Dict[str, Any]]) -> None:
    """Merge-then-replace: re-read the file so concurrent searchers (other
    processes sharing the plan file) don't have their fresh keys clobbered
    by this process's stale in-memory mirror; last writer wins per-key
    only, never per-file."""
    _mem.pop(path, None)
    merged = dict(_load(path))
    merged.update(plans)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d or ".", suffix=".plans")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic on POSIX
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    _mem[path] = (_file_sig(path), merged)


def _time_once(fn: Callable[[], Any], iters: int = 3) -> float:
    jax.block_until_ready(fn())  # compile + warm-up
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def search(
    candidates: Iterable[Dict[str, Any]],
    run: Callable[..., Any],
) -> Optional[Dict[str, Any]]:
    """Best-wall-clock candidate (min over iters).  A candidate that raises
    (a tile that doesn't divide the shape, a block the compiler refuses)
    is logged and skipped; if every candidate raises, the search raises
    with the last error as its cause — a plan never silently degrades to
    the defaults that were just refused."""
    best, best_t = None, float("inf")
    failures = []
    for cand in candidates:
        try:
            t = _time_once(lambda: run(**cand))
        except Exception as e:  # each candidate is an independent trial
            _log.warning("autotune: skipping candidate %s: %r", cand, e)
            failures.append(e)
            continue
        if t < best_t:
            best, best_t = dict(cand), t
    if best is None and failures:
        raise RuntimeError(
            f"autotune: all {len(failures)} candidates failed"
        ) from failures[-1]
    return best


def plan_for(
    kind: str,
    shape: Sequence[int],
    dtype,
    *,
    candidates: Sequence[Dict[str, Any]],
    run: Callable[..., Any],
) -> Optional[Dict[str, Any]]:
    """The one entry point kernels dispatch through.

    Returns the plan dict for ``(kind, shape, dtype)`` or ``None`` (use the
    kernel's defaults).  ``run(**candidate)`` must execute the kernel on
    *synthetic* inputs of the given shape — it is called (and timed) only in
    ``search`` mode on a cache miss, and must not close over tracers (plans
    are consulted from inside jit traces, where timing the traced values
    would be meaningless).
    """
    m = mode()
    if m == "off" or not candidates:
        return None
    # a plan is only usable if the kernel knows its params: keys outside
    # the candidate vocabulary (schema drift, hand-edited file) are
    # dropped so a stale plan file degrades to defaults instead of a
    # TypeError on the first request of a shape — load is serving-safe
    allowed = set()
    for c in candidates:
        allowed.update(c)
    path = plan_file()
    key = plan_key(kind, shape, dtype)
    with _lock:
        plans = _load(path)
        if key in plans:
            plan = {k: v for k, v in plans[key].items() if k in allowed}
            return plan or None
        if m != "search":
            return None
    best = search(candidates, run)
    if best is None:
        return None
    with _lock:
        plans = dict(_load(path))
        plans.setdefault(key, best)
        _persist(path, plans)
        return {k: v for k, v in plans[key].items() if k in allowed}
