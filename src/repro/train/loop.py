"""The one preemption-safe, resumable training loop (DESIGN.md §10).

Every training entry point — both examples, the kill-and-resume tests, and
(through :func:`repro.train.trainer.abstract_train_state`) the multi-pod
dry-run — drives :class:`TrainLoop` instead of hand-rolling its own
step/checkpoint/telemetry lifecycle.  The loop owns, on the shared
``ExecutionContext`` substrate (§9):

  * **Resume-from-latest-committed.**  One checkpoint tree
    ``{"train": state, "rng": base_key}`` + manifest meta
    ``{"step", "loader"}`` captures everything a bit-exact restart needs:
    train state (params / Adam moments / compression residuals), the
    data-loader cursor, the loop's base PRNG key, and the step count.
    Restore places every leaf through ``ctx.train_state_shardings`` — an
    elastic re-mesh restart lands sharded by rule, not by replaying the
    original topology.
  * **Async checkpointing** overlapped with compute
    (:class:`repro.train.checkpoint.AsyncCheckpointer`), with explicit
    retention (``keep_last``) and bounded-backoff retry on restore I/O.
  * **Preemption draining** — SIGTERM sets a flag; the loop finishes the
    in-flight step, writes a final committed checkpoint at the step
    boundary, and returns ``status="preempted"``.
  * **Telemetry** — straggler EWMA, heartbeat liveness file, per-step
    ``on_step`` hook, periodic logging.  Dispatch returns before the
    device has run a step, so steps are timed only over intervals that
    end in a host sync (``_StepClock``); the profiler sees the loop's
    ``repro.train.data``, ``repro.train.sync`` and
    ``repro.train.checkpoint`` spans on the device's clock.

Data sources are either a *stateless* callable ``(step, rng) -> batch``
(synthetic tasks: resume needs only the step and the checkpointed base
key) or a *stateful* stream exposing ``next_batch()/state()/restore()``
(:class:`repro.data.lm_data.TokenStream`); the loop wraps streams in a
:class:`~repro.data.lm_data.Prefetcher` *after* restoring the cursor and
checkpoints the consumed-batch cursor, never the prefetch head.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.data import lm_data
from repro.train import checkpoint as ckpt
from repro.common import ft
from repro.train.trainer import (
    TrainConfig,
    abstract_train_state,
    init_train_state,
    jit_train_step,
)


def _span(name: str):
    return jax.profiler.TraceAnnotation(f"repro.train.{name}")


class _StepClock:
    """Steps and tokens since the last lap, and the seconds they took.
    A lap is taken right after a host sync, so the interval holds the
    device's work for those steps and not only their dispatch."""

    def __init__(self):
        self.start = time.perf_counter()
        self.steps = self.tokens = 0

    def add(self, tokens: int) -> None:
        self.steps += 1
        self.tokens += tokens

    def lap(self):
        """(steps, seconds, tokens) since the last lap; starts the next."""
        now = time.perf_counter()
        out = (self.steps, now - self.start, self.tokens)
        self.start, self.steps, self.tokens = now, 0, 0
        return out


@dataclasses.dataclass(frozen=True)
class LoopConfig:
    total_steps: int
    ckpt_dir: Optional[str] = None  # None = no checkpointing/heartbeat
    ckpt_every: int = 100
    keep_last: int = 3
    heartbeat_interval: Optional[float] = 30.0  # None = no heartbeat file
    log_every: int = 20
    prefetch_depth: int = 2
    donate: bool = True
    straggler_threshold: float = 2.0
    restore_attempts: int = 3  # bounded-backoff retry on restore I/O

    def __post_init__(self):
        if self.total_steps < 1:
            raise ValueError("total_steps must be >= 1")
        if self.ckpt_every < 1:
            raise ValueError("ckpt_every must be >= 1")
        if self.keep_last < 1:
            raise ValueError("keep_last must be >= 1 (explicit retention)")


@dataclasses.dataclass
class LoopResult:
    status: str  # "done" | "preempted"
    state: Any
    step: int  # completed steps
    history: List[float]  # per-step loss, this run only
    metrics: Dict[str, float]  # last step's metrics (host floats)
    stragglers: int


class TrainLoop:
    """Owns the step/checkpoint/telemetry lifecycle for one training run."""

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainConfig,
        lcfg: LoopConfig,
        *,
        mesh=None,
        handler: Optional[ft.PreemptionHandler] = None,
        log: Callable[[str], None] = print,
    ):
        self.cfg = cfg
        self.tcfg = tcfg
        self.lcfg = lcfg
        self.ectx = tcfg.apply_context(mesh=mesh)
        self.log = log
        # injectable for tests (signals=()); created lazily otherwise so
        # constructing a loop off the main thread stays legal
        self._handler = handler
        self.monitor = ft.StragglerMonitor(threshold=lcfg.straggler_threshold)
        self._struct, self._axes = abstract_train_state(cfg, tcfg)
        self._step_fn = jit_train_step(cfg, tcfg, donate=lcfg.donate)

    # ------------------------------------------------------------- restore
    def _ckpt_shardings(self):
        if self.ectx.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        return {
            "train": self.ectx.train_state_shardings(self._axes, self._struct),
            "rng": NamedSharding(self.ectx.mesh, P()),
        }

    def restore_or_init(self, key: jax.Array):
        """(state, base_key, start_step, loader_state) — from the latest
        committed checkpoint when one exists, else a fresh init from
        ``key``.  Leaves land placed by the context's rules either way."""
        shardings = self._ckpt_shardings()
        d = self.lcfg.ckpt_dir
        if d and ckpt.latest_step(d) is not None:
            like = {
                "train": self._struct,
                "rng": jax.eval_shape(lambda: jax.random.PRNGKey(0)),
            }
            tree, meta, step = ft.retry(
                lambda: ckpt.restore(d, like, shardings=shardings),
                attempts=self.lcfg.restore_attempts,
            )
            return tree["train"], tree["rng"], step, meta.get("loader")
        state, _ = init_train_state(key, self.cfg, self.tcfg)
        if shardings is not None:
            state = self.ectx.place(state, shardings["train"])
        return state, key, 0, None

    # ---------------------------------------------------------------- data
    def _wrap_data(self, data, loader_state, resumed: bool):
        """Returns (fetch(step, rng) -> batch, loader_meta() -> state|None,
        close()).  Source kind and checkpointed loader state must agree in
        BOTH directions — a mid-trajectory source swap would silently fork
        the run from its uninterrupted twin."""
        if callable(data):
            if loader_state is not None and "cursor" in loader_state:
                # a stream checkpoint can't drive a stateless source
                raise ValueError(
                    "checkpoint carries a loader cursor but the data source "
                    "is a stateless callable"
                )
            return (lambda step, rng: data(step, rng)), (lambda: None), (lambda: None)
        if loader_state is not None:
            data.restore(loader_state)
        elif resumed:
            # the opposite swap: a checkpoint written with a stateless
            # source cannot position a stream — it would restart at
            # cursor 0 mid-trajectory
            raise ValueError(
                "checkpoint has no loader cursor but the data source is a "
                "stream — resuming would replay batches from cursor 0"
            )
        pf = lm_data.Prefetcher(data, depth=self.lcfg.prefetch_depth)
        return (
            (lambda step, rng: pf.next()),
            (lambda: getattr(pf, "consumed_state", None)),
            pf.close,
        )

    def _place_batch(self, batch):
        out = {}
        for k, v in batch.items():
            if v is None:
                continue
            v = jnp.asarray(v)
            if self.ectx.mesh is not None:
                seq = v.shape[1] if v.ndim >= 2 else None
                v = jax.device_put(
                    v, self.ectx.data_sharding(v.ndim, v.shape[0], seq)
                )
            out[k] = v
        return out

    # ----------------------------------------------------------------- run
    def run(
        self,
        data,
        *,
        key: Optional[jax.Array] = None,
        on_step: Optional[Callable[[int, Dict[str, float], float], None]] = None,
    ) -> LoopResult:
        """Train to ``total_steps`` (or a preemption boundary).

        ``data``: stateless ``(step, rng) -> batch`` callable or a stateful
        stream (see module docstring).  ``key`` seeds a fresh run; once a
        checkpoint exists the checkpointed base key wins, so restarts never
        fork the trajectory.  ``on_step(step, metrics, seconds)`` fires
        after every step (telemetry hook; step counts completed steps);
        reading ``metrics`` to the host syncs, so ``seconds`` is the
        step's time from the previous sync to this one.
        """
        lcfg = self.lcfg
        if key is None:
            key = jax.random.PRNGKey(0)
        state, base_key, start, loader_state = self.restore_or_init(key)
        if start >= lcfg.total_steps:
            self.log(f"nothing to do: checkpoint at step {start}")
            return LoopResult("done", state, start, [], {}, 0)
        if start:
            self.log(f"resumed from step {start} (latest committed)")
        fetch, loader_meta, close_data = self._wrap_data(
            data, loader_state, resumed=start > 0
        )

        handler = self._handler or ft.PreemptionHandler()
        writer = heartbeat = None
        if lcfg.ckpt_dir:
            os.makedirs(lcfg.ckpt_dir, exist_ok=True)
            writer = ckpt.AsyncCheckpointer(lcfg.ckpt_dir, lcfg.keep_last)
            if lcfg.heartbeat_interval:
                heartbeat = ft.Heartbeat(
                    os.path.join(lcfg.ckpt_dir, "heartbeat"),
                    lcfg.heartbeat_interval,
                )
                heartbeat.start()

        steps_clock, log_clock = _StepClock(), _StepClock()

        def save(step: int):
            if writer is not None:
                with _span("checkpoint"):
                    writer.save(
                        step,
                        {"train": state, "rng": base_key},
                        meta={"step": step, "loader": loader_meta()},
                    )
                steps_clock.lap()  # the save is not a step's time

        def synced(step: int) -> bool:
            """After a host sync: the steps since the last one feed the
            straggler monitor their mean time; True if they were slow."""
            n, secs, _ = steps_clock.lap()
            return n > 0 and self.monitor.record(step, secs / n)

        # per-step losses stay device-side between boundaries so the host
        # never blocks on step i before dispatching step i+1; they flush
        # to host floats (one batched transfer) at every log/checkpoint/
        # preempt boundary, keeping at most ~ckpt_every scalars alive
        history: List[float] = []
        pending: List[Any] = []
        metrics: Dict[str, Any] = {}
        to_host = lambda m: {k: float(v) for k, v in m.items()}

        def flush_history(step: int) -> bool:
            if pending:
                with _span("sync"):
                    got = jax.device_get(list(pending))
                history.extend(float(x) for x in got)
                pending.clear()
            return synced(step)

        status = "done"
        last_saved = -1
        slow = False
        try:
            with self.ectx.scope():
                for i in range(start, lcfg.total_steps):
                    with _span("data"):
                        batch = self._place_batch(
                            fetch(i, jax.random.fold_in(base_key, i))
                        )
                    state, metrics = self._step_fn(state, batch)
                    pending.append(metrics["loss"])
                    tokens = batch["tokens"].size if "tokens" in batch else 0
                    steps_clock.add(tokens)
                    log_clock.add(tokens)
                    done = i + 1
                    if on_step is not None:
                        with _span("sync"):
                            host = to_host(metrics)
                        dt = steps_clock.lap()[1]
                        slow = self.monitor.record(done, dt) or slow
                        on_step(done, host, dt)
                        steps_clock.lap()  # the hook's time is no step's
                    if done % lcfg.ckpt_every == 0 and done < lcfg.total_steps:
                        slow = flush_history(done) or slow
                        save(done)
                        last_saved = done
                    if handler.preempted():
                        # drain: final committed checkpoint at this step
                        # boundary, then a clean exit the controller can
                        # restart from
                        if last_saved != done:
                            save(done)
                            last_saved = done
                        status = "preempted"
                        self.log(
                            f"preempted — committed step {done}, exiting"
                        )
                        flush_history(done)
                        return LoopResult(
                            status, state, done, history,
                            to_host(metrics), self.monitor.stragglers,
                        )
                    if done % lcfg.log_every == 0 or done == lcfg.total_steps:
                        slow = flush_history(done) or slow
                        _, secs, tok = log_clock.lap()
                        self.log(
                            f"step {done:5d} loss {history[-1]:.3f} "
                            f"gnorm {float(metrics.get('grad_norm', 0.0)):.2f} "
                            f"{tok / secs:.0f} tok/s"
                            + (" [straggler]" if slow else "")
                        )
                        slow = False
            if last_saved != lcfg.total_steps:
                save(lcfg.total_steps)
            flush_history(lcfg.total_steps)
            return LoopResult(
                status, state, lcfg.total_steps, history,
                to_host(metrics) if metrics else {},
                self.monitor.stragglers,
            )
        finally:
            if writer is not None:
                writer.close()  # drains pending writes (and re-raises)
            if heartbeat is not None:
                heartbeat.stop()
            close_data()
            if self._handler is None:
                handler.restore()
