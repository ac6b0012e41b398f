"""Deterministic, resumable, host-sharded LM data pipeline.

A token corpus (np.memmap on disk or in-memory array) is read as
next-token-prediction windows.  Each host reads only its shard of the
global batch (host h gets rows ``[h·B/H, (h+1)·B/H)``); the loader's state
is a single integer cursor saved inside the checkpoint → bit-exact resume
after preemption/restart, including on a *different* host count (elastic:
the cursor is in units of global steps, not host rows).

A background prefetch thread keeps ``prefetch`` batches ready so host input
never blocks the device step (compute/IO overlap).
"""
from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, Optional

import jax
import numpy as np


class TokenStream:
    def __init__(
        self,
        tokens: np.ndarray,  # (N,) int32 corpus (or np.memmap)
        *,
        global_batch: int,
        seq_len: int,
        host_id: int = 0,
        n_hosts: int = 1,
        cursor: int = 0,
        seed: int = 0,
        shuffle_windows: bool = True,
    ):
        assert global_batch % n_hosts == 0
        self.tokens = tokens
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.host_id = host_id
        self.n_hosts = n_hosts
        self.cursor = cursor
        self.seed = seed
        self.shuffle = shuffle_windows
        self.n_windows = (len(tokens) - 1) // seq_len
        if self.n_windows < global_batch:
            raise ValueError("corpus too small for one global batch")

    # ------------------------------------------------------------- state
    def state(self) -> Dict[str, int]:
        return {"cursor": int(self.cursor), "seed": int(self.seed)}

    def restore(self, state: Dict[str, int]) -> None:
        self.cursor = int(state["cursor"])
        self.seed = int(state["seed"])

    # -------------------------------------------------------------- next
    def _window_ids(self, step: int) -> np.ndarray:
        """Global window permutation for this epoch, deterministic in
        (seed, epoch)."""
        per_step = self.global_batch
        steps_per_epoch = self.n_windows // per_step
        epoch = step // steps_per_epoch
        within = step % steps_per_epoch
        rng = np.random.default_rng(self.seed * 1000003 + epoch)
        perm = (
            rng.permutation(self.n_windows)
            if self.shuffle
            else np.arange(self.n_windows)
        )
        sel = perm[within * per_step : (within + 1) * per_step]
        lo = self.host_id * (per_step // self.n_hosts)
        hi = lo + per_step // self.n_hosts
        return sel[lo:hi]

    def next_batch(self) -> Dict[str, np.ndarray]:
        ids = self._window_ids(self.cursor)
        L = self.seq_len
        tok = np.stack([self.tokens[i * L : i * L + L] for i in ids]).astype(np.int32)
        lab = np.stack(
            [self.tokens[i * L + 1 : i * L + L + 1] for i in ids]
        ).astype(np.int32)
        self.cursor += 1
        return {"tokens": tok, "labels": lab}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next_batch()


class Prefetcher:
    """Background-thread prefetch of `depth` batches."""

    def __init__(self, stream: TokenStream, depth: int = 2):
        self.stream = stream
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            batch = self.stream.next_batch()
            item = (batch, self.stream.state())
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.5)
                    break
                except queue.Full:
                    continue

    def next(self):
        # on the profiler's clock, beside the device ops: a gap in the
        # device's work that falls inside this span waited on the loader
        with jax.profiler.TraceAnnotation("repro.data.wait"):
            batch, state = self._q.get()
        # state as of the *consumed* batch — checkpoint this (not the
        # stream's own cursor, which has run ahead by the prefetch depth)
        self.consumed_state = state
        return batch

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
