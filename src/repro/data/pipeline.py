"""Sharded host feed with double-buffered prefetch.

The Ray-object-store translation (DESIGN.md §2): instead of a shared
plasma store, each host materializes only its shard of every batch and
``jax.device_put``s it under the batch NamedSharding; a background thread
keeps ``depth`` batches in flight so host generation overlaps device
compute.  Lineage is deterministic: batch s is a pure function of
(base_key, s), so checkpoint-restart at step s replays identically.
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Iterator, Optional

import jax
from jax.sharding import NamedSharding


class ShardedFeed:
    """Wraps a (step -> host batch) function into a prefetching iterator
    of device-resident, sharding-constrained batches."""

    def __init__(self, make_batch: Callable[[int], Dict[str, jax.Array]],
                 sharding: Optional[NamedSharding] = None,
                 start_step: int = 0, depth: int = 2):
        self._make_batch = make_batch
        self._sharding = sharding
        self._step = start_step
        self._depth = depth
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _place(self, batch):
        if self._sharding is None:
            return batch
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._sharding), batch)

    def _worker(self):
        step = self._step
        while not self._stop.is_set():
            try:
                b = self._place(self._make_batch(step))
            except Exception as e:  # surface generation errors to consumer
                self._q.put(e)
                return
            self._q.put((step, b))
            step += 1

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        item = self._q.get()
        if isinstance(item, Exception):
            raise item
        step, batch = item
        self._step = step + 1
        return batch

    @property
    def step(self) -> int:
        """Next step the consumer will receive (checkpoint this)."""
        return self._step

    def close(self):
        self._stop.set()
        # drain so the worker's blocked put() releases
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)

