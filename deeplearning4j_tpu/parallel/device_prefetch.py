"""Device-side batch prefetch (reference `parallelism/MagicQueue.java:21` —
the device-aware multi-queue that stages each mini-batch on its target GPU
before the worker needs it).

TPU equivalent: `DevicePrefetchIterator` wraps any DataSetIterator and
`jax.device_put`s upcoming batches (optionally with a mesh sharding) a few
steps ahead. `device_put` is asynchronous, so the host→HBM DMA of batch
N+k overlaps the compiled step for batch N; the training loop then passes
already-resident arrays to the jitted step instead of paying the transfer
on the critical path.

Opt-in, not the default: each `device_put` is a dispatch of its own, so
on a dispatch-bound model it can cost more than letting the jitted call
carry the batch; the overlap wins once the transfer is large. Use it when
profiling shows H2D on the critical path.
"""
from __future__ import annotations

from collections import deque
from typing import Iterator, Optional

import jax
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import DataSetIterator


class DevicePrefetchIterator(DataSetIterator):
    """Yields DataSets whose arrays are already device-resident.

    `sharding`: optional `jax.sharding.Sharding` for the batch axis (e.g.
    `NamedSharding(mesh, P("data"))`) — batches land pre-sharded across the
    mesh, so the sharded step consumes them without a relayout.
    `depth`: how many batches to keep in flight ahead of the consumer.
    """

    def __init__(self, underlying: DataSetIterator, depth: int = 2,
                 sharding=None):
        self._under = underlying
        self.depth = max(1, depth)
        self.sharding = sharding
        self._fifo: deque = deque()
        self._iter: Optional[Iterator[DataSet]] = None

    def _put(self, a):
        if a is None:
            return None
        arr = np.asarray(a)  # dtype preserved: the step casts if it wants to
        if self.sharding is not None:
            return jax.device_put(arr, self.sharding)
        return jax.device_put(arr)

    def _stage(self, ds: DataSet) -> DataSet:
        return DataSet(self._put(ds.features), self._put(ds.labels),
                       self._put(ds.features_mask), self._put(ds.labels_mask))

    def _refill(self):
        while len(self._fifo) < self.depth:
            try:
                ds = next(self._iter)
            except StopIteration:
                return
            self._fifo.append(self._stage(ds))

    def reset(self) -> None:
        self._iter = iter(self._under)
        self._fifo.clear()
        self._refill()

    def has_next(self) -> bool:
        if self._iter is None:
            self.reset()
        return bool(self._fifo)

    def next(self) -> DataSet:
        if not self.has_next():
            raise StopIteration
        ds = self._fifo.popleft()
        self._refill()
        return ds

    def batch(self) -> int:
        return self._under.batch()

    @property
    def async_supported(self) -> bool:
        # already ahead-of-time; wrapping in the host-thread prefetcher too
        # would just add queue handoffs
        return False
