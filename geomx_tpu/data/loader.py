"""Host-side batched loader for the HiPS topology.

Each (party, worker) cell of the mesh trains on its own shard, produced by
SplitSampler / ClassSplitSampler exactly as each reference worker process
loads its slice (examples/utils.py:39-117, cnn.py:100-108).  A global step
consumes one batch per worker, stacked to

    [num_parties, workers_per_party, local_batch, H, W, C]

and placed with the mesh's (dc, worker) sharding so each device receives
only its own slice.

Two overlap mechanisms (the role of the reference's prefetching iterators,
src/io/iter_prefetcher.h, re-expressed for TPU):

- ``prefetch`` (default): batch assembly + device_put run on a producer
  thread ahead of the consumer.
- ``device_cache=True``: the whole dataset lives in HBM (replicated over
  the mesh) and each step gathers its batch **on device** from a few KB of
  selection indices — including the CIFAR crop/flip augmentation as a
  jitted kernel.  This removes the per-step host->device image transfer
  entirely, which dominates when the interconnect to the chip is slow and
  is still the fastest path whenever the dataset fits HBM (CIFAR10 at
  uint8 is ~180 MB).
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator, Optional, Tuple

import jax
import numpy as np

from geomx_tpu.data.samplers import (ClassSplitSampler, SplitSampler,
                                     class_sorted_indices)
from geomx_tpu.topology import HiPSTopology
from geomx_tpu.utils.profiler import profile_scope


def gather_batch(dx, dy, sel, key, augment: bool, pad: int):
    """On-device batch assembly: gather by index, then the CIFAR
    crop/flip recipe as XLA ops (static shapes, vmapped dynamic_slice).
    Module-level (not a loader method) so jitted closures over it never
    pin a loader — and its HBM-cached dataset — in memory."""
    import jax.numpy as jnp
    from jax import lax, random

    xb = dx[sel]                      # [P, W, b, H, Wd, C]
    yb = dy[sel]
    if augment:
        p = pad
        lead = xb.shape[:-3]
        h, w, c = xb.shape[-3:]
        flat = xb.reshape((-1, h, w, c))
        n = flat.shape[0]
        k1, k2, k3 = random.split(key, 3)
        oy = random.randint(k1, (n,), 0, 2 * p + 1)
        ox = random.randint(k2, (n,), 0, 2 * p + 1)
        padded = jnp.pad(flat, ((0, 0), (p, p), (p, p), (0, 0)),
                         mode="reflect")
        crops = jax.vmap(
            lambda img, a, b: lax.dynamic_slice(img, (a, b, 0),
                                                (h, w, c)))(padded, oy, ox)
        flip = random.bernoulli(k3, 0.5, (n,))
        crops = jnp.where(flip[:, None, None, None],
                          crops[:, :, ::-1, :], crops)
        xb = crops.reshape(lead + (h, w, c))
    return xb, yb


class GeoDataLoader:
    def __init__(self, x: np.ndarray, y: np.ndarray, topology: HiPSTopology,
                 batch_size: int, split_by_class: bool = False,
                 shuffle: bool = True, seed: int = 0, drop_last: bool = True,
                 sharding: Optional[jax.sharding.Sharding] = None,
                 augment: bool = False, pad: int = 4,
                 device_cache: bool = False):
        """``batch_size`` is per-worker, matching the reference's -bs flag
        (each worker process trains batch_size samples per step).

        ``augment=True`` applies the standard CIFAR recipe on host —
        random crop from a ``pad``-pixel reflection border + horizontal
        flip (the reference's gluon transforms path,
        python/mxnet/gluon/data/vision/transforms.py RandomResizedCrop /
        RandomFlipLeftRight as used by its CIFAR training recipes).

        ``sharding`` may be a single sharding for both tensors, or an
        (x_sharding, y_sharding) pair — sequence-parallel token batches
        shard x's sequence dim over the sp axis while labels stay on the
        replica grid."""
        self.topology = topology
        self.batch_size = int(batch_size)
        if isinstance(sharding, (tuple, list)):
            self.x_sharding, self.y_sharding = sharding
        else:
            self.x_sharding = self.y_sharding = sharding
        self.shuffle = shuffle
        self.seed = seed
        self.augment = augment
        self.pad = int(pad)
        n_workers = topology.total_workers
        length = len(x)
        if split_by_class:
            order = class_sorted_indices(y)
            shards = [ClassSplitSampler(order, length, n_workers, i).indices()
                      for i in range(n_workers)]
        else:
            shards = [SplitSampler(length, n_workers, i).indices()
                      for i in range(n_workers)]
        self.x, self.y = x, y
        self.shards = shards
        self.steps_per_epoch = min(len(s) for s in shards) // self.batch_size
        if self.steps_per_epoch < 1:
            raise ValueError(
                f"shard of {min(len(s) for s in shards)} samples cannot fill "
                f"a batch of {self.batch_size}")
        self.device_cache = device_cache
        if device_cache:
            rep = None
            if isinstance(self.x_sharding, jax.sharding.NamedSharding):
                rep = jax.sharding.NamedSharding(
                    self.x_sharding.mesh, jax.sharding.PartitionSpec())
            self._dev_x = jax.device_put(x, rep)
            self._dev_y = jax.device_put(y, rep)
            self._gather = jax.jit(
                gather_batch, static_argnames=("augment", "pad"),
                out_shardings=None if self.x_sharding is None
                else (self.x_sharding, self.y_sharding))

    def epoch(self, epoch: int = 0,
              prefetch: int = 2) -> Iterator[Tuple[jax.Array, jax.Array]]:
        """Yield (x, y) global batches for one epoch.

        ``prefetch`` > 0 runs batch assembly (indexing, augmentation,
        device_put) on a producer thread with a bounded queue, so host-side
        input work overlaps device compute — the role the reference's
        prefetching data iterators play (src/io/iter_prefetcher.h).  Set 0
        to assemble synchronously in the caller's thread."""
        if prefetch <= 0:
            yield from self._batches(epoch)
            return
        q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        stop = threading.Event()

        def put_or_stop(item) -> bool:
            """Put unless the consumer abandoned the epoch; True if put."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.5)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            try:
                for batch in self._batches(epoch):
                    if not put_or_stop(batch):
                        return
                put_or_stop(None)
            except BaseException as e:  # surface to the consumer
                put_or_stop(e)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is None:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()

    def _epoch_order(self, epoch: int) -> list:
        rng = np.random.RandomState(self.seed + epoch)
        order = []
        for s in self.shards:
            idx = s.copy()
            if self.shuffle:
                rng.shuffle(idx)
            order.append(idx)
        return order

    def epoch_indices(self, epoch: int):
        """The whole epoch's selection indices at once:
        ([steps, P, W, b] int32, epoch PRNG key) — the input of the
        scanned-epoch training path (Trainer.fit(scan_epochs=True)), which
        runs every step of an epoch in ONE device dispatch."""
        topo = self.topology
        order = self._epoch_order(epoch)
        b = self.batch_size
        sel = np.stack([
            np.stack([idx[step * b:(step + 1) * b] for idx in order]).reshape(
                (topo.num_parties, topo.workers_per_party, b))
            for step in range(self.steps_per_epoch)]).astype(np.int32)
        return sel, jax.random.PRNGKey(self.seed + epoch)

    def _batches(self, epoch: int) -> Iterator[Tuple[jax.Array, jax.Array]]:
        # the loader/* spans run on whichever thread assembles: the
        # producer thread under prefetch, where they show beside the
        # loop's fit/next_batch wait in a profile
        topo = self.topology
        with profile_scope("loader/epoch_start", args={"epoch": epoch}):
            order = self._epoch_order(epoch)
            rng = np.random.RandomState(self.seed + epoch + 1)  # augment
        b = self.batch_size
        if self.device_cache:
            ekey = jax.random.PRNGKey(self.seed + epoch)
            for step in range(self.steps_per_epoch):
                sel = np.stack(
                    [idx[step * b:(step + 1) * b] for idx in order]).reshape(
                    (topo.num_parties, topo.workers_per_party, b))
                yield self._gather(self._dev_x, self._dev_y, sel,
                                   jax.random.fold_in(ekey, step),
                                   augment=self.augment, pad=self.pad)
            return
        for step in range(self.steps_per_epoch):
            with profile_scope("loader/assemble", args={"step": step}):
                sel = np.stack(
                    [idx[step * b:(step + 1) * b] for idx in order])
                xflat = self.x[sel.reshape(-1)]
                if self.augment:
                    xflat = self._augment_batch(xflat, rng)
                xb = xflat.reshape(
                    (topo.num_parties, topo.workers_per_party, b)
                    + self.x.shape[1:])
                # class labels [N] or per-token labels [N, L]
                yb = self.y[sel.reshape(-1)].reshape(
                    (topo.num_parties, topo.workers_per_party, b)
                    + self.y.shape[1:])
            if self.x_sharding is not None:
                with profile_scope("loader/device_put",
                                   args={"step": step}):
                    xb = jax.device_put(xb, self.x_sharding)
                    yb = jax.device_put(yb, self.y_sharding)
            yield xb, yb

    def _augment_batch(self, x: np.ndarray,
                       rng: np.random.RandomState) -> np.ndarray:
        """Vectorized random crop (reflection pad) + horizontal flip."""
        n, h, w = x.shape[:3]
        p = self.pad
        padded = np.pad(x, ((0, 0), (p, p), (p, p), (0, 0)), mode="reflect")
        dy = rng.randint(0, 2 * p + 1, size=n)
        dx = rng.randint(0, 2 * p + 1, size=n)
        # gather shifted windows with one fancy-index (no python loop)
        rows = dy[:, None] + np.arange(h)[None, :]          # [n, h]
        cols = dx[:, None] + np.arange(w)[None, :]          # [n, w]
        out = padded[np.arange(n)[:, None, None],
                     rows[:, :, None], cols[:, None, :]]
        flip = rng.rand(n) < 0.5
        out[flip] = out[flip, :, ::-1]
        return out
