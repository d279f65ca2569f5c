"""Host input pipeline: sharded device placement with double-buffered
prefetch.

The reference delegates data loading to the frameworks and ships only a
synthetic generator for tests (reference: tests/utils.py fake_data,
example/pytorch/benchmark_byteps.py synthetic inputs). Here the input
path is part of the framework because on TPU it is a real bottleneck
class: the host must overlap (a) producing the next batch and (b) the
host→device transfer with the current step's compute.

``prefetch_to_mesh`` is the workhorse: a background thread device_puts
batches with the data-axis sharding while the caller trains on the
previous one — the JAX-native equivalent of a framework DataLoader's
pinned-memory prefetch queue.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .parallel.mesh import data_axes


def data_sharding(mesh: Mesh, spec: Optional[P] = None) -> NamedSharding:
    """The batch placement: split over the mesh's data axes by default."""
    if spec is None:
        axes = data_axes(mesh)
        spec = P(axes) if axes else P()
    return NamedSharding(mesh, spec)


def shard_batch(batch, mesh: Mesh, spec: Optional[P] = None,
                sharding: Optional[NamedSharding] = None):
    """Place one host batch onto the mesh, split over the data axes.

    Hot loops should build the sharding once with ``data_sharding`` and
    pass it, avoiding per-batch construction.
    """
    if sharding is None:
        sharding = data_sharding(mesh, spec)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding),
                                  batch)


def shard_local_batch(batch, mesh: Mesh, spec: Optional[P] = None,
                      sharding: Optional[NamedSharding] = None):
    """Assemble a GLOBAL array from this process's LOCAL batch shard.

    Multi-host input pipelines: each process loads only its slice of the
    global batch (global = local × process_count along the batch dim)
    and JAX stitches the distributed array — no host ships data it
    doesn't own. Single-process: identical to ``shard_batch``."""
    if sharding is None:
        sharding = data_sharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), batch)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(sharding, x),
        batch)


def _host_bytes(batch) -> int:
    """Bytes of a batch's leaves, as the host holds them."""
    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(batch))


def prefetch_to_mesh(it: Iterable, mesh: Mesh, spec: Optional[P] = None,
                     buffer_size: int = 2, local: bool = False) -> Iterator:
    """Iterate ``it``, yielding mesh-sharded batches, transferring up to
    ``buffer_size`` batches ahead on a background thread.

    device_put is async, but issuing it from a separate thread also
    overlaps the host-side work (pytree traversal, layout, page pinning)
    with the training loop's Python time.

    ``local=True``: each process's iterator yields only ITS slice of
    the global batch (``shard_local_batch`` assembly) — the multi-host
    input contract; identical to the default in a single process.
    """
    q: "queue.Queue" = queue.Queue(maxsize=buffer_size)
    stop = threading.Event()
    _END = object()
    sharding = data_sharding(mesh, spec)
    place = shard_local_batch if local else shard_batch

    # bps.feed.* annotations are host spans in the profiler's own trace
    # and a flag test when no profiler session runs (docs/timeline.md)
    annotate = jax.profiler.TraceAnnotation

    def producer():
        source = iter(it)
        try:
            while True:
                with annotate("bps.feed.source"):
                    batch = next(source, _END)
                if batch is _END:
                    break
                if stop.is_set():
                    return
                with annotate("bps.feed.h2d", bytes=_host_bytes(batch)
                              if annotate.is_enabled() else 0):
                    placed = place(batch, mesh, sharding=sharding)
                q.put(placed)
            q.put(_END)
        except BaseException as e:          # propagate into the consumer
            q.put(e)

    t = threading.Thread(target=producer, daemon=True,
                         name="bps-prefetch")
    t.start()
    try:
        while True:
            with annotate("bps.feed.wait"):
                item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        # drain so the producer's blocked put() can observe stop
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass


# ------------------------------------------------------ synthetic sources

def synthetic_batches(make_batch: Callable[[np.random.RandomState], object],
                      seed: int = 0, steps: Optional[int] = None) -> Iterator:
    """Endless (or ``steps``-long) stream from a batch factory — the
    fake_data equivalent for benchmarks/tests."""
    rng = np.random.RandomState(seed)
    i = 0
    while steps is None or i < steps:
        yield make_batch(rng)
        i += 1


def mlm_stream(batch: int, seq: int, vocab: int, seed: int = 0,
               steps: Optional[int] = None) -> Iterator:
    """Synthetic MLM batches (tokens, targets) for BERT-style pretraining."""
    from .models.bert import synth_mlm_batch
    return synthetic_batches(
        lambda rng: synth_mlm_batch(rng, batch, seq, vocab),
        seed=seed, steps=steps)


def imagenet_stream(batch: int, seed: int = 0,
                    steps: Optional[int] = None) -> Iterator:
    """Synthetic 224×224 image batches (images, labels) for ResNet/VGG."""
    from .models.resnet import synth_imagenet_batch
    return synthetic_batches(
        lambda rng: synth_imagenet_batch(rng, batch),
        seed=seed, steps=steps)


# ---------------------------------------------------- file-backed sources

def write_npz_shards(path, arrays_fn: Callable[[int], dict],
                     n_shards: int) -> list:
    """Write ``n_shards`` dataset shard files (``shard-00042.npz``) to
    ``path``; ``arrays_fn(i)`` returns shard i's named arrays. Returns
    the file list. The reference's recipes read RecordIO/ImageRecord
    shard files (example/mxnet/train_gluon_imagenet_byteps_gc.py) —
    npz is the dependency-free stand-in with the same access pattern:
    many sequential-read shard files, sample-addressable after load."""
    import os
    os.makedirs(path, exist_ok=True)
    files = []
    for i in range(n_shards):
        f = os.path.join(path, f"shard-{i:05d}.npz")
        np.savez(f, **arrays_fn(i))
        files.append(f)
    return files


def _npz_sample_count(path) -> int:
    """Leading-axis length of the arrays in an .npz, read from the npy
    headers only — no array data is decompressed.

    EVERY member's header is checked and their leading axes must agree:
    zip member order is whatever the writer produced (externally built
    shards reorder freely), so "first member in zip order" was not a
    stable notion of the shard's sample count — two workers reading
    differently-ordered but equal shards could disagree, and a shard
    whose arrays disagree internally (truncated write) must fail here,
    loudly, not desynchronize a collective mid-epoch."""
    import zipfile
    with zipfile.ZipFile(path) as zf:
        names = sorted(n for n in zf.namelist() if n.endswith(".npy"))
        if not names:
            raise ValueError(f"{path} holds no arrays — not a dataset shard")
        counts = {}
        for name in names:
            with zf.open(name) as f:
                version = np.lib.format.read_magic(f)
                reader = (np.lib.format.read_array_header_1_0
                          if version[0] == 1
                          else np.lib.format.read_array_header_2_0)
                shape, _, _ = reader(f)
            counts[name[:-4]] = shape[0] if shape else 0
    if len(set(counts.values())) > 1:
        raise ValueError(
            f"{path}: arrays disagree on the leading (sample) axis: "
            f"{counts} — not a consistent dataset shard")
    return next(iter(counts.values()))


class NpzShardDataset:
    """File-backed training dataset over a directory of .npz shards.

    The distributed contract (reference: every per-framework recipe
    shards its record files by rank —
    train_gluon_imagenet_byteps_gc.py's split DataLoader): worker
    ``rank`` of ``world`` reads only shard files ``rank::world``
    (disjoint and complete), shuffles WITHIN its shards per epoch with
    a seed derived from (seed, epoch) — the same permutation on every
    restart, different every epoch — and yields ``batch``-sized dicts
    of arrays. Ragged tails are dropped (distributed steps need
    identical batch shapes on every worker).

    Every rank must take the SAME number of steps per epoch or the
    stragglers' collectives hang the job, so the shard count must
    divide evenly by ``world`` AND every shard must hold the same
    number of samples. Both are enforced at construction when
    ``world > 1`` — sample counts are read from the npz headers
    (cheap; no array data is loaded) so externally produced unequal
    shards fail loudly here instead of hanging a collective
    mid-epoch. Single-process runs skip the size check: with one
    rank there is no collective to hang and a short tail shard is
    harmless.

    Feed the iterator to ``prefetch_to_mesh`` for the device side."""

    def __init__(self, path, rank: int = 0, world: int = 1,
                 seed: int = 0) -> None:
        import glob
        import os
        self.files = sorted(glob.glob(os.path.join(path, "shard-*.npz")))
        if not self.files:
            raise FileNotFoundError(f"no shard-*.npz files under {path}")
        if len(self.files) % max(world, 1) != 0:
            raise ValueError(
                f"{len(self.files)} shard files don't divide over "
                f"{world} workers — unequal per-rank step counts would "
                f"hang the stragglers' collectives; re-shard the "
                f"dataset to a multiple of the worker count")
        counts = ([_npz_sample_count(f) for f in self.files]
                  if world > 1 else [])
        if len(set(counts)) > 1:
            detail = ", ".join(
                f"{os.path.basename(f)}={c}"
                for f, c in zip(self.files, counts))
            raise ValueError(
                f"shard sample counts differ ({detail}) — ranks would "
                f"take different per-epoch step counts and hang the "
                f"stragglers' collectives; re-shard to equal sizes")
        self.rank, self.world, self.seed = rank, world, seed
        self.my_files = self.files[rank::world]

    def epoch(self, epoch: int, batch: int) -> Iterator:
        """One epoch of ``batch``-sized dicts from this rank's shards."""
        rng = np.random.RandomState((self.seed * 1000003 + epoch)
                                    & 0x7FFFFFFF)
        order = rng.permutation(len(self.my_files))
        yielded = 0
        for fi in order:
            with np.load(self.my_files[fi]) as z:
                arrays = {k: z[k] for k in z.files}
            n = len(next(iter(arrays.values())))
            perm = rng.permutation(n)
            for s in range(0, n - batch + 1, batch):
                idx = perm[s:s + batch]
                yield {k: v[idx] for k, v in arrays.items()}
                yielded += 1
        if yielded == 0:
            # without this a too-large batch silently trains for zero
            # steps and reports untrained "results"
            raise ValueError(
                f"batch={batch} exceeds every shard's sample count — "
                f"no batches produced (batches never span shard files)")

    def batches(self, batch: int, epochs: Optional[int] = None) -> Iterator:
        """Epoch-concatenated stream (``epochs=None`` → endless)."""
        e = 0
        while epochs is None or e < epochs:
            yield from self.epoch(e, batch)
            e += 1
