"""Input pipelines: DataLoader, Dataset, BatchSampler, reader decorators.

Capability parity: reference `python/paddle/fluid/reader.py` (DataLoader:101,
from_generator:361 double-buffered feed), `python/paddle/fluid/dataloader/`
(Dataset, BatchSampler, worker prefetch) and `python/paddle/reader/decorator.py`
(batch/shuffle/buffered composition).

TPU-first: the C++ BufferedReader/LoDTensorBlockingQueue
(`operators/reader/buffered_reader.cc`) becomes a host-side background-thread
prefetcher whose slots are `jax.device_put`-ahead batches — the XLA dispatch
queue overlaps H2D copies with compute, so one thread + a small queue gives
the same double-buffering.
"""

import itertools
import queue
import threading

import numpy as np


# ---------------------------------------------------------------------------
# reader decorators (cf. paddle.batch / paddle.reader.shuffle)
# ---------------------------------------------------------------------------

def batch(reader, batch_size, drop_last=False):
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched


def shuffle(reader, buf_size, seed=None):
    rs = np.random.RandomState(seed)

    def shuffled():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) >= buf_size:
                rs.shuffle(buf)
                yield from buf
                buf = []
        rs.shuffle(buf)
        yield from buf

    return shuffled


def cache(reader):
    items = []

    def cached():
        if not items:
            for it in reader():
                items.append(it)
                yield it
        else:
            yield from items

    return cached


def firstn(reader, n):
    def limited():
        yield from itertools.islice(reader(), n)

    return limited


# ---------------------------------------------------------------------------
# Dataset / BatchSampler (cf. python/paddle/fluid/dataloader/)
# ---------------------------------------------------------------------------

class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError


class TensorDataset(Dataset):
    def __init__(self, *arrays):
        self.arrays = [np.asarray(a) for a in arrays]
        assert all(len(a) == len(self.arrays[0]) for a in self.arrays)

    def __getitem__(self, idx):
        return tuple(a[idx] for a in self.arrays)

    def __len__(self):
        return len(self.arrays[0])


class BatchSampler:
    def __init__(self, dataset=None, shuffle=False, batch_size=1, drop_last=False,
                 seed=None):
        self.n = len(dataset)
        self.shuffle = shuffle
        self.batch_size = batch_size
        self.drop_last = drop_last
        self._rs = np.random.RandomState(seed)

    def __iter__(self):
        idx = np.arange(self.n)
        if self.shuffle:
            self._rs.shuffle(idx)
        for i in range(0, self.n, self.batch_size):
            b = idx[i : i + self.batch_size]
            if len(b) < self.batch_size and self.drop_last:
                return
            yield list(b)

    def __len__(self):
        if self.drop_last:
            return self.n // self.batch_size
        return (self.n + self.batch_size - 1) // self.batch_size


def _mp_worker_main(dataset, collate, task_q, res_q):
    """DataLoader worker entry (module-level: spawn pickles it).

    Persistent across epochs: tasks carry an epoch tag that is echoed
    back so the parent can discard results of an abandoned epoch."""
    while True:
        item = task_q.get()
        if item is None:
            return
        epoch, i, idx = item
        try:
            res_q.put((epoch, i, collate([dataset[j] for j in idx]), None))
        except Exception as e:  # surface in the parent
            res_q.put((epoch, i, None, "%s: %s" % (type(e).__name__, e)))


def default_collate(items):
    """Batch a list of samples: tuple/list samples -> tuple of stacked
    arrays; dict samples -> dict of stacked arrays (keys must agree
    across the batch).  Anything else raises — a clear error beats a
    silent mis-zip."""
    first = items[0]
    if isinstance(first, dict):
        keys = set(first)
        for i, it in enumerate(items):
            if not isinstance(it, dict) or set(it) != keys:
                raise TypeError(
                    "default_collate: dict samples must share one key set; "
                    "sample 0 has %s, sample %d has %s"
                    % (sorted(keys), i,
                       sorted(it) if isinstance(it, dict) else type(it)))
        return {
            k: np.stack([np.asarray(it[k]) for it in items]) for k in first
        }
    if isinstance(first, (tuple, list)):
        transposed = list(zip(*items))
        return tuple(
            np.stack([np.asarray(x) for x in col]) for col in transposed)
    raise TypeError(
        "default_collate supports tuple/list or dict samples, got %s; "
        "pass collate_fn= for anything else" % type(first).__name__)


class DataLoader:
    """Iterable over batches with background-thread prefetch.

    Two construction modes, mirroring the reference:
      * DataLoader(dataset, batch_size=..., shuffle=...) — map-style dataset.
      * DataLoader.from_generator(capacity=..., feed_list=...) then
        .set_sample_list_generator / .set_batch_generator — generator-fed.
    """

    def __init__(self, dataset=None, feed_list=None, batch_size=1, shuffle=False,
                 drop_last=False, collate_fn=None, num_workers=0, capacity=4,
                 batch_sampler=None, return_list=True):
        self.dataset = dataset
        self.feed_list = feed_list
        self.capacity = max(2, capacity)
        self.collate_fn = collate_fn or default_collate
        self.num_workers = max(0, int(num_workers))
        self._gen = None
        self._pool = None        # persistent mp worker pool (lazily started)
        self._mp_epoch = 0
        if dataset is not None:
            self.batch_sampler = batch_sampler or BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last
            )

    # -- generator-fed mode (cf. reader.py:361) -----------------------------
    @staticmethod
    def from_generator(feed_list=None, capacity=4, use_double_buffer=True,
                       iterable=True, return_list=False):
        return DataLoader(feed_list=feed_list, capacity=capacity)

    def set_sample_generator(self, generator, batch_size, drop_last=False,
                             places=None):
        """Feed from a per-sample generator, batching by `batch_size`.

        `drop_last` defaults to False, ALIGNED with the constructor's
        default (the reference defaulted this one method to True, so the
        same DataLoader dropped the tail batch or not depending on which
        entry point fed it — a silent data-loss footgun; pass
        drop_last=True explicitly for fixed-shape feeding)."""
        from .reader import batch as _batch  # self-module import for clarity

        self._gen = lambda: (
            self.collate_fn(samples)
            for samples in _batch(generator, batch_size, drop_last)()
        )
        return self

    def set_sample_list_generator(self, generator, places=None):
        self._gen = lambda: (self.collate_fn(samples) for samples in generator())
        return self

    def set_batch_generator(self, generator, places=None):
        self._gen = generator
        return self

    # -- iteration with prefetch -------------------------------------------
    def _batches(self):
        if self._gen is not None:
            yield from self._gen()
            return
        if self.num_workers > 0:
            yield from self._mp_batches()
            return
        for indices in self.batch_sampler:
            yield self.collate_fn([self.dataset[i] for i in indices])

    def _mp_batches(self):
        """Multiprocess map-style loading (reference dataloader_iter.py
        _DataLoaderIterMultiProcess capability): N spawned workers pull
        index lists from a task queue and push collated numpy batches
        back; the parent reassembles them IN ORDER.

        Spawn (not fork): the parent runs a multithreaded JAX runtime and
        forking it is the textbook deadlock; spawn requires the dataset /
        collate_fn to be picklable, same contract as the reference's
        multiprocess workers.  Tasks are issued through a bounded window
        so a straggler batch cannot let the others run arbitrarily far
        ahead (the in-order buffer stays <= window batches), and the
        result wait polls worker liveness so a killed worker raises
        instead of hanging the trainer.

        The worker pool PERSISTS across epochs (spawn + dataset pickling
        cost is paid once per DataLoader, not once per epoch); epochs are
        distinguished by a generation tag so results of an abandoned
        epoch are discarded, and close() tears the pool down.  Two
        consequences, both matching the reference's persistent workers:
        only ONE live iterator per DataLoader — starting a new iteration
        invalidates the previous one (it raises on next use) — and the
        dataset is pickled once at pool start, so mutating it between
        epochs has no effect on workers (call close() to force a
        respawn)."""
        import queue as _queue

        batches = list(self.batch_sampler)
        if not batches:
            return
        procs, task_q, res_q = self._ensure_pool()
        self._mp_epoch += 1
        epoch = self._mp_epoch
        window = max(2 * len(procs), self.capacity)
        issued = 0

        def issue_up_to(limit):
            nonlocal issued
            if self._mp_epoch != epoch:
                return                       # superseded: stop issuing work
            while issued < min(limit, len(batches)):
                task_q.put((epoch, issued, batches[issued]))
                issued += 1

        def check_live():
            if self._mp_epoch != epoch:
                raise RuntimeError(
                    "this DataLoader iterator was invalidated by a newer "
                    "iteration (one live iterator per DataLoader when "
                    "num_workers > 0)")

        issue_up_to(window)
        pending = {}
        next_i = 0
        received = 0
        stalled_polls = 0
        while received < len(batches):
            check_live()
            try:
                ep, i, b, e = res_q.get(timeout=5.0)
                stalled_polls = 0
                if ep != epoch:
                    if ep == self._mp_epoch:
                        # belongs to the iterator that invalidated us —
                        # hand it back before we raise at the loop top
                        res_q.put((ep, i, b, e))
                    continue         # stale result of an abandoned epoch
            except _queue.Empty:
                dead = sum(1 for p in procs if not p.is_alive())
                if dead == len(procs):
                    self.close()
                    raise RuntimeError(
                        "all DataLoader workers died without "
                        "delivering results (OOM-killed?)")
                if dead:
                    # a dead worker took its in-flight task with it;
                    # no result can ever unblock next_i — fail fast
                    # instead of hanging the trainer
                    stalled_polls += 1
                    if stalled_polls >= 2:
                        self.close()
                        raise RuntimeError(
                            "%d DataLoader worker(s) died and the "
                            "stream stalled (batch %d never arrived)"
                            % (dead, next_i))
                continue
            received += 1
            if e is not None:
                raise RuntimeError(
                    "DataLoader worker failed on batch %d: %s" % (i, e))
            pending[i] = b
            while next_i in pending:
                yield pending.pop(next_i)
                next_i += 1
                # a newer iterator may have invalidated us while we were
                # suspended at the yield — stop issuing and raise NOW, not
                # several buffered batches later
                check_live()
                issue_up_to(next_i + window)

    def _ensure_pool(self):
        """Start (once) and return the persistent worker pool."""
        if self._pool is not None:
            procs = self._pool[0]
            if all(p.is_alive() for p in procs):
                return self._pool
            self.close()                     # respawn a broken pool
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        task_q = ctx.Queue()
        res_q = ctx.Queue()
        procs = [
            ctx.Process(
                target=_mp_worker_main,
                args=(self.dataset, self.collate_fn, task_q, res_q),
                daemon=True,
            )
            for _ in range(self.num_workers)
        ]
        from .core.place import cpu_only_children

        with cpu_only_children():    # workers collate on the host only
            for p in procs:
                p.start()
        self._pool = (procs, task_q, res_q)
        return self._pool

    def close(self):
        """Tear down the persistent worker pool (idempotent)."""
        if self._pool is None:
            return
        procs, task_q, _ = self._pool
        self._pool = None
        for p in procs:
            if p.is_alive():
                task_q.put(None)
        for p in procs:
            p.join(timeout=1)
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(timeout=5)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _sampler_state(self):
        """The sampler's cursor, or None when it has none (a plain
        BatchSampler) or it is not meaningfully positional here."""
        sampler = getattr(self, "batch_sampler", None)
        if sampler is None or not hasattr(sampler, "state_dict"):
            return None
        try:
            return sampler.state_dict()
        except TypeError:
            return None

    def __iter__(self):
        q = queue.Queue(maxsize=self.capacity)
        sentinel = object()
        err = []
        # the background thread pulls the sampler up to capacity+1
        # batches ahead of the consumer: pair each batch with the
        # sampler cursor AS OF ITS PULL so state_dict() can report the
        # position of the batch the trainer actually received
        track = self.num_workers == 0 and self._gen is None

        def worker():
            try:
                for b in self._batches():
                    q.put((b, self._sampler_state() if track else None))
            except BaseException as e:  # propagate to consumer
                err.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                if err:
                    raise err[0]
                return
            item, state = item
            if state is not None:
                self._last_sampler_state = state
            if self.feed_list is not None:
                yield {
                    v.name if hasattr(v, "name") else v: arr
                    for v, arr in zip(self.feed_list, item)
                }
            else:
                yield item

    def __len__(self):
        if self._gen is not None:
            raise TypeError("generator-fed DataLoader has no length")
        return len(self.batch_sampler)

    # -- checkpointable iteration (paddle_tpu.io contract) ------------------
    def state_dict(self):
        """Sampler state aligned to YIELDED batches (the internal
        prefetch thread runs ahead; see __iter__) — exact for
        num_workers=0 map-style iteration with an io.ShardedBatchSampler.
        With num_workers>0 the batch list is drained upfront, so
        positional resume needs io.ResumableDataLoader instead."""
        state = getattr(self, "_last_sampler_state", None)
        if state is not None:
            return {"sampler": state}
        state = self._sampler_state()
        if state is None:
            raise TypeError(
                "this DataLoader's sampler has no state_dict(); use "
                "io.ResumableDataLoader (or io.ShardedBatchSampler) for "
                "checkpointable iteration")
        return {"sampler": state}

    def load_state_dict(self, state):
        sampler = getattr(self, "batch_sampler", None)
        if sampler is None or not hasattr(sampler, "load_state_dict"):
            raise TypeError(
                "this DataLoader's sampler has no load_state_dict(); use "
                "io.ResumableDataLoader (or io.ShardedBatchSampler) for "
                "checkpointable iteration")
        sampler.load_state_dict(state["sampler"])
        self._last_sampler_state = state["sampler"]

    def set_epoch(self, epoch):
        sampler = getattr(self, "batch_sampler", None)
        if sampler is not None and hasattr(sampler, "set_epoch"):
            sampler.set_epoch(epoch)


class DistributedBatchSampler(BatchSampler):
    """cf. reference `paddle.io.DistributedBatchSampler`: each rank
    iterates its own 1/nranks slice of the (optionally shuffled) index
    space, padded so every rank sees the same number of batches."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False, seed=None):
        super().__init__(dataset=dataset, shuffle=shuffle,
                         batch_size=batch_size, drop_last=drop_last,
                         seed=seed)
        if num_replicas is None or rank is None:
            import os

            num_replicas = int(os.getenv("PADDLE_TRAINERS_NUM", "1"))
            rank = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        self.nranks = max(int(num_replicas), 1)
        self.rank = int(rank)
        self.epoch = 0
        self._seed_base = int(seed or 0)

    def set_epoch(self, epoch):
        """Reshuffle deterministically per epoch (reference contract)."""
        self.epoch = int(epoch)

    def state_dict(self):
        """Epoch-granular state (the permutation is a pure function of
        (seed, epoch)); `io.ShardedBatchSampler` extends this with the
        exact batch offset for mid-epoch resume."""
        return {"epoch": self.epoch, "seed": self._seed_base,
                "nranks": self.nranks, "rank": self.rank}

    def load_state_dict(self, state):
        self.epoch = int(state["epoch"])

    def _shard_batches(self, idx):
        """Permuted global indices -> this rank's batch list: pad
        (tiling if needed) to a multiple of nranks so every rank yields
        equally many batches even when pad > dataset size, take the
        rank-strided slice, split into batches.  Single-sourced: the
        resumable io.ShardedBatchSampler's offsets index into exactly
        this list.  Sized off `idx` (not self.n): an elastic resume
        hands in the epoch's unconsumed SUFFIX and only it may be
        sharded — tiling it back up to the dataset size would replay
        consumed samples."""
        per = (len(idx) + self.nranks - 1) // self.nranks
        padded = np.resize(idx, per * self.nranks)
        local = padded[self.rank::self.nranks]
        out = []
        for i in range(0, len(local), self.batch_size):
            b = local[i:i + self.batch_size]
            if len(b) < self.batch_size and self.drop_last:
                break
            out.append([int(j) for j in b])
        return out

    def __iter__(self):
        idx = np.arange(self.n)
        if self.shuffle:
            np.random.RandomState(
                (self._seed_base or 0) + self.epoch).shuffle(idx)
        yield from self._shard_batches(idx)

    def __len__(self):
        per = (self.n + self.nranks - 1) // self.nranks
        if self.drop_last:
            return per // self.batch_size
        return (per + self.batch_size - 1) // self.batch_size
