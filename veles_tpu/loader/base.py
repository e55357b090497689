"""Loader base: the minibatch engine.

Parity: reference `veles/loader/base.py` — three sample classes
(TEST=0, VALIDATION=1, TRAIN=2, the reference's ordering), per-epoch global
shuffle of the train set (a function of the loader's seeded order seed and
the epoch number: `_train_order`), `minibatch_class` /
`last_minibatch` / `epoch_ended` / `epoch_number` bookkeeping consumed by
the Decision unit, and `IDistributable`-shaped index partitioning (on TPU
the data-parallel shard split — see `shard_batch`).

TPU-first deviation (documented): minibatches have a STATIC size — XLA
compiles one program per shape. When a class length is not divisible by
`minibatch_size`, the final minibatch wraps around to the start of the
class's index list instead of shrinking (the reference shrank the last
minibatch — a dynamic shape we must not feed jit). The wrapped rows are
marked invalid in `minibatch_valid` (a (minibatch_size,) 0/1 float pad
mask): evaluators weight metrics by it, so epoch metrics are EXACT at
any minibatch size while shapes stay static.

`balanced_train=True` enables the reference's class-balanced sampling
(SURVEY.md §2.7 Loader row): each epoch's train order is a seeded
weighted draw with per-class probabilities equalized (minority classes
oversampled with replacement), epoch length unchanged.
"""

from __future__ import annotations

import threading
import time
from typing import Any, List, Optional

import numpy as np

from veles_tpu import prng
from veles_tpu.accelerated_units import AcceleratedUnit
from veles_tpu.distributable import IDistributable
from veles_tpu.memory import Array
from veles_tpu.mutable import Bool
from veles_tpu.telemetry import metrics as _metrics
from veles_tpu.telemetry import tracer as _tracer

TEST, VALIDATION, TRAIN = 0, 1, 2


class Loader(AcceleratedUnit, IDistributable):
    """Subclasses implement `load_data()` (fill `class_lengths`) and
    `fill_minibatch(indices)` (fill minibatch_data/labels for the given
    global sample indices)."""

    #: sequence number of the batch the last run() delivered, counted
    #: over the loader's life (-1 before the first); spans carry it
    #: (class defaults: a loader restored from an older snapshot)
    batch_seq = -1
    #: batches delivered by the epochs already finished
    _seq_base = 0
    #: seed of the train orders (drawn once by initialize())
    _order_seed = 0

    def __init__(self, workflow=None, minibatch_size: int = 100,
                 shuffle_train: bool = True, on_device: bool = True,
                 balanced_train: bool = False,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.minibatch_size = minibatch_size
        self.shuffle_train = shuffle_train
        self.balanced_train = balanced_train
        #: when True, minibatches are pushed to the device once per fill
        self.on_device = on_device
        self.class_lengths: List[int] = [0, 0, 0]
        self.minibatch_data = Array()
        self.minibatch_labels = Array()
        self.minibatch_indices = Array()
        #: (minibatch_size,) 0/1 pad mask: 0 on wrap-around filler rows of
        #: a class's final minibatch (see module docstring)
        self.minibatch_valid = Array()
        self.minibatch_class = TRAIN
        self.last_minibatch = Bool(False)
        self.epoch_ended = Bool(False)
        #: shared gate object for GD units: True on non-train minibatches
        self.not_train = Bool(False)
        self.epoch_number = 0
        self._cursor = 0
        #: train orders of epochs still to come, by epoch number: drawn
        #: when a lookahead reaches past the schedule's end, adopted at
        #: the rollover; pickled, so a snapshot taken near an epoch's
        #: end resumes into the same next epoch
        self._orders: dict = {}
        self._indices_per_class: List[np.ndarray] = [
            np.empty(0, np.int64)] * 3

    # -- subclass contract ---------------------------------------------------

    def load_data(self) -> None:
        raise NotImplementedError

    def fill_minibatch(self, indices: np.ndarray) -> None:
        raise NotImplementedError

    def train_labels(self) -> Optional[np.ndarray]:
        """Integer labels of the train set in pristine (unshuffled) order,
        or None when unknown — required for `balanced_train`. Subclasses
        with labels (FullBatchLoader) implement this."""
        return None

    def wire_format(self) -> Optional[dict]:
        """The uint8-over-the-wire offer for the device feed
        (loader/device_feed.py): loaders that can emit raw uint8
        minibatches return {"emit": "uint8", "normalize": {"scale",
        "offset", "mean"}} describing the on-device affine that
        reproduces their host float path; the fused/pipeline step then
        normalizes on device and the H2D transfer shrinks 4x. None (the
        default) keeps the host float wire."""
        return None

    # -- lifecycle -----------------------------------------------------------

    def __setstate__(self, d):
        self.__dict__.update(d)
        self.__dict__.setdefault("_orders", {})     # an older snapshot
        #: unpickled from a snapshot: the next initialize() preserves the
        #: carried schedule/cursor/shuffle (explicit marker — a second
        #: initialize() of a LIVE loader must still re-derive them)
        self._restored = True

    def __getstate__(self):
        d = super().__getstate__()
        # device-feed counters (loader/device_feed.py) are process-local
        # observability with timing floats: dropped so identical model
        # state pickles to identical bytes (mirror digest dedup)
        d.pop("feed_stats", None)
        # a RUN-SCOPED negotiated wire format (uint8 wire; see
        # _run_with_step) must not ride into snapshots: the restored
        # graph carries no normalize prologue, so a granular resume
        # would train on raw un-normalized bytes. Pickle the
        # constructed emit instead — which also keeps identical model
        # state byte-identical regardless of which wire the producing
        # run negotiated.
        pristine = d.pop("_emit_pristine", None)
        if pristine is not None:
            d["emit"] = pristine
        return d

    @_tracer.in_phase("setup.loader")
    def initialize(self, device=None, **kwargs: Any):
        self.load_data()
        # A restored (snapshot-unpickled) loader arrives with its shuffle
        # order, schedule and cursor intact; re-deriving them here would
        # fork the resumed trajectory from the uninterrupted one (an
        # extra shuffle draw + a cursor reset to the epoch start). Keep
        # the carried state and only rebuild the data-dependent pieces.
        restored = getattr(self, "_restored", False) \
            and bool(getattr(self, "_schedule", None))
        self._restored = False
        if not restored:
            offset = 0
            for cls in (TEST, VALIDATION, TRAIN):
                n = self.class_lengths[cls]
                self._indices_per_class[cls] = np.arange(
                    offset, offset + n, dtype=np.int64)
                offset += n
            #: pristine train index list: every epoch's order is drawn
            #: from it
            self._train_base = self._indices_per_class[TRAIN].copy()
            self._order_seed = int(prng.get().randint(0, 2 ** 31))
            self._orders = {}
            self._start_epoch()
        self.total_samples = sum(self.class_lengths)
        # Shape-probe fill: downstream units size their buffers off
        # minibatch_data at initialize time (the reference allocated its
        # minibatch Arrays in Loader.initialize too). The first run() refills
        # the same indices, so this is idempotent.
        cls, b, _ = self._schedule[0]
        chosen, valid = self._batch_rows(cls, b, self._indices_per_class[cls])
        self.fill_minibatch(chosen)
        self.minibatch_indices.reset(chosen)
        self.minibatch_valid.reset(valid)
        return super().initialize(device=device, **kwargs)

    def _train_order(self, epoch: int) -> np.ndarray:
        """The train index order of `epoch`: a function of the order
        seed and the epoch number alone, in an array of its own. It is
        the same whenever it is asked for (at the rollover, or up to
        `prefetch` fills earlier by a lookahead that reaches into the
        epoch) and takes nothing from the shared `prng.get()` stream,
        whose draws belong to the units between two rollovers. Held in
        `_orders` until the rollover adopts it."""
        order = self._orders.get(epoch)
        if order is not None:
            return order
        gen = np.random.RandomState([self._order_seed, epoch])
        if self.balanced_train and self.class_lengths[TRAIN]:
            labels = self.train_labels()
            if labels is None:
                raise ValueError(
                    f"{type(self).__name__}: balanced_train needs "
                    "train_labels() (integer labels in pristine order)")
            counts = np.bincount(labels).astype(np.float64)
            p = 1.0 / counts[labels]
            p /= p.sum()
            order = self._train_base[
                gen.choice(len(labels), size=len(labels), p=p)]
        elif self.shuffle_train:
            order = self._train_base[gen.permutation(len(self._train_base))]
        else:
            order = self._train_base
        self._orders[epoch] = order
        return order

    def _start_epoch(self) -> None:
        self._indices_per_class[TRAIN] = self._train_order(self.epoch_number)
        del self._orders[self.epoch_number]
        self._schedule = []
        for cls in (TEST, VALIDATION, TRAIN):
            n = self.class_lengths[cls]
            if n == 0:
                continue
            n_batches = -(-n // self.minibatch_size)  # ceil
            for b in range(n_batches):
                self._schedule.append((cls, b, b == n_batches - 1))
        self._cursor = 0

    def _batch_rows(self, cls: int, b: int, idx: np.ndarray):
        """(global indices, 0/1 valid mask) of batch `b` of class `cls`
        under the index list `idx`: the last batch of a class wraps to
        the list's start, and the wrapped rows are not valid."""
        at = np.arange(b * self.minibatch_size, (b + 1) * self.minibatch_size)
        return idx[at % len(idx)], (at < len(idx)).astype(np.float32)

    @property
    def next_batch_seq(self) -> int:
        """The sequence number the next run()'s batch will carry."""
        return self._seq_base + self._cursor

    def run(self) -> None:
        # (overrides AcceleratedUnit.run: one code path, host index math)
        cls, b, last = self._schedule[self._cursor]
        self.batch_seq = self.next_batch_seq
        chosen, valid = self._batch_rows(cls, b, self._indices_per_class[cls])
        self.minibatch_class = cls
        self.last_minibatch <<= last
        self.not_train <<= (cls != TRAIN)
        self.minibatch_indices.reset(chosen)
        self.minibatch_valid.reset(valid)
        self.fill_minibatch(chosen)
        if self.on_device and self.device is not None \
                and getattr(self.device, "backend_name", "") == "xla":
            self.minibatch_data.devmem(self.device)
            self.minibatch_labels.devmem(self.device)
        self._cursor += 1
        at_end = self._cursor >= len(self._schedule)
        self.epoch_ended <<= at_end
        if at_end:
            # Only this thread reads epoch_number. A producer is handed
            # the epoch of its batch together with the batch's indices
            # (PrefetchingLoader._produce_one), and the lookahead of an
            # epoch's last fills already works for the next epoch: what
            # the rollover changes, no produce thread reads.
            self.epoch_number += 1
            self._seq_base += len(self._schedule)
            self._start_epoch()

    # -- data-parallel partitioning (IDistributable-shaped; SPMD sharding) ---

    def shard_batch(self, n_shards: int, shard: int) -> slice:
        """The slice of the current minibatch owned by data-parallel shard
        `shard` (parity: the reference master handed each slave a disjoint
        index range via generate_data_for_slave)."""
        per = self.minibatch_size // n_shards
        return slice(shard * per, (shard + 1) * per)

    def generate_data_for_slave(self, slave: Any = None) -> Any:
        return {"indices": self.minibatch_indices.mem}

    def apply_data_from_master(self, data: Any) -> None:
        if data and "indices" in data:
            self.fill_minibatch(np.asarray(data["indices"]))

    def generate_data_for_master(self) -> Any:
        """Update piece: this process's epoch/minibatch accounting (the
        reference slaves reported per-minibatch metrics upstream)."""
        return {"epoch_number": self.epoch_number,
                "cursor": int(getattr(self, "_cursor", 0)),
                "rows_decoded": int(getattr(self, "rows_decoded", 0))}


class PrefetchingLoader(Loader):
    """Loader whose minibatch production runs on background threads with
    `prefetch` batches of exact lookahead at EVERY fill. The schedule is
    deterministic and an epoch's train order is a function of the order
    seed and the epoch number (`Loader._train_order`), so future index
    sets are known across the epoch boundary too: the lookahead of an
    epoch's last `prefetch` fills produces the next epoch's first
    batches, and the rollover finds them in `_pending` under their
    batch numbers. Subclasses implement `_produce_batch(indices) ->
    (x, y)` — an image decode, a memmap gather, … — and inherit the
    overlap machinery: host input prep runs concurrently with device
    compute (the property that matters on TPU; SURVEY.md §2.7)."""

    def __init__(self, workflow=None, n_workers: int = 2,
                 prefetch: int = 2, hflip: bool = False,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.n_workers = n_workers
        self.prefetch = prefetch
        #: seeded horizontal-flip augmentation on TRAIN samples only (the
        #: AlexNet-era recipe's one standing augmentation). Host-side, on
        #: the produce threads; eval/test batches are never flipped.
        self.hflip = hflip
        self._hflip_seed = 0
        self._pool = None
        #: lookahead in production: batch number -> future
        self._pending: dict = {}
        #: multi-host input sharding: when set (by run_fused on a mesh
        #: spanning processes), `local_rows_fn(n) -> bool (n,)` marks the
        #: GLOBAL batch rows whose device shards this process owns. Only
        #: those rows are decoded; the rest are zero-filled — the jit's
        #: data-axis in_shardings never transfer or read them, so host
        #: decode cost divides by the host count. Not pickled: re-wired by
        #: the next run.
        self.local_rows_fn = None
        #: decoded-row counter (tests/observability)
        self.rows_decoded = 0
        self._reset_produce_stats()
        #: guards rows_decoded increments from pool workers; created
        #: HERE (and re-created on unpickle), never lazily on the
        #: produce threads — two workers racing the lazy `if None:
        #: create` each made their own lock and lost increments
        self._count_lock = threading.Lock()

    def initialize(self, device=None, **kwargs: Any):
        # a restored loader keeps its pickled flip seed (and must NOT
        # re-draw: the snapshotted "hflip" generator stream already
        # reflects the original draw — same restored gate as the
        # schedule/cursor preservation in Loader.initialize)
        if self.hflip and not getattr(self, "_restored", False):
            self._hflip_seed = int(prng.get("hflip").randint(0, 2 ** 31))
        # a live loader initialized again draws its orders anew: what
        # is still pending was produced for the old ones
        self._drop_pending()
        return super().initialize(device=device, **kwargs)

    def _produce_batch(self, indices: np.ndarray):
        raise NotImplementedError

    def _flip_mask(self, indices: np.ndarray,
                   epoch: int) -> Optional[np.ndarray]:
        """Per-(sample, epoch) horizontal-flip coins for TRAIN rows, or
        None when augmentation is off. A stateless integer hash decides
        each coin so produce threads need no shared RNG state and
        re-visits flip identically within an epoch but differently
        across epochs; `epoch` is the batch's own (a lookahead batch
        may belong to the epoch after the loader's current one).
        Shared by the numpy `_augment` path and the
        native gather (loader/memmap.py), which folds the flip into its
        row copy."""
        if not self.hflip:
            return None
        train_lo = self.class_lengths[TEST] + self.class_lengths[VALIDATION]
        h = (indices.astype(np.uint64) * np.uint64(2654435761)
             + np.uint64(epoch + 1) * np.uint64(0x9E3779B9)
             + np.uint64(self._hflip_seed))
        h ^= h >> np.uint64(15)
        h *= np.uint64(0x2545F4914F6CDD1D)
        flip = ((h >> np.uint64(32)) & np.uint64(1)).astype(bool)
        flip &= indices >= train_lo
        return flip

    def _augment(self, x: np.ndarray, indices: np.ndarray,
                 epoch: int) -> np.ndarray:
        """Seeded horizontal flip of TRAIN rows (see _flip_mask)."""
        if x.ndim < 3:
            return x
        flip = self._flip_mask(indices, epoch)
        if flip is not None and flip.any():
            x = np.ascontiguousarray(x)
            x[flip] = x[flip, :, ::-1]
        return x

    def _produce_rows(self, indices: np.ndarray, epoch: int):
        """Materialize rows for exactly these indices as epoch `epoch`
        sees them (subclass hook for custom gather paths; the default
        decodes + augments)."""
        x, y = self._produce_batch(indices)
        return self._augment(x, indices, epoch), y

    def local_rows_mask(self, n: int) -> np.ndarray:
        """The partition kernel behind `generate_data_for_slave`: which
        of `n` global-batch rows THIS process must materialize (all of
        them outside multi-host runs)."""
        fn = self.local_rows_fn
        return np.ones(n, bool) if fn is None else np.asarray(fn(n))

    def generate_data_for_slave(self, slave: Any = None) -> Any:
        """Job piece for this data-parallel participant: the minibatch
        indices plus the row mask its device shards own — the reference
        master's disjoint-index-range handout, computed SPMD-side."""
        piece = super().generate_data_for_slave(slave)
        piece["local_rows"] = self.local_rows_mask(self.minibatch_size)
        return piece

    def _produce(self, indices: np.ndarray, epoch: int):
        if self.local_rows_fn is not None:
            mask = self.local_rows_mask(len(indices))
            if not mask.all():
                x, y = self._produce_rows(indices[mask], epoch)
                self._count_rows(int(mask.sum()))
                fx = np.zeros((len(indices),) + x.shape[1:], x.dtype)
                fy = np.zeros((len(indices),) + y.shape[1:], y.dtype)
                fx[mask] = x
                fy[mask] = y
                return fx, fy
        x, y = self._produce_rows(indices, epoch)
        self._count_rows(len(indices))
        return x, y

    def _reset_produce_stats(self) -> None:
        """Process-local observability (feed.stats(), veles_loader_*),
        never pickled: seconds inside _produce summed over the produce
        threads and batches it completed (under _count_lock); fills
        whose lookahead future was done when asked / that had to wait,
        and futures submitted for a batch of a later epoch (driver
        thread); the registry handles, bound when the produce pool
        starts."""
        self.produce_s = 0.0
        self.batches_produced = 0
        self.lookahead_ready = 0
        self.lookahead_waited = 0
        self.lookahead_cross_epoch = 0
        self._m = None

    def _produce_one(self, indices: np.ndarray, seq: int, epoch: int):
        """`_produce` as the pool (or a fill with no lookahead) runs it:
        one `loader.produce` span and one count per batch, recorded on
        the thread that does the work. The batch's epoch comes with its
        indices: a producer reads nothing that a rollover changes."""
        t0 = time.perf_counter()
        with _tracer.span("loader.produce", "loader", seq):
            out = self._produce(indices, epoch)
        dt = time.perf_counter() - t0
        with self._count_lock:
            self.produce_s += dt
            self.batches_produced += 1
        self._m.produce_s.inc(dt)
        self._m.produced.inc()
        return out

    def _count_rows(self, n: int) -> None:
        # _produce runs on pool worker threads: a bare += would lose
        # increments under interleaving
        with self._count_lock:
            self.rows_decoded += n

    def _batch_at(self, pos: int):
        """(indices, epoch) of the batch `pos` places into the current
        epoch's schedule. Past its end lie the next epochs (every epoch
        has the same schedule), whose train order is drawn here, now."""
        ahead, at = divmod(pos, len(self._schedule))
        epoch = self.epoch_number + ahead
        cls, b, _ = self._schedule[at]
        idx = (self._train_order(epoch) if ahead and cls == TRAIN
               else self._indices_per_class[cls])
        return self._batch_rows(cls, b, idx)[0], epoch

    def _drop_pending(self) -> None:
        for fut in self._pending.values():
            fut.cancel()
        self._pending.clear()

    def fill_minibatch(self, indices: np.ndarray) -> None:
        from concurrent.futures import CancelledError, ThreadPoolExecutor
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.n_workers,
                thread_name_prefix=f"{self.name}-produce")
            self._m = _metrics.loader_handles()
        m = self._m
        seq = self.next_batch_seq
        # the lookahead is valid for the schedule's indices only; a
        # caller feeding different ones (e.g. a master's
        # apply_data_from_master) must get THOSE indices, and nothing
        # is produced ahead for a caller the schedule does not describe
        scheduled = np.array_equal(indices, self._batch_at(self._cursor)[0])
        if not scheduled:
            self._drop_pending()
        fut = self._pending.pop(seq, None)
        if fut is not None and fut.done() and not fut.cancelled():
            self.lookahead_ready += 1
            m.ready.inc()
        else:
            self.lookahead_waited += 1
            m.waited.inc()
        try:
            x, y = (fut.result() if fut is not None else
                    self._produce_one(indices, seq, self.epoch_number))
        except CancelledError:
            # stop() from another thread (manhole, Ctrl-C handler)
            # cancelled the lookahead mid-fill: produce synchronously so
            # the pump loop winds down cleanly instead of crashing
            x, y = self._produce_one(indices, seq, self.epoch_number)
        self.minibatch_data.reset(x)
        self.minibatch_labels.reset(y)
        if not scheduled:
            return
        for ahead in range(1, self.prefetch + 1):
            if seq + ahead in self._pending:
                continue
            nxt, epoch = self._batch_at(self._cursor + ahead)
            try:
                self._pending[seq + ahead] = self._pool.submit(
                    self._produce_one, nxt, seq + ahead, epoch)
            except RuntimeError:     # pool shut down by concurrent stop()
                break
            if epoch != self.epoch_number:
                self.lookahead_cross_epoch += 1
                m.cross_epoch.inc()

    def set_emit(self, emit: str) -> None:
        """Flip the wire dtype mid-run (the device feed's uint8-wire
        negotiation), dropping any lookahead produced under the old
        format — a pending float32 future handed to a step built with a
        uint8 prologue would be normalized twice. No-op for loaders
        without an `emit` knob or when the format is unchanged."""
        if getattr(self, "emit", None) in (None, emit):
            return
        # Negotiation happens between runs on the driver thread; every
        # pending produce future is cancelled and the lookahead queue
        # cleared below, so no consumer ever observes a half-switched
        # wire — and a worst-case mid-write read is a torn-free str
        # whose result is discarded with the cancelled future.
        # velint: disable=shared-write-no-lock
        self.emit = emit
        self._drop_pending()

    def stop(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
        self._pending.clear()

    def __setstate__(self, d):
        super().__setstate__(d)
        # pickled as None (locks don't pickle); re-created on the
        # unpickling thread, before any produce pool exists
        self._count_lock = threading.Lock()
        self._reset_produce_stats()

    def __getstate__(self):
        d = super().__getstate__()
        d["_pool"] = None
        d["_pending"] = {}
        d["_count_lock"] = None
        for k in ("_m", "produce_s", "batches_produced", "lookahead_ready",
                  "lookahead_waited", "lookahead_cross_epoch"):
            d.pop(k, None)      # timing floats must not reach a pickle
        d["local_rows_fn"] = None   # step-bound closure: re-wired by run
        return d
