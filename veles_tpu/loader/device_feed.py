"""DeviceFeed: the one async host->device input pipeline.

The north-star metric is END-TO-END samples/s, host pipeline included
(the `alexnet.feed` cell of BENCHMARK.json), and the loader contract the
reference established is host prep overlapped with device compute
(SURVEY.md §2.7). Without this module the production loop
(`StandardWorkflow._run_with_step`, everything `run_fused`/
`run_pipelined`/`--supervise` actually executes) would pass host numpy
straight into the jitted step, paying the H2D transfer synchronously
inside dispatch, on the critical path.

`DeviceFeed` wraps any `Loader` and yields device-resident batches ONE
step ahead: right after step *k* is DISPATCHED (dispatch is async — the
device is still executing), the driver calls `prefetch()`, which pops
batch *k+1* from the loader (whose `PrefetchingLoader` threads did the
host prep concurrently) and issues an **async sharded
`jax.device_put`** to the step's data-axis input shardings — so the
transfer rides under step *k*'s compute instead of serializing after
it. Each `FeedBatch` carries the per-batch Decision metadata
(`minibatch_class`, `last_minibatch`, valid mask) snapshotted at
production time, and `next()` replays it onto the loader, so the epoch
bookkeeping downstream (`DecisionGD` reads the loader's attrs through
`link_attrs`) stays aligned with the batch being trained, not the
batch being prefetched.

Why `prefetch()` is a SEPARATE call at the bottom of the driver loop
(after the Decision/snapshot window) instead of an eager fill inside
`next()`: a snapshot pickles the whole workflow, loader cursor
included. Producing batch k+1 before the snapshot branch would pickle
a cursor one batch PAST the trained one, and a restore would silently
skip that batch — forking the resumed trajectory from the
uninterrupted run (the exact-resume contract, proven bit-identical by
tests/dist_ft_worker.py). With prefetch after the snapshot window the
pickled cursor always equals consumed+1, exactly as the synchronous
loop it replaced, while the transfer still overlaps the executing
step.

Sharding: `make_batch_put(step)` derives the put from the step —
`P("data")` leading-dim shardings for fused dp/gspmd/seq steps,
replicated for the GPipe pipeline step, a plain async `device_put` when
the step has no mesh. On a MULTI-HOST mesh `device_put` cannot target
non-addressable shards, so the feed degrades to host handoff (the jit's
uniform-host-input convention transfers only local shards, exactly as
before) — the `local_rows` zero-fill decode sharding set up by
`_run_with_step` still applies, so host decode cost divides by the host
count either way.

Wire format: when the loader offers `wire_format()` (memmap/image
loaders), `StandardWorkflow` flips it to uint8 emission and builds the
step with a matching on-device `input_normalize` prologue — raw bytes
leave the host (4x less H2D traffic and host conversion), normalization
fuses into the first layer's HBM read. The feed's byte counters make
this mechanically checkable: `stats()["bytes_per_batch"]` drops 4x.

Overlap observability: the feed counts time blocked on the loader
(host pipeline too slow), time inside the put call, batches fed ahead,
bytes per batch, and whether a popped batch had reached the device
(`is_ready()`, asked without blocking) — in `stats()` (with the
loader's own counters beside them: `produce_s`, `batches_produced`,
`lookahead_ready`, `lookahead_waited`, `lookahead_cross_epoch`) and,
written by the feed
itself per batch, in the one metrics registry (`veles_feed_*`). Spans
(`feed.produce` > `loader.run`, `feed.device_put`) carry the batch's
sequence number and land in the `--trace` ring and in any open profiler
session (telemetry/tracer.py).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from veles_tpu.loader.base import TRAIN
from veles_tpu.telemetry import metrics as _metrics
from veles_tpu.telemetry import tracer as _tracer

#: how many trailing per-epoch counter rows stats() keeps
_EPOCH_LOG_KEEP = 8


class FeedBatch:
    """One device-fed minibatch plus the Decision metadata that describes
    it (snapshotted at production time — the loader itself has already
    moved on to the next batch)."""

    __slots__ = ("x", "y", "w", "w_host", "minibatch_class",
                 "last_minibatch", "epoch_ended", "bytes_h2d",
                 "loader_block_s", "seq")

    def __init__(self) -> None:
        self.x = self.y = self.w = None
        self.seq = -1
        self.w_host: Optional[np.ndarray] = None
        self.minibatch_class = TRAIN
        self.last_minibatch = False
        self.epoch_ended = False
        self.bytes_h2d = 0
        self.loader_block_s = 0.0


def make_batch_put(step) -> Optional[Callable]:
    """The async transfer callable for `step`'s data inputs: takes a
    tuple of host arrays, returns matching device arrays laid out per
    the step's input shardings (leading-dim specs; extra trailing dims
    replicate). Returns None when the feed must fall back to host
    handoff — a mesh spanning processes, where `jax.device_put` rejects
    shardings with non-addressable devices and the jit's uniform-host-
    input convention already transfers only the local shards. Shared by
    DeviceFeed and the serving warm path (one transfer implementation,
    no bespoke loops)."""
    import jax

    mesh = getattr(step, "mesh", None)
    if mesh is None:
        def put(arrays: Tuple) -> Tuple:
            # async: returns immediately, the H2D transfer rides under
            # whatever the device is already executing
            return tuple(jax.device_put(a) for a in arrays)
        return put
    from veles_tpu.parallel.mesh import is_multihost
    if is_multihost(mesh):
        return None
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    specs_fn = getattr(step, "input_put_specs", None)
    specs = specs_fn() if callable(specs_fn) else (P(), P(), P())
    shardings = tuple(NamedSharding(mesh, s) for s in specs)

    def put(arrays: Tuple) -> Tuple:
        return tuple(jax.device_put(a, s)
                     for a, s in zip(arrays, shardings))
    return put


def make_input_put(step) -> Optional[Callable]:
    """The async transfer callable for a single INPUT batch under the
    step's data-axis spec (``input_put_specs()[0]``) — the x-only twin
    of `make_batch_put`, shared by the serving slot ring (ISSUE 15: the
    ring batch lands on device in the SAME sharding training batches
    do, and the put is async so the transfer rides under the executing
    forward — the DeviceFeed double-buffer pattern pointed at
    inference). None on multi-host meshes, same degrade rule as
    make_batch_put."""
    import jax

    mesh = getattr(step, "mesh", None)
    if mesh is None:
        return lambda a: jax.device_put(a)
    from veles_tpu.parallel.mesh import is_multihost
    if is_multihost(mesh):
        return None
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    specs_fn = getattr(step, "input_put_specs", None)
    spec = (specs_fn() if callable(specs_fn) else (P(),))[0]
    sharding = NamedSharding(mesh, spec)
    return lambda a: jax.device_put(a, sharding)


class DeviceFeed:
    """Async device-feed over a Loader — the double buffer as a
    reusable component. Driver contract:

        b = feed.next()          # pop (transfer issued one step ago)
        state = step.train(state, b.x, b.y, b.w)   # async dispatch
        ... bookkeeping / snapshot window (pickles see cursor==b) ...
        feed.prefetch()          # k+1's put rides under step k

    `put` is `(x, y, w) tuple -> device tuple` (None = host handoff:
    arrays pass through untouched and the jitted step transfers them at
    dispatch). `ahead` is the lookahead depth: `ahead=1` is the classic
    double buffer, `0` disables lookahead (produce on demand, puts
    still async). A driver that stops calling prefetch() once the run
    completes wastes zero batches.

    NOTE `ahead >= 2` leaves ahead-1 batches pending ACROSS the
    bookkeeping window, so a snapshot taken there pickles a cursor that
    far past the trained batch — a restore would skip those batches.
    Drivers that snapshot mid-run must clamp to 1 (`_run_with_step`
    does); deeper lookahead is only exact-resume-safe for loops that
    never pickle the loader (bench).
    """

    def __init__(self, loader, put: Optional[Callable] = None,
                 ahead: int = 1) -> None:
        self.loader = loader
        self._put = put
        self.ahead = max(0, int(ahead))
        self._queue: deque = deque()
        self._n = 0
        self._on_demand = 0
        self._epochs = 0
        self._bytes = 0
        self._bytes_last = 0
        self._loader_block_s = 0.0
        self._put_block_s = 0.0
        self._device_sync_s = 0.0
        self._h2d_ready = 0
        self._h2d_late = 0
        self._epoch_acc = {"batches": 0, "bytes_h2d": 0,
                           "loader_block_s": 0.0, "device_sync_s": 0.0}
        #: an epoch-ending batch was CONSUMED but its row not yet rolled
        #: (held open so the class-pass-boundary device sync noted right
        #: after consumption lands in the epoch it belongs to)
        self._pending_roll = False
        self._epoch_log: List[Dict[str, Any]] = []
        self._last_dtype = None
        #: pre-bound registry counters: this feed is their producer
        self._m = _metrics.feed_handles()

    @classmethod
    @_tracer.in_phase("setup.loader")
    def for_step(cls, loader, step, ahead: int = 1) -> "DeviceFeed":
        """Feed wired to `step`'s input shardings (multi-host meshes
        degrade to host handoff — see make_batch_put)."""
        return cls(loader, put=make_batch_put(step), ahead=ahead)

    @property
    def sharded_put(self) -> bool:
        """False = host-handoff fallback (multi-host mesh)."""
        return self._put is not None

    # -- production -----------------------------------------------------------

    def _produce(self) -> FeedBatch:
        ld, span, m = self.loader, _tracer.span, self._m
        b = FeedBatch()
        b.seq = seq = getattr(ld, "next_batch_seq", self._n)
        with span("feed.produce", "feed", seq):
            t0 = time.perf_counter()
            with span("loader.run", "feed", seq):
                ld.run()
            t1 = time.perf_counter()
            x = ld.minibatch_data.mem
            y = ld.minibatch_labels.mem
            w = ld.minibatch_valid.mem
            b.minibatch_class = ld.minibatch_class
            b.last_minibatch = bool(ld.last_minibatch)
            b.epoch_ended = bool(ld.epoch_ended)
            b.w_host = w
            b.bytes_h2d = int(getattr(x, "nbytes", 0)
                              + getattr(y, "nbytes", 0)
                              + getattr(w, "nbytes", 0))
            # the trace's overlap evidence: this device_put span lies
            # under step k's device time when batch k+1 transfers
            # beneath the executing compute
            with span("feed.device_put", "feed", seq):
                if self._put is not None:
                    b.x, b.y, b.w = self._put((x, y, w))
                else:
                    b.x, b.y, b.w = x, y, w
            t2 = time.perf_counter()
        b.loader_block_s = t1 - t0
        self._loader_block_s += t1 - t0
        self._put_block_s += t2 - t1
        self._n += 1
        self._bytes += b.bytes_h2d
        self._bytes_last = b.bytes_h2d
        self._last_dtype = getattr(x, "dtype", None)
        m.batches.inc()
        m.bytes_h2d.inc(b.bytes_h2d)
        m.loader_block_s.inc(t1 - t0)
        m.put_s.inc(t2 - t1)
        return b

    def _flush_epoch(self) -> None:
        """Roll the held-open epoch row (see _pending_roll)."""
        if not self._pending_roll:
            return
        self._pending_roll = False
        self._epochs += 1
        row = {"epoch": self._epochs}
        row.update({k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in self._epoch_acc.items()})
        self._epoch_log.append(row)
        del self._epoch_log[:-_EPOCH_LOG_KEEP]
        self._epoch_acc = {"batches": 0, "bytes_h2d": 0,
                           "loader_block_s": 0.0, "device_sync_s": 0.0}
        # observability hook: loader_throughput() and anything else
        # holding the loader can read the feed's counters from it
        self.loader.feed_stats = self.stats()

    # -- consumption ----------------------------------------------------------

    def next(self) -> FeedBatch:
        """Pop the current batch (its device_put was issued by the
        previous iteration's prefetch()) and replay its Decision
        metadata onto the loader so downstream bookkeeping describes
        the batch being trained. Produces on demand when nothing is
        pending (the first batch, or ahead=0)."""
        if not self._queue:
            self._on_demand += 1
            self._m.on_demand.inc()
            self._queue.append(self._produce())
        b = self._queue.popleft()
        if hasattr(b.x, "is_ready"):
            # had the transfer finished when the loop took the batch?
            # (asked, never waited for: the step dispatched on a late
            # batch waits on the device's side)
            if b.x.is_ready() and b.y.is_ready() and b.w.is_ready():
                self._h2d_ready += 1
                self._m.h2d_ready.inc()
            else:
                self._h2d_late += 1
                self._m.h2d_late.inc()
        # per-epoch rows are keyed by CONSUMPTION (a pending batch
        # produced past the boundary must not land in the old epoch's
        # row), and the ending row stays open until the next pop /
        # stats() so the boundary device sync noted after this call is
        # attributed to the epoch it closed
        self._flush_epoch()
        acc = self._epoch_acc
        acc["batches"] += 1
        acc["bytes_h2d"] += b.bytes_h2d
        acc["loader_block_s"] += b.loader_block_s
        if b.epoch_ended:
            self._pending_roll = True
        self._replay(b)
        return b

    def prefetch(self) -> None:
        """Produce + issue the async put for up to `ahead` batches
        beyond those already pending. Call AFTER dispatching the step
        and after any Decision/snapshot window: the transfer overlaps
        the still-executing step, and a snapshot taken between next()
        and prefetch() pickles a loader cursor exactly at the consumed
        batch (the exact-resume contract — see the module docstring)."""
        while len(self._queue) < self.ahead:
            self._queue.append(self._produce())

    def _replay(self, b: FeedBatch) -> None:
        """Write batch `b`'s bookkeeping onto the loader. The loader's
        cursor is `ahead` batches past the one being consumed (which is
        exactly what a snapshot should capture: the pending batches are
        re-produced on restore), but the attrs the Decision unit reads
        through link_attrs must describe the CONSUMED batch."""
        ld = self.loader
        ld.minibatch_class = b.minibatch_class
        ld.last_minibatch <<= b.last_minibatch
        ld.not_train <<= (b.minibatch_class != TRAIN)
        ld.epoch_ended <<= b.epoch_ended
        ld.minibatch_valid.reset(b.w_host)

    def note_device_sync(self, seconds: float) -> None:
        """Record time the DRIVER spent blocked on the device (the
        class-pass-boundary host sync in `_run_with_step`) so stats()
        decomposes blocked time into loader vs device."""
        self._device_sync_s += seconds
        self._epoch_acc["device_sync_s"] += seconds
        self._m.device_sync_s.inc(seconds)

    def stop(self) -> None:
        """Drop pending batches and stop the loader's produce threads
        (idempotent; safe to combine with Workflow._stop_units)."""
        self._queue.clear()
        self._flush_epoch()
        self.loader.feed_stats = self.stats()
        stop = getattr(self.loader, "stop", None)
        if stop is not None:
            stop()

    # -- observability --------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Overlap counters: batches/bytes fed, uint8-wire flag, time
        blocked on the host pipeline vs the device, lookahead health
        (`on_demand` > first batch means the loader fell behind)."""
        self._flush_epoch()
        ld = self.loader
        return {
            "batches": self._n,
            "epochs": self._epochs,
            "ahead": self.ahead,
            "sharded_put": self.sharded_put,
            "bytes_h2d": self._bytes,
            "bytes_per_batch": self._bytes_last,
            "uint8_wire": bool(self._last_dtype == np.uint8),
            "loader_block_s": round(self._loader_block_s, 6),
            "put_block_s": round(self._put_block_s, 6),
            "device_sync_s": round(self._device_sync_s, 6),
            # batches the consumer had to wait a full produce for: 1 is
            # the unavoidable first batch; growth = loader too slow
            "on_demand": self._on_demand,
            # popped batches already on the device / still in transfer
            "h2d_ready": self._h2d_ready,
            "h2d_late": self._h2d_late,
            # the loader's own counters (PrefetchingLoader; 0 elsewhere):
            # produce-thread seconds and batches, whether the lookahead
            # future was done when a fill asked for it, and futures
            # submitted for a batch of the next epoch
            "produce_s": round(getattr(ld, "produce_s", 0.0), 6),
            "batches_produced": getattr(ld, "batches_produced", 0),
            "lookahead_ready": getattr(ld, "lookahead_ready", 0),
            "lookahead_waited": getattr(ld, "lookahead_waited", 0),
            "lookahead_cross_epoch": getattr(ld, "lookahead_cross_epoch", 0),
            "epoch_log": list(self._epoch_log),
        }
