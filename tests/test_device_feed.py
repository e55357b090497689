"""DeviceFeed (loader/device_feed.py): the async device-feed pipeline
that overlaps H2D with compute in the REAL training loop (ISSUE 5).

Mechanical off-chip verification of the overlap contract:
- the feed issues the async put for batch k+1 BEFORE batch k's result is
  consumed (recording-stub lookahead test);
- Decision metadata stays aligned with the batch it describes even
  though the loader's cursor runs ahead;
- memmap-fed fused training ships uint8 over the wire (per-batch H2D
  bytes exactly /4 on the image tensor vs the float path, asserted on
  the feed's byte counter) while matching the float path's numerics;
- the benchmark's fed cell and _run_with_step consume the SAME feed
  implementation (contract test — no bespoke loops);
- clean stop() releases the loader's produce threads (the conftest
  leaked-thread check enforces it for every test in the suite).
"""

import inspect

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.loader.base import TRAIN, VALIDATION
from veles_tpu.loader.device_feed import DeviceFeed, make_batch_put
from veles_tpu.loader.synthetic import SyntheticClassifierLoader


def make_loader(minibatch=10, n_validation=20, n_train=40):
    prng.seed_all(3)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(6,), n_validation=n_validation,
        n_train=n_train, minibatch_size=minibatch, shuffle_train=False)
    loader.initialize(device=None)
    return loader


class RecordingPut:
    """device_put stub: records every issued transfer, hands the host
    arrays through untouched."""

    def __init__(self):
        self.calls = []

    def __call__(self, arrays):
        self.calls.append(tuple(np.asarray(a).nbytes for a in arrays))
        return arrays


def test_lookahead_put_issued_before_consumption():
    """The overlap property, mechanically: with ahead=1, the put for
    batch k+1 is on record (prefetch after dispatch) BEFORE batch k's
    results are consumed — and the steady state produces exactly one
    batch per (next, prefetch) cycle."""
    loader = make_loader()
    put = RecordingPut()
    feed = DeviceFeed(loader, put=put, ahead=1)
    b0 = feed.next()
    assert len(put.calls) == 1
    assert b0.minibatch_class == VALIDATION
    # "step k dispatched" here; its results are untouched — k+1 flies:
    feed.prefetch()
    assert len(put.calls) == 2      # batch 1 in flight under "step 0"
    b1 = feed.next()
    assert len(put.calls) == 2      # popped the pending one, no produce
    assert b1.minibatch_class == VALIDATION and b1.last_minibatch
    feed.prefetch()
    assert len(put.calls) == 3
    assert feed.stats()["on_demand"] == 1   # only the unavoidable first


def test_lookahead_depth_configurable():
    loader = make_loader()
    put = RecordingPut()
    feed = DeviceFeed(loader, put=put, ahead=3)
    feed.next()
    feed.prefetch()
    assert len(put.calls) == 4      # popped 1, 3 still in flight
    assert feed.stats()["ahead"] == 3

    loader0 = make_loader()
    put0 = RecordingPut()
    feed0 = DeviceFeed(loader0, put=put0, ahead=0)
    feed0.next()
    feed0.prefetch()                # no-op at depth 0
    assert len(put0.calls) == 1     # no lookahead: produce on demand


def test_metadata_alignment_through_full_epoch():
    """Each FeedBatch describes the batch it CARRIES (class, last flag,
    epoch boundary), and next() replays that metadata onto the loader —
    even though the loader itself has already produced one batch ahead."""
    loader = make_loader(minibatch=10, n_validation=20, n_train=40)
    feed = DeviceFeed(loader, put=None, ahead=1)
    expected = [(VALIDATION, False), (VALIDATION, True),
                (TRAIN, False), (TRAIN, False), (TRAIN, False),
                (TRAIN, True)]
    for i, (cls, last) in enumerate(expected):
        b = feed.next()
        assert (b.minibatch_class, b.last_minibatch) == (cls, last), i
        assert b.epoch_ended == (i == len(expected) - 1)
        # the replay: Decision reads these loader attrs via link_attrs
        assert loader.minibatch_class == cls
        assert bool(loader.last_minibatch) == last
        assert bool(loader.not_train) == (cls != TRAIN)
        assert bool(loader.epoch_ended) == b.epoch_ended
        # BEFORE prefetch: the cursor sits exactly at consumed+1, so a
        # snapshot in this window resumes the exact trajectory
        assert loader._cursor == (i + 1) % len(expected)
        feed.prefetch()
        # AFTER prefetch: one batch ahead — that is the overlap
        assert loader._cursor == (i + 2) % len(expected) \
            or loader._cursor == i + 2
    st = feed.stats()
    assert st["epochs"] == 1
    assert st["epoch_log"][0]["batches"] == len(expected)


def test_w_host_is_the_valid_mask():
    loader = make_loader(minibatch=15, n_validation=20, n_train=40)
    feed = DeviceFeed(loader, put=None, ahead=1)
    b = feed.next()     # first validation batch: 15 of 20 rows
    assert b.w_host.sum() == 15
    b = feed.next()     # wrapped final validation batch: 5 valid rows
    assert b.last_minibatch and b.w_host.sum() == 5


def test_byte_counter_and_device_sync():
    loader = make_loader()
    feed = DeviceFeed(loader, put=None, ahead=1)
    b = feed.next()
    per_batch = (b.x.nbytes + np.asarray(b.y).nbytes
                 + np.asarray(b.w_host).nbytes)
    st = feed.stats()
    assert st["bytes_per_batch"] == per_batch == b.bytes_h2d
    assert st["bytes_h2d"] == per_batch
    feed.prefetch()
    assert feed.stats()["bytes_h2d"] == 2 * per_batch   # lookahead too
    feed.note_device_sync(0.25)
    assert feed.stats()["device_sync_s"] == pytest.approx(0.25)


def test_sharded_put_lands_on_data_axis(eight_devices):
    """for_step over a dp-mode fused step: the feed's put commits the
    batch to the step's data-axis sharding before dispatch."""
    import jax
    from veles_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    prng.seed_all(8)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(6,), n_validation=16, n_train=32,
        minibatch_size=16, shuffle_train=False)
    wf = StandardWorkflow(
        layers=[{"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1}, name="FeedDP")
    wf.initialize(device=None)
    mesh = make_mesh(jax.devices(), data=8)
    step = wf.build_fused_step(mesh=mesh, mode="dp")
    feed = DeviceFeed.for_step(loader, step)
    assert feed.sharded_put
    b = feed.next()
    assert isinstance(b.x, jax.Array)
    assert b.x.sharding.spec == jax.sharding.PartitionSpec(DATA_AXIS)
    # the committed layout is what the jitted step consumes
    state = step.init_state()
    loss, n_err = step.evaluate(state, b.x, b.y, b.w)
    assert np.isfinite(float(loss))


def test_run_with_step_trains_through_feed(tmp_path):
    """End-to-end: run_fused (the production loop) drives the feed and
    the Decision bookkeeping lands exactly as the synchronous loop's —
    plus the workflow exposes the feed counters afterwards."""
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    prng.seed_all(13)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(6,), n_validation=20, n_train=60,
        minibatch_size=20)
    wf = StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": 12,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 4, "fail_iterations": 50},
        gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
        name="FeedWF")
    wf.run_fused()
    assert wf.decision.epoch_number == 4
    assert wf.decision.best_validation_err is not None
    st = wf.feed_stats
    assert st["batches"] >= 4 * 4           # 4 epochs x 4 batches
    assert st["epochs"] >= 3                # per-epoch counters rolled
    assert st["bytes_h2d"] > 0


def _memmap_workflow(tmp_path, uint8_wire, sub, max_epochs=3):
    from veles_tpu.loader import memmap as mm
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    rng = np.random.RandomState(2)
    labels = (np.arange(96) % 3).astype(np.int64)
    protos = rng.randint(60, 200, (3, 6, 6, 3)).astype(np.float32)
    data = np.clip(protos[labels] + rng.randn(96, 6, 6, 3) * 10,
                   0, 255).astype(np.uint8)
    perm = rng.permutation(96)
    mean = data.astype(np.float64).mean(0) / 127.5 - 1.0
    out = mm.pack_arrays(str(tmp_path / f"wire_{sub}"), data[perm],
                         labels[perm], [0, 24, 72], shard_mb=0.01,
                         mean_image=mean.astype(np.float32))
    prng.seed_all(21)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=24)
    wf = StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 3,
                 "weights_stddev": 0.05}],
        loader=loader, loss="softmax", n_classes=3,
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 50},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        name=f"Wire-{sub}")
    wf.run_fused(uint8_wire=uint8_wire)
    return wf


def test_uint8_wire_quarters_h2d_bytes(tmp_path):
    """The acceptance-bar assertion: memmap-fed fused training transfers
    uint8 — the image tensor's per-batch H2D bytes are exactly f32/4 on
    the feed's byte counter, and the loader's emit format is restored
    afterwards."""
    wf_u8 = _memmap_workflow(tmp_path, "auto", "u8", max_epochs=1)
    wf_f32 = _memmap_workflow(tmp_path, False, "f32", max_epochs=1)
    overhead = 24 * 8 + 24 * 4          # int64 labels + f32 pad mask
    x_u8 = wf_u8.feed_stats["bytes_per_batch"] - overhead
    x_f32 = wf_f32.feed_stats["bytes_per_batch"] - overhead
    assert x_u8 == 24 * 6 * 6 * 3               # raw bytes on the wire
    assert x_f32 == 4 * x_u8                    # the /4 claim, exactly
    assert wf_u8.feed_stats["uint8_wire"] is True
    assert wf_f32.feed_stats["uint8_wire"] is False
    # negotiation is scoped to the run: the loader leaves as it arrived
    assert wf_u8.loader.emit == "float32"


def test_uint8_wire_matches_float_path_numerics(tmp_path):
    """Auto-negotiated uint8 wire (on-device input_normalize prologue)
    trains the same trajectory as the host-normalized float path — the
    prologue applies exactly `_normalize`'s affine, on device."""
    wf_u8 = _memmap_workflow(tmp_path, "auto", "eq_u8")
    wf_f32 = _memmap_workflow(tmp_path, False, "eq_f32")
    assert wf_u8.decision.best_validation_err == \
        wf_f32.decision.best_validation_err
    np.testing.assert_allclose(
        wf_u8.forwards[-1].weights.mem, wf_f32.forwards[-1].weights.mem,
        rtol=1e-4, atol=1e-5)


def test_uint8_wire_pipeline(tmp_path, eight_devices):
    """The pipeline step gains the same prologue: run_pipelined over a
    memmap loader negotiates the uint8 wire and still trains."""
    from veles_tpu.loader import memmap as mm
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    rng = np.random.RandomState(5)
    labels = (np.arange(64) % 2).astype(np.int64)
    protos = rng.randint(60, 200, (2, 4, 4, 3)).astype(np.float32)
    data = np.clip(protos[labels] + rng.randn(64, 4, 4, 3) * 10,
                   0, 255).astype(np.uint8)
    out = mm.pack_arrays(str(tmp_path / "pp"), data, labels,
                         [0, 16, 48], shard_mb=0.01)
    prng.seed_all(31)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                  mean_normalize=False)
    wf = StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": 8,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 2,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=2,
        decision_config={"max_epochs": 2, "fail_iterations": 50},
        gd_config={"learning_rate": 0.05},
        name="WirePP")
    wf.run_pipelined(n_microbatches=2)
    assert wf.decision.epoch_number == 2
    assert wf.feed_stats["uint8_wire"] is True


def test_mid_run_snapshot_pickles_constructed_emit(tmp_path):
    """The negotiated uint8 wire is RUN-scoped: a snapshot taken inside
    the loop must pickle the loader's CONSTRUCTED emit ("float32"), not
    the negotiated one — a granular resume of a snapshot carrying
    emit="uint8" would train on raw un-normalized bytes, and identical
    model state would pickle to different bytes per wire (review
    finding)."""
    import pickle

    from veles_tpu.loader import memmap as mm
    from veles_tpu.snapshotter import Snapshotter
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    rng = np.random.RandomState(12)
    data = rng.randint(0, 256, (48, 4, 4, 3), dtype=np.uint8)
    out = mm.pack_arrays(str(tmp_path / "snapemit"), data,
                         (np.arange(48) % 2).astype(np.int64),
                         [0, 16, 32], shard_mb=0.01)
    prng.seed_all(71)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                  mean_normalize=False)
    snap_dir = tmp_path / "snaps"
    snap_dir.mkdir()
    wf = StandardWorkflow(
        layers=[{"type": "softmax", "output_sample_shape": 2,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=2,
        decision_config={"max_epochs": 2, "fail_iterations": 50},
        snapshot_config={"directory": str(snap_dir), "prefix": "se"},
        name="SnapEmit")
    wf.run_fused()                      # auto uint8 wire + snapshots
    assert wf.feed_stats["uint8_wire"] is True
    snap = Snapshotter.latest(str(snap_dir), prefix="se")
    assert snap is not None
    restored = Snapshotter.import_(snap)
    assert restored.loader.emit == "float32"    # constructed, not wire
    assert getattr(restored.loader, "_emit_pristine", None) is None


def test_uint8_wire_false_pins_float_emission(tmp_path):
    """run_fused(uint8_wire=False) on a loader CONSTRUCTED with
    emit="uint8" (and no input_normalize layer) must switch it to
    host-normalized float emission for the run — raw 0..255 bytes with
    no prologue would silently train un-normalized (review finding)."""
    from veles_tpu.loader import memmap as mm
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    rng = np.random.RandomState(9)
    data = rng.randint(0, 256, (48, 4, 4, 3), dtype=np.uint8)
    labels = (np.arange(48) % 2).astype(np.int64)
    out = mm.pack_arrays(str(tmp_path / "pin"), data, labels,
                         [0, 16, 32], shard_mb=0.01)
    prng.seed_all(51)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                  emit="uint8", mean_normalize=False)
    wf = StandardWorkflow(
        layers=[{"type": "softmax", "output_sample_shape": 2,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=2,
        decision_config={"max_epochs": 1}, name="PinWF")
    spec = wf._wire_spec(False)
    assert spec == {"emit": "float32", "normalize": None}
    wf.run_fused(uint8_wire=False)
    assert wf.feed_stats["uint8_wire"] is False   # floats on the wire
    assert wf.loader.emit == "uint8"              # restored afterwards


def _boundary_feed(out, restored=None):
    """16 validation + 48 train rows at batch 16 (a 4-batch epoch,
    reshuffled and flipped anew each epoch, three batches of lookahead)
    behind the feed as `_run_with_step` builds it."""
    from veles_tpu.loader import memmap as mm
    if restored is None:
        prng.seed_all(29)
        loader = mm.MemmapImageLoader(
            data_path=out, minibatch_size=16, mean_normalize=False,
            emit="uint8", hflip=True, n_workers=2, prefetch=3)
    else:
        loader = restored
    loader.initialize(device=None)
    return DeviceFeed(loader, put=None, ahead=1)


def _consume(feed, n, snapshot_after=()):
    """`n` passes of the driver's loop; (batches, pickles taken in the
    snapshot window after the named passes)."""
    import pickle
    ld, got, blobs = feed.loader, [], {}
    for k in range(n):
        b = feed.next()
        got.append((b.seq, b.minibatch_class, b.last_minibatch,
                    b.epoch_ended, np.array(b.x), np.array(b.y),
                    np.array(b.w), ld.minibatch_class,
                    bool(ld.last_minibatch), bool(ld.epoch_ended)))
        if k in snapshot_after:
            blobs[k] = pickle.dumps((ld, prng.snapshot_registry()))
        feed.prefetch()
    return got, blobs


@pytest.mark.parametrize("after", [0, 1, 2, 3, 4])
def test_resume_near_the_epoch_boundary_is_exact(tmp_path, after,
                                                 monkeypatch):
    """A loader pickled in the snapshot window after batch `after` (the
    last three places of a 4-batch epoch hold lookahead into the next
    one; 3 is the rollover, 4 the next epoch's first place) delivers the
    remaining batches bit for bit as the uninterrupted run does, and the
    next epoch's order, held in the pickle, is not drawn again."""
    import pickle

    from veles_tpu.loader import memmap as mm
    rng = np.random.RandomState(4)
    out = mm.pack_arrays(
        str(tmp_path / "resume"),
        rng.randint(0, 256, (64, 6, 6, 3), dtype=np.uint8),
        (np.arange(64) % 4).astype(np.int64), [0, 16, 48], shard_mb=0.002)
    feed = _boundary_feed(out)
    try:
        whole, blobs = _consume(feed, 12, snapshot_after=(after,))
    finally:
        feed.stop()
    assert [b[0] for b in whole] == list(range(12))

    loader, registry = pickle.loads(blobs[after])
    prng.restore_registry(registry)
    assert loader._pending == {} and loader._cursor == (after + 1) % 4
    # the fill at place 2 looked ahead to place 5, epoch 1's first TRAIN
    # batch (place 4 is its validation batch: no order needed)
    held = sorted(loader._orders)
    assert held == ([1] if after == 2 else [])
    drawn = []
    real = np.random.RandomState
    monkeypatch.setattr(
        np.random, "RandomState",
        lambda seed=None: drawn.append(seed) or real(seed))
    feed = _boundary_feed(out, restored=loader)
    try:
        rest, _ = _consume(feed, 11 - after)
    finally:
        feed.stop()
    for a, b in zip(whole[after + 1:], rest, strict=True):
        assert a[:4] == b[:4] and a[7:] == b[7:]
        for u, v in zip(a[4:7], b[4:7]):
            np.testing.assert_array_equal(u, v)
    epochs_drawn = [int(s[1]) for s in drawn]
    assert len(set(epochs_drawn)) == len(epochs_drawn)    # each once
    assert not set(epochs_drawn) & set(held) and 0 not in epochs_drawn


def test_feed_ahead_clamped_when_snapshotting(tmp_path):
    """feed_ahead >= 2 would leave pending batches across the snapshot
    window (a restore would skip them): with a live snapshotter the run
    clamps lookahead to 1 (review finding)."""
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    prng.seed_all(61)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(6,), n_validation=20, n_train=40,
        minibatch_size=20)
    wf = StandardWorkflow(
        layers=[{"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 2, "fail_iterations": 50},
        snapshot_config={"directory": str(tmp_path), "prefix": "clamp"},
        name="ClampWF")
    wf.run_fused(feed_ahead=4)
    assert wf.device_feed.ahead == 1              # clamped
    assert wf.decision.epoch_number == 2

    # without a snapshotter, deeper lookahead is honored
    prng.seed_all(61)
    loader2 = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(6,), n_validation=20, n_train=40,
        minibatch_size=20)
    wf2 = StandardWorkflow(
        layers=[{"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader2, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 2, "fail_iterations": 50},
        name="NoSnapWF")
    wf2.run_fused(feed_ahead=3)
    assert wf2.device_feed.ahead == 3


def test_explicit_input_normalize_layer_skips_negotiation(tmp_path):
    """Graphs that already carry an input_normalize layer keep their
    own on-device normalize — the negotiation
    must not stack a second prologue on top."""
    from veles_tpu.loader import memmap as mm
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    rng = np.random.RandomState(6)
    data = rng.randint(0, 256, (48, 4, 4, 3), dtype=np.uint8)
    labels = (np.arange(48) % 2).astype(np.int64)
    out = mm.pack_arrays(str(tmp_path / "layer"), data, labels,
                         [0, 16, 32], shard_mb=0.01)
    prng.seed_all(41)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                  emit="uint8", mean_normalize=False)
    wf = StandardWorkflow(
        layers=[{"type": "input_normalize"},
                {"type": "softmax", "output_sample_shape": 2,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=2,
        decision_config={"max_epochs": 1}, name="LayerWF")
    assert wf._wire_spec("auto") is None
    wf.run_fused()
    assert wf.feed_stats["uint8_wire"] is True   # wire stayed raw bytes


def test_clean_stop_releases_produce_threads(tmp_path):
    """stop() drains the queue and releases the loader's prefetch pool
    (the conftest leaked-thread check fails the suite otherwise)."""
    import threading

    from veles_tpu.loader import memmap as mm

    rng = np.random.RandomState(7)
    data = rng.randint(0, 256, (64, 4, 4, 3), dtype=np.uint8)
    out = mm.pack_arrays(str(tmp_path / "stop"), data,
                         (np.arange(64) % 4).astype(np.int64),
                         [0, 0, 64], shard_mb=0.01)
    prng.seed_all(17)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                  n_workers=2, prefetch=2)
    loader.initialize(device=None)
    feed = DeviceFeed(loader, put=None, ahead=2)
    feed.next()
    feed.prefetch()
    assert any("-produce" in t.name for t in threading.enumerate())
    feed.stop()
    # loader carries the final counters for loader_throughput() et al.
    assert loader.feed_stats["batches"] >= 3
    stats = mm.loader_throughput(loader, n_batches=2)
    assert stats["feed"]["batches"] >= 3


def test_multihost_fallback_is_host_handoff(monkeypatch, eight_devices):
    """A mesh spanning processes cannot take a local device_put: the
    feed degrades to host handoff (the jit's uniform-host-input path)."""
    import jax
    from veles_tpu.parallel import mesh as mesh_mod

    m = mesh_mod.make_mesh(jax.devices(), data=8)
    monkeypatch.setattr(mesh_mod, "is_multihost", lambda mm_: True)

    class StubStep:
        mesh = m

        def input_put_specs(self):
            raise AssertionError("must not be consulted on multihost")

    assert make_batch_put(StubStep()) is None
    loader = make_loader()
    feed = DeviceFeed.for_step(loader, StubStep())
    assert not feed.sharded_put
    b = feed.next()
    assert isinstance(b.x, np.ndarray)      # host arrays pass through


def test_heartbeat_carries_feed_counters(tmp_path):
    """The supervisor-report plumbing: feed counters ride the heartbeat
    payload (minus the bulky per-epoch rows) and round-trip."""
    from veles_tpu.resilience.supervisor import (read_heartbeat,
                                                 write_heartbeat)
    hb = str(tmp_path / "hb.json")
    feed = {"batches": 12, "bytes_per_batch": 2592, "uint8_wire": True,
            "loader_block_s": 0.5, "epoch_log": [{"epoch": 1}]}
    write_heartbeat(hb, 3, feed=feed)
    got = read_heartbeat(hb)
    assert got["epoch"] == 3
    assert got["feed"]["uint8_wire"] is True
    assert "epoch_log" not in got["feed"]
    write_heartbeat(hb, 4)                  # feed omitted: stays absent
    assert "feed" not in read_heartbeat(hb)


def test_contract_bench_and_production_share_the_feed():
    """ISSUE 5 contract: the benchmark's `feed` traffic (the
    `alexnet.feed` cell) and the production loop (_run_with_step) build
    the SAME DeviceFeed the same way — no bespoke double-buffer loop
    remains anywhere."""
    import os

    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    driver = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "drivers", "train.py")
    with open(driver) as f:
        bench_src = f.read()
    run_src = inspect.getsource(StandardWorkflow._run_with_step)
    assert "DeviceFeed.for_step(self.loader, self.step" in bench_src
    assert "DeviceFeed.for_step(loader, step" in run_src
    # the bespoke transfer the feed replaced must not creep back in
    assert "jax.device_put(" not in run_src
    # and the serving warm path issues its probe through the same put
    from veles_tpu import serving
    assert "make_batch_put" in inspect.getsource(
        serving.InferenceServer._build)


def test_feed_ahead_cli_requires_fused_or_pp():
    """--feed-ahead on a granular run would be silently inert: the
    Launcher rejects it unless --fused/--pp/distributed consumes the
    feed (the --autotune precedent)."""
    from veles_tpu.launcher import Launcher
    with pytest.raises(SystemExit):
        Launcher(feed_ahead=2)
    assert Launcher(feed_ahead=2, fused=True).feed_ahead == 2
    assert Launcher(feed_ahead=1, pp=4).feed_ahead == 1
