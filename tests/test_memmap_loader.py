"""Packed memmap dataset format (SURVEY.md §2.7 ImageNet pipeline row):
pack -> manifest/shards on disk -> MemmapImageLoader round-trip, mean
normalization, sharding, prefetch overlap, and the throughput microbench
that proves the host pipeline outruns the device step rate."""

import json
import os

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.loader import memmap as mm


def make_packed(tmp_path, n=64, hw=8, n_valid=16, shard_mb=0.001):
    rng = np.random.RandomState(0)
    data = rng.randint(0, 256, (n, hw, hw, 3), dtype=np.uint8)
    labels = np.arange(n, dtype=np.int64) % 4
    mean = data.astype(np.float64).mean(axis=0) / 127.5 - 1.0
    out = mm.pack_arrays(str(tmp_path / "packed"), data, labels,
                         [0, n_valid, n - n_valid], shard_mb=shard_mb,
                         mean_image=mean.astype(np.float32))
    return out, data, labels


def test_pack_shards_and_manifest(tmp_path):
    out, data, labels = make_packed(tmp_path)
    with open(os.path.join(out, mm.MANIFEST)) as f:
        man = json.load(f)
    assert man["n_samples"] == 64
    assert sum(s["rows"] for s in man["shards"]) == 64
    assert len(man["shards"]) > 1          # tiny shard_mb -> truly sharded
    total = os.path.getsize(os.path.join(out, man["shards"][0]["file"]))
    assert total == man["shards"][0]["rows"] * 8 * 8 * 3


def test_memmap_loader_roundtrip_and_mean(tmp_path):
    out, data, labels = make_packed(tmp_path)
    prng.seed_all(5)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                  shuffle_train=False)
    loader.initialize(device=None)
    assert loader.class_lengths == [0, 16, 48]
    loader.run()                            # first validation batch
    x = loader.minibatch_data.mem
    idx = loader.minibatch_indices.mem
    expect = data[idx].astype(np.float32) / 127.5 - 1.0 - loader.mean_image
    np.testing.assert_allclose(x, expect, atol=1e-6)
    np.testing.assert_array_equal(loader.minibatch_labels.mem, labels[idx])
    # row gathers cross shard boundaries transparently
    assert len(loader._maps) > 1
    loader.stop()


def test_memmap_loader_trains(tmp_path):
    """End-to-end: a workflow trains from the packed format."""
    rng = np.random.RandomState(1)
    labels = (np.arange(96) % 3).astype(np.int64)
    protos = rng.randint(60, 200, (3, 6, 6, 3)).astype(np.float32)
    data = np.clip(protos[labels] + rng.randn(96, 6, 6, 3) * 10,
                   0, 255).astype(np.uint8)
    perm = rng.permutation(96)
    out = mm.pack_arrays(str(tmp_path / "p2"), data[perm], labels[perm],
                         [0, 24, 72], shard_mb=0.01)
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    prng.seed_all(11)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=24)
    wf = StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 3,
                 "weights_stddev": 0.05}],
        loader=loader, loss="softmax", n_classes=3,
        decision_config={"max_epochs": 6, "fail_iterations": 50},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        name="MemmapWF")
    wf.run_fused()
    assert wf.decision.best_validation_err < 10, \
        wf.decision.best_validation_err
    loader.stop()


def test_memmap_loader_pickles_and_restores(tmp_path):
    import pickle
    out, data, labels = make_packed(tmp_path)
    prng.seed_all(5)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16)
    loader.initialize(device=None)
    loader.run()
    blob = pickle.dumps(loader)
    loader.stop()
    restored = pickle.loads(blob)
    assert len(restored._maps) > 0          # memmaps re-established
    restored.run()
    assert restored.minibatch_data.mem.shape == (16, 8, 8, 3)
    restored.stop()


def test_uint8_emit_with_input_normalize_trains(tmp_path):
    """The ImageNet-rate input path: RAW uint8 minibatches + on-device
    normalization via the paramless input_normalize layer — numerics
    match the host-normalized float path in granular AND fused modes."""
    rng = np.random.RandomState(2)
    labels = (np.arange(96) % 3).astype(np.int64)
    protos = rng.randint(60, 200, (3, 6, 6, 3)).astype(np.float32)
    data = np.clip(protos[labels] + rng.randn(96, 6, 6, 3) * 10,
                   0, 255).astype(np.uint8)
    perm = rng.permutation(96)
    mean = data.astype(np.float64).mean(0) / 127.5 - 1.0
    out = mm.pack_arrays(str(tmp_path / "p3"), data[perm], labels[perm],
                         [0, 24, 72], shard_mb=0.01,
                         mean_image=mean.astype(np.float32))
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    def build(emit):
        prng.seed_all(21)
        loader = mm.MemmapImageLoader(data_path=out, minibatch_size=24,
                                      emit=emit)
        head = ([{"type": "input_normalize"}] if emit == "uint8" else [])
        return StandardWorkflow(
            layers=head + [
                {"type": "all2all_tanh", "output_sample_shape": 16,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 3,
                 "weights_stddev": 0.05}],
            loader=loader, loss="softmax", n_classes=3,
            decision_config={"max_epochs": 3, "fail_iterations": 50},
            gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
            name=f"U8-{emit}")

    wf_u8 = build("uint8")
    wf_u8.run_fused()
    wf_f32 = build("float32")
    # pin the host-normalized float wire: this arm IS the golden
    # reference — letting run_fused auto-negotiate uint8 (ISSUE 5)
    # would compare the device path against itself
    wf_f32.run_fused(uint8_wire=False)
    # identical trajectories: on-device normalize == host normalize
    assert wf_u8.decision.best_validation_err == \
        wf_f32.decision.best_validation_err
    np.testing.assert_allclose(
        wf_u8.forwards[-1].weights.mem, wf_f32.forwards[-1].weights.mem,
        rtol=1e-4, atol=1e-5)

    # granular mode works too (uint8 input through the unit graph)
    wf_g = build("uint8")
    wf_g.initialize(device=None)
    wf_g.run()
    assert wf_g.decision.best_validation_err <= \
        wf_u8.decision.best_validation_err + 4
    for wf in (wf_u8, wf_f32, wf_g):
        wf.loader.stop()


def test_pack_image_dataset_streams_tree(tmp_path):
    """pack_image_dataset: image tree -> packed shards, streaming (tiny
    shard_mb forces multiple chunks), loadable and trainable."""
    from PIL import Image
    rng = np.random.RandomState(4)
    for ci, cname in enumerate(("apple", "pear")):
        d = tmp_path / "tree" / cname
        d.mkdir(parents=True)
        for i in range(12):
            arr = np.full((10, 10, 3), 60 + 120 * ci, np.uint8) + \
                rng.randint(0, 40, (10, 10, 3)).astype(np.uint8)
            Image.fromarray(arr).save(d / f"img_{i}.png")
    prng.seed_all(9)
    out = mm.pack_image_dataset(str(tmp_path / "tree"),
                                str(tmp_path / "packed_tree"),
                                size_hw=(8, 8), n_validation=8,
                                shard_mb=0.0005)
    with open(os.path.join(out, mm.MANIFEST)) as f:
        man = json.load(f)
    assert man["n_samples"] == 24
    assert man["class_lengths"] == [0, 8, 16]
    assert len(man["shards"]) > 1          # streamed in multiple chunks
    assert os.path.exists(os.path.join(out, "mean.npy"))
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=8)
    loader.initialize(device=None)
    loader.run()
    assert loader.minibatch_data.mem.shape == (8, 8, 8, 3)
    loader.stop()


def test_loader_throughput_microbench(tmp_path):
    """The packed-gather pipeline must comfortably beat a realistic
    device step rate at this toy geometry; with prefetch the measured
    fill cost per batch must be far below a serial re-gather."""
    out, _, _ = make_packed(tmp_path, n=256, hw=16, n_valid=0)
    prng.seed_all(6)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=32,
                                  n_workers=2, prefetch=3)
    loader.initialize(device=None)
    stats = mm.loader_throughput(loader, n_batches=40)
    loader.stop()
    assert stats["samples_per_sec"] > 2000, stats


def test_hflip_train_only_and_seeded(tmp_path):
    """hflip=True: TRAIN rows flip by a seeded per-(sample, epoch) coin
    (some flip, some don't, identically on a re-visit within the epoch);
    VALIDATION rows NEVER flip."""
    out, data, labels = make_packed(tmp_path)
    prng.seed_all(7)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                  shuffle_train=False, hflip=True,
                                  mean_normalize=False)
    loader.initialize(device=None)
    raw = data.astype(np.float32) / 127.5 - 1.0

    flipped_any = unflipped_any = 0
    # 1 validation + 3 train batches: the whole epoch (the coins belong
    # to the batch's epoch, which a re-produce names)
    for _ in range(4):
        loader.run()
        idx = loader.minibatch_indices.mem
        x = loader.minibatch_data.mem
        again = loader._produce(idx, 0)[0]  # re-produce: must match exactly
        np.testing.assert_array_equal(x, again)
        for row, i in zip(x, idx):
            if np.array_equal(row, raw[i]):
                unflipped_any += 1
                if i < 16:
                    continue
            elif np.array_equal(row, raw[i][:, ::-1]):
                assert i >= 16, f"validation row {i} was flipped"
                flipped_any += 1
            else:
                raise AssertionError(f"row {i} is neither raw nor flipped")
    assert flipped_any > 0 and unflipped_any > 0
    # across epochs the coin re-draws: at least one sample differs
    first_epoch = {}
    loader2 = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                   shuffle_train=False, hflip=True,
                                   mean_normalize=False)
    prng.seed_all(7)
    loader2.initialize(device=None)
    diffs = 0
    for epoch in range(2):
        for _ in range(4):
            loader2.run()
            for row, i in zip(loader2.minibatch_data.mem,
                              loader2.minibatch_indices.mem):
                if epoch == 0:
                    first_epoch[int(i)] = row.copy()
                elif not np.array_equal(first_epoch[int(i)], row):
                    diffs += 1
    assert diffs > 0
    loader.stop()
    loader2.stop()


def test_prefetch_master_indices_override(tmp_path):
    """apply_data_from_master-style calls pass indices that differ from
    the cursor schedule: fill_minibatch must produce THOSE indices, not
    hand back the prefetched future (round-3 advisor finding)."""
    out, data, labels = make_packed(tmp_path)
    prng.seed_all(9)
    loader = mm.MemmapImageLoader(data_path=out, minibatch_size=16,
                                  shuffle_train=False,
                                  mean_normalize=False)
    loader.initialize(device=None)
    loader.run()                       # warms the prefetch window
    master_idx = np.asarray([3, 5, 7, 9] * 4, np.int64)
    loader.fill_minibatch(master_idx)  # cursor has a pending future
    expect = data[master_idx].astype(np.float32) / 127.5 - 1.0
    np.testing.assert_allclose(loader.minibatch_data.mem, expect,
                               atol=1e-6)
    np.testing.assert_array_equal(loader.minibatch_labels.mem,
                                  labels[master_idx])
    loader.stop()


def test_native_gather_matches_numpy(tmp_path):
    """The C++ multithreaded gather (native/host_gather.cpp) is an exact
    twin of the numpy path: float32 + mean path, uint8 path, and the
    seeded hflip augmentation all agree bit-for-bit."""
    import shutil
    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")
    from veles_tpu import native_gather
    if not native_gather.available():
        pytest.skip("native gather did not build")
    out, data, labels = make_packed(tmp_path, n=96, hw=8, n_valid=24)

    def run_loader(native, emit, hflip):
        prng.seed_all(11)
        loader = mm.MemmapImageLoader(
            data_path=out, minibatch_size=16, shuffle_train=False,
            native=native, emit=emit, hflip=hflip)
        loader.initialize(device=None)
        got = []
        for _ in range(6):                 # a full epoch of 96/16
            loader.run()
            got.append((loader.minibatch_data.mem.copy(),
                        loader.minibatch_labels.mem.copy()))
        loader.stop()
        return got

    for emit in ("float32", "uint8"):
        for hflip in (False, True):
            a = run_loader("auto", emit, hflip)
            b = run_loader("off", emit, hflip)
            for (xa, ya), (xb, yb) in zip(a, b):
                np.testing.assert_array_equal(
                    xa, xb, err_msg=f"emit={emit} hflip={hflip}")
                np.testing.assert_array_equal(ya, yb)


# -- the lookahead runs on across the epoch boundary (ISSUE 25) ------------------


def boundary_loader(out, prefetch, seed=11, before_initialize=None, **kw):
    """16 validation + 48 train rows at batch 16: a 4-batch epoch of raw
    bytes."""
    prng.seed_all(seed)
    loader = mm.MemmapImageLoader(
        data_path=out, minibatch_size=16, mean_normalize=False,
        emit="uint8", n_workers=2, prefetch=prefetch, **kw)
    if before_initialize is not None:
        before_initialize(loader)
    loader.initialize(device=None)
    return loader


def delivered(loader):
    """Everything one run() hands the rest of the system."""
    return {"x": loader.minibatch_data.mem.copy(),
            "y": loader.minibatch_labels.mem.copy(),
            "valid": loader.minibatch_valid.mem.copy(),
            "indices": loader.minibatch_indices.mem.copy(),
            "meta": (loader.minibatch_class, bool(loader.last_minibatch),
                     bool(loader.epoch_ended), loader.epoch_number,
                     loader.batch_seq)}


def assert_same_batch(a, b):
    assert a["meta"] == b["meta"]
    for k in ("x", "y", "valid", "indices"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{k} {a['meta']}")


def test_every_fill_after_the_first_finds_a_future(tmp_path):
    """Over three epochs no fill but the run's first gathers on the
    caller's thread: the first batch of each later epoch was submitted
    by the last fills of the epoch before, and `_pending` never holds
    more than `prefetch` futures."""
    import threading
    out, _data, _labels = make_packed(tmp_path)
    made_on = {}

    def watch(loader):          # (the shape probe submits lookahead too)
        inner = loader._produce_one

        def spy(indices, seq, epoch):
            made_on.setdefault(seq, []).append(
                threading.current_thread().name)
            return inner(indices, seq, epoch)
        loader._produce_one = spy

    loader = boundary_loader(out, prefetch=3, before_initialize=watch)
    epochs = 3
    try:
        for k in range(4 * epochs):
            loader.run()
            assert loader.batch_seq == k
            assert len(loader._pending) <= 3
    finally:
        loader.stop()
    me = threading.current_thread().name
    # seq 0 twice on this thread: the first run() refills the probe
    assert made_on[0] == [me, me]
    for seq in range(1, 4 * epochs):
        assert len(made_on[seq]) == 1 and made_on[seq][0] != me, (
            seq, made_on[seq])
    # 12 runs + the shape probe asked; 13 fills were answered
    assert loader.lookahead_ready + loader.lookahead_waited == 13
    assert loader.lookahead_cross_epoch >= 3 * (epochs - 1)
    assert loader.lookahead_cross_epoch == 3 * epochs     # the last too


@pytest.mark.parametrize("case", ["shuffle_train", "balanced_train",
                                  "hflip"])
def test_batches_do_not_depend_on_prefetch(tmp_path, case):
    """Rows, labels, valid mask and the per-batch bookkeeping over three
    epochs (two boundaries) are the same for `prefetch` 0, 1 and 3, and
    the order is drawn without touching the shared `prng.get()` stream,
    so WHEN it is drawn cannot matter."""
    out, _data, labels = make_packed(tmp_path)
    kw = {"shuffle_train": dict(shuffle_train=True),
          "balanced_train": dict(balanced_train=True),
          "hflip": dict(shuffle_train=True, hflip=True)}[case]
    runs = {}
    for prefetch in (0, 1, 3):
        loader = boundary_loader(out, prefetch, **kw)
        shared = prng.get().state.get_state()[1].copy()
        try:
            got = []
            for _ in range(12):
                loader.run()
                got.append(delivered(loader))
        finally:
            loader.stop()
        np.testing.assert_array_equal(prng.get().state.get_state()[1],
                                      shared)
        runs[prefetch] = got
    for prefetch in (1, 3):
        for a, b in zip(runs[0], runs[prefetch]):
            assert_same_batch(a, b)
    train = [np.concatenate([b["indices"] for b in runs[0][e + 1:e + 4]])
             for e in (0, 4, 8)]
    # every epoch has an order of its own
    assert not np.array_equal(train[0], train[1])
    assert not np.array_equal(train[1], train[2])
    if case != "balanced_train":
        for order in train:         # each a permutation of the train set
            np.testing.assert_array_equal(np.sort(order),
                                          np.arange(16, 64))
    if case == "hflip":             # the coins are the batch's epoch's
        flips = [sum(not np.array_equal(b["x"][r], _data[i])
                     for b in runs[0][e + 1:e + 4]
                     for r, i in enumerate(b["indices"]))
                 for e in (0, 4, 8)]
        assert all(0 < f < 48 for f in flips), flips


def test_a_plain_loader_draws_the_same_order(tmp_path):
    """The order is the Loader's, not the produce pool's: a
    FullBatchLoader over the same rows and seed walks the same
    indices."""
    from veles_tpu.loader.fullbatch import FullBatchLoader
    out, data, labels = make_packed(tmp_path)
    loader = boundary_loader(out, prefetch=3)
    prng.seed_all(11)
    plain = FullBatchLoader(minibatch_size=16, on_device=False)
    plain.load_data = lambda: plain.bind_arrays(data, labels, 0, 16, 48)
    plain.initialize(device=None)
    try:
        for _ in range(9):
            loader.run()
            plain.run()
            np.testing.assert_array_equal(loader.minibatch_indices.mem,
                                          plain.minibatch_indices.mem)
            assert loader.epoch_number == plain.epoch_number
    finally:
        loader.stop()


@pytest.mark.parametrize("how", ["stop", "set_emit", "foreign_indices"])
def test_dropping_the_lookahead_drops_the_next_epochs_too(tmp_path, how):
    from concurrent.futures import wait
    out, _data, _labels = make_packed(tmp_path)
    loader = boundary_loader(out, prefetch=3)
    ref = boundary_loader(out, prefetch=0)
    try:
        for _ in range(3):
            loader.run()
            ref.run()
        # at the epoch's last place: two of the three belong to epoch 1
        assert sorted(loader._pending) == [3, 4, 5]
        assert loader.lookahead_cross_epoch == 2
        futures = list(loader._pending.values())
        if how == "stop":
            loader.stop()
        elif how == "set_emit":
            loader.set_emit("float32")
            ref.set_emit("float32")
        else:
            loader.fill_minibatch(np.arange(16, dtype=np.int64)[::-1])
        assert loader._pending == {}
        # cancelled, or made (`wait` never reports a future that `stop`'s
        # shutdown cancelled while it was still queued: no worker is left
        # to notify it, and the test failed whenever a loaded machine had
        # not started the third produce yet)
        assert not wait([f for f in futures if not f.cancelled()],
                        timeout=10).not_done
        for _ in range(3):          # over the boundary, from nothing
            loader.run()
            ref.run()
            assert_same_batch(delivered(loader), delivered(ref))
    finally:
        loader.stop()
        ref.stop()
