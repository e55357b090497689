"""The trace reduction: on a hand-made trace with the arithmetic written
out, and on a cut-down copy of the first real trace of `alexnet.step`."""

import json
import os

import pytest

from bench_paths import FIXTURES

from benchmark import trace_reduce as T


def hand_trace():
    """Device 0, seconds. Three runs of the step program `jit_step`; the
    first and the last are cut by the trace and do not count. The whole
    one runs from 10 to 20:

        conv        10 .. 14
        all-reduce  13 .. 17     (overlaps conv for 1, fusion for 1)
        fusion      15 .. 16
        idle        17 .. 18
        all-gather  18 .. 19.5   (nothing beside it)
        update      19.5 .. 20

    busy = 10..17 + 18..20 = 9 of 10, idle share 0.1; collectives cover
    13..17 + 18..19.5 = 5.5, of which conv and fusion hide 13..14 and
    15..16, so 3.5 is exposed. The host sat in `bench.sync` from 16.5 to
    18.2, which covers the gap; `bench.dispatch` only touches it."""
    ops = [("%conv.1 = f32[8]", 10.0, 14.0),
           ("%all-reduce.3 = f32[8]", 13.0, 17.0),
           ("%fusion.7 = f32[8]", 15.0, 16.0),
           ("%all-gather.2 = f32[8]", 18.0, 19.5),
           ("%update.1 = f32[8]", 19.5, 20.0),
           ("%conv.1 = f32[8]", 8.0, 9.5),         # in the clipped first run
           ("%conv.1 = f32[8]", 20.0, 21.0)]       # in the clipped last run
    modules = [("jit_step(11)", 8.0, 10.0), ("jit_step(11)", 10.0, 20.0),
               ("jit_step(11)", 20.0, 21.0), ("jit_norms(5)", 1.0, 2.0)]
    host = [("bench.sync", 16.5, 18.2), ("bench.dispatch", 17.9, 18.4)]
    return {"devices": {0: {T.OPS_LINE: ops, T.MODULES_LINE: modules}},
            "host": host}


def test_interval_arithmetic():
    assert T.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert T.total([(0, 2.5), (3, 4)]) == 3.5
    assert T.subtract([(0, 10)], [(2, 3), (5, 11)]) == [(0, 2), (3, 5)]


def test_the_hand_made_trace_reduces_to_the_numbers_worked_out_by_hand():
    r = T.reduce_events(hand_trace(), n_devices=1)
    assert r["step_module"] == "jit_step" and r["steps"] == 1
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(9.0)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.1)
    assert r["step_device_s"] == pytest.approx(9.0)
    assert r["collective_s_per_step"] == pytest.approx(5.5)
    assert r["collective_exposed_s_per_step"] == pytest.approx(3.5)
    assert r["breakdown"]["device_ops"][0] == ["%conv.1 = f32[8]", 4.0]
    assert r["breakdown"]["idle_gaps"] == [["bench.sync", pytest.approx(1.0)]]


def test_a_trace_without_every_device_of_the_cell_is_an_error():
    with pytest.raises(RuntimeError, match="device planes"):
        T.reduce_events(hand_trace(), n_devices=4)


def test_the_recorded_alexnet_step_trace():
    """Worked out beside the fixture when it was cut (PR 23): the three
    whole steps run from 0.064594075 s to 0.266009442 s; a sweep over the
    930 operations inside gives 0.201360232 s busy."""
    with open(os.path.join(FIXTURES, "alexnet_step_trace.json")) as f:
        fx = json.load(f)
    ev = {"devices": {int(d): rows for d, rows in fx["devices"].items()},
          "host": fx["host"]}
    r = T.reduce_events(ev, n_devices=1)
    assert r["steps"] == 3 and r["step_module"] == "jit__lambda"
    assert r["window_s"] == pytest.approx(0.201415367, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.201360232, abs=1e-9)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(2.737e-4,
                                                            rel=1e-3)
    assert 1e3 * r["step_device_s"] == pytest.approx(67.120077, abs=1e-5)
    assert r["collective_exposed_s_per_step"] == 0.0
    assert r["breakdown"]["device_ops"][0][0].startswith("%fusion.171")
    assert len(r["breakdown"]["device_ops"]) == 10


def test_the_layer_metrics_read_the_reduced_trace():
    from benchmark.manifest import Manifest
    from bench_paths import ROOT
    man = Manifest(ROOT)
    ctx = {"trace": {"step_device_s": 0.0671,
                     "collective_exposed_s_per_step": 0.004},
           "counters": {"flops_per_step": 6.6e9 * 1024, "chips": 4,
                        "peak_bytes": 3e9, "compile_s": 2.0,
                        "window_s": 10.0, "feed_wait_s": 1.0,
                        "feed_block_ms": [1.0] * 90 + [50.0] * 10},
           "peaks": man.peaks(), "device_kind": "TPU v5 lite"}
    read = lambda name: man.layer_metric(name).read(ctx)  # noqa: E731
    assert read("step_device_ms") == pytest.approx(67.1)
    assert read("step_mxu_share") == pytest.approx(
        100 * 6.6e9 * 1024 / (0.0671 * 197e12))
    assert read("collective_exposed_ms") == pytest.approx(4.0)
    assert read("hbm_peak_gb") == 3.0 and read("compile_s") == 2.0
    assert read("feed_wait_share") == pytest.approx(10.0)
    assert read("feed_wait_ms_p95") == 50.0
    # nothing to read: the reader returns nothing
    ctx["trace"] = None
    ctx["counters"] = {"chips": 1, "peak_bytes": 1}
    for name in ("step_device_ms", "step_mxu_share", "collective_exposed_ms",
                 "feed_wait_share", "feed_wait_ms_p95"):
        assert read(name) is None
