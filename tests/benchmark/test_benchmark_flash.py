"""The three flash kernels of latent attention's core in the benchmark
(ISSUE 38): the tile sizes `benchmark/xing4_flash_count.py` counts by are
the program's, the visited pairs are what brute force counts, the three
rooflines read the kernels' own time and count their calls from the
trace's events, and on a program without the kernels the readers find
nothing."""

import pytest

from bench_paths import ROOT

import test_benchmark_lrn_roofline as lrn_test
from benchmark import keye2_ops_count, manifest
from benchmark import xing4_flash_count as F

REAL = "xing4_ep8.step"
KERNELS = ("veles_flash_fwd", "veles_flash_dq", "veles_flash_dkv")
#: operations a (head, query, key) pair: the products of 2 x 192 (keys)
#: and of 2 x 128 (values) a kernel forms
PER_PAIR = {"veles_flash_fwd": 384 + 256, "veles_flash_dq": 2 * 384 + 256,
            "veles_flash_dkv": 2 * 384 + 2 * 256}


def test_the_counts_tiles_are_the_programs():
    from veles_tpu.ops import pallas_kernels as pk
    assert F.FLASH_BLOCKS == (pk._FLASH_BLK_Q, pk._FLASH_BLK_K)
    assert set(F.FLASH_KERNEL_PRODUCTS) == {
        v for k, v in pk.KERNEL_NAMES.items() if k.startswith("_flash")}
    for seq in (128, 384, 2048, 4096, 12288):
        for blk in F.FLASH_BLOCKS:
            assert keye2_ops_count._fit(seq, blk) == pk.flash_fit_block(
                seq, blk)


@pytest.mark.parametrize("seq", [128, 1024, 2048, 4096, 4608, 16384])
def test_the_visited_pairs_by_brute_force(seq):
    bq, bk = (keye2_ops_count._fit(seq, b) for b in F.FLASH_BLOCKS)
    want = sum(bq * bk for i in range(seq // bq) for j in range(seq // bk)
               if j * bk <= i * bq + bq - 1)
    assert F.pairs_visited(seq) == want >= seq * (seq + 1) // 2
    if seq == 4096:
        assert want == 20 * 512 * 1024      # 20 of the 32 tiles


def test_a_calls_work_against_a_hand_count():
    """2 sequences x 4 heads x 20 tiles of 512 x 1,024: 53.7, 85.9 and
    107.4 GFLOP a call (ISSUE 38's arithmetic)."""
    cfg = manifest.Manifest(ROOT).cell(REAL)["config_data"]
    for kernel, per_pair in PER_PAIR.items():
        assert F.flash_call_flops(cfg, kernel, 2) == \
            2 * 4 * 20 * 512 * 1024 * per_pair
    assert [round(F.flash_call_flops(cfg, k, 2) / 1e9, 1) for k in KERNELS] \
        == [53.7, 85.9, 107.4]


def custom_call(kernel, number):
    return (f"%{kernel}{number} = (bf16[8,4096,128]{{2,1,0:T(8,128)(2,1)}}) "
            "custom-call(bf16[8,4096,192]{2,1,0:T(8,128)(2,1)} %fusion.7)")


#: device 0, seconds. Four runs of the step; the two whole ones run from 10
#: to 30. The forward kernel runs at 3 sites a step (6 events, 6 s), dQ at
#: 2 (4 events, 8 s); dK/dV not at all. The events of the clipped runs do
#: not count, nor does the other family's kernel.
OPS = [(custom_call("veles_flash_fwd", f".{i % 3 + 1}"), lo, lo + 1)
       for i, lo in enumerate((10, 11, 12, 20, 21, 22))] \
    + [(custom_call("veles_flash_dq", "" if i % 2 else ".1"), lo, lo + 2)
       for i, lo in enumerate((13, 15, 23, 25))] \
    + [(custom_call("veles_flash_fwd", ".1"), 8, 9),
       (custom_call("veles_flash_dq", ".1"), 30.5, 31),
       (custom_call("veles_dsa_attend_fwd", ".1"), 17, 18),
       ("%convolution.3 = bf16[8]{0} convolution(bf16[8]{0} %p)", 18, 20)]
MODULES = [("jit_train_step(7)", 8, 10), ("jit_train_step(7)", 10, 20),
           ("jit_train_step(7)", 20, 30), ("jit_train_step(7)", 30, 31)]


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    from veles_tpu import caches
    monkeypatch.setattr(caches, "cache_path", lambda *parts: str(tmp_path))
    # that file's writer of a device plane, over this file's four runs
    monkeypatch.setattr(lrn_test, "MODULES", MODULES)
    F._kernel_events.cache_clear()
    man = manifest.Manifest(ROOT)
    return {"cell": man.cell(REAL), "counters": {}, "trace": {},
            "peaks": {"a chip": {"bf16_flops_per_s": 1e12}},
            "device_kind": "a chip"}


def test_the_calls_are_counted_from_the_traces_events(tmp_path, ctx):
    """Calls and seconds a step from the events inside the two whole
    steps; the share is the calls' work over their time: a step that
    called the forward kernel twice as often (a forward recomputed in the
    backward pass) would read the same share, not twice it."""
    man = manifest.Manifest(ROOT)
    # no trace on the disk yet
    assert F.kernel_calls(ctx, "veles_flash_fwd") is None
    lrn_test.write_xplane(tmp_path, OPS)
    F._kernel_events.cache_clear()
    assert F.kernel_calls(ctx, "veles_flash_fwd") == (3.0, 3.0)
    assert F.kernel_calls(ctx, "veles_flash_dq") == (2.0, 4.0)
    assert F.kernel_calls(ctx, "veles_flash_dkv") is None
    cfg = ctx["cell"]["config_data"]
    for kernel, calls, seconds in (("veles_flash_fwd", 3, 3.0),
                                   ("veles_flash_dq", 2, 4.0)):
        got = man.layer_metric(kernel + "_roofline").read(ctx)
        assert got == pytest.approx(
            100 * calls * F.flash_call_flops(cfg, kernel, 2)
            / seconds / 1e12, rel=1e-9)
    assert man.layer_metric("veles_flash_fwd_roofline").read(ctx) \
        == pytest.approx(100 * 0.0536870912, rel=1e-9)
    assert man.layer_metric("veles_flash_dkv_roofline").read(ctx) is None
    with pytest.raises(KeyError, match="not in peaks.json"):
        man.layer_metric("veles_flash_fwd_roofline").read(
            {**ctx, "device_kind": "TPU v9 imaginary"})


def test_the_readers_find_nothing_without_a_trace_or_the_kernels(
        tmp_path, ctx):
    """On the parent commit the files lie over a program whose step runs
    no such kernel, and an untraced run has no trace: None, no raise."""
    man = manifest.Manifest(ROOT)
    lrn_test.write_xplane(
        tmp_path, [row for row in OPS if "veles_flash" not in row[0]])
    for kernel in KERNELS:
        read = man.layer_metric(kernel + "_roofline").read
        assert read(ctx) is None
        assert read({**ctx, "trace": None}) is None


def test_the_manifest_names_the_three_rooflines_in_the_cell():
    man = manifest.Manifest(ROOT)
    assert manifest.problems(man) == []
    entries = {m["name"]: m for m in man.data["per_layer"]}
    for kernel in KERNELS:
        m = entries[kernel + "_roofline"]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"], m["workloads"]) == (
            "%", "higher", "device_trace", "ops and kernels",
            "train_samples_per_s_per_chip", [REAL])
        assert man.layer_metric(kernel + "_roofline").__doc__
