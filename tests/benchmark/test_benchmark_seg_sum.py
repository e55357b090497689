"""The held experts' combine in the benchmark (ISSUE 43): the bytes
`benchmark/moe_seg_sum_count.py` says a call of `veles_seg_sum` cannot
avoid are the three language-model cells' by hand, from a configuration's
file alone; the roofline reads the kernel's own time, counts its calls
from the trace's events and the held rows from the program's counters; a
call at HBM's own time reads 100 and no more; on a program without the
kernel the reader finds nothing; and `BENCHMARK.json` gained that one
entry."""

import json
import subprocess

import pytest

from bench_paths import ROOT

import test_benchmark_lrn_roofline as lrn_test
import test_benchmark_qwen3next as q3_test
from benchmark import manifest
from benchmark import moe_seg_sum_count as S

CELLS = ("xing4_ep8.step", "keye2_ep8.long16k", "qwen3next_ep16.seq8k")
METRIC = "veles_seg_sum_roofline"
#: a cell's tokens, held rows a layer at balance, and MB a call reads and
#: writes: the held rows and a row a token of the width in bfloat16
BY_HAND = {"xing4_ep8.step": (8192, 4096, 29, 59),
           "keye2_ep8.long16k": (16384, 16384, 67, 67),
           "qwen3next_ep16.seq8k": (32768, 20480, 84, 134)}
PARENT = "8313f18e818336dc503521495450746924128741"


def test_the_kernels_name_is_the_programs():
    from veles_tpu.ops import pallas_kernels as pk
    assert pk.KERNEL_NAMES["_seg_sum_kernel"] == S.KERNEL


@pytest.mark.parametrize("cell", CELLS)
def test_a_calls_bytes_by_hand_from_the_configurations_file_alone(cell):
    cfg = manifest.Manifest(ROOT).cell(cell)["config_data"]
    tokens, held, read_mb, written_mb = BY_HAND[cell]
    assert S.tokens(cfg) == tokens
    assert S.held_rows_at_balance(cfg) == held
    read, written = S.call_bytes(cfg, held)
    assert (round(read / 1e6), round(written / 1e6)) == (read_mb, written_mb)
    assert read == held * cfg["hidden_size"] * 2
    # half a millisecond at HBM's rate in the largest of the three
    peak = manifest.Manifest(ROOT).peaks()["TPU v5 lite"]
    assert S.call_seconds_at_peak(cfg, held, peak) \
        == pytest.approx((read + written) / 819e9)
    assert S.call_seconds_at_peak(cfg, held, peak) < 0.3e-3


def custom_call(kernel: str, number: str = "") -> str:
    return (f"%{kernel}{number} = bf16[32768,2048]{{1,0:T(8,128)(2,1)}} "
            "custom-call(s32[367]{0:T(512)} %gather.1, "
            "bf16[61440,2048]{1,0:T(8,128)(2,1)} %fusion.7)")


#: device 0, seconds. Four runs of the step; the two whole ones run from 10
#: to 30 and call the kernel at 3 sites a step (6 events of 1 s). The
#: events of the clipped runs do not count, nor does another kernel whose
#: name starts alike, nor the grouped products'.
OPS = [(custom_call(S.KERNEL, f".{i % 3 + 1}" if i % 3 else ""), lo, lo + 1)
       for i, lo in enumerate((10, 12, 14, 20, 22, 24))] \
    + [(custom_call(S.KERNEL, ".1"), 8, 9),
       (custom_call(S.KERNEL, ".2"), 30.5, 31),
       (custom_call("veles_seg_summary", ".1"), 16, 17),
       (custom_call("veles_tgmm", ".1"), 18, 19)]
MODULES = [("jit_train_step(7)", 8, 10), ("jit_train_step(7)", 10, 20),
           ("jit_train_step(7)", 20, 30), ("jit_train_step(7)", 30, 31)]
PEAK = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e15}


def publish(held: int, layers: int = 4, steps: int = 10) -> None:
    from veles_tpu.telemetry import metrics
    from veles_tpu.znicz import lm
    metrics.reset_default_registry()
    count = {"steps": steps, "slots": steps * 327680, "held": steps * held,
             "fullest": 0, "dropped": 0}
    lm.publish_moe_counters(
        {f"L{i:02d}": dict(count) for i in range(1, layers + 1)})


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    from veles_tpu import caches
    from veles_tpu.telemetry import metrics
    monkeypatch.setattr(caches, "cache_path", lambda *parts: str(tmp_path))
    monkeypatch.setattr(lrn_test, "MODULES", MODULES)
    S._kernel_events.cache_clear()
    man = manifest.Manifest(ROOT)
    yield {"cell": man.cell(CELLS[2]), "counters": {}, "trace": {},
           "peaks": {"a chip": PEAK}, "device_kind": "a chip"}
    metrics.reset_default_registry()


def test_the_calls_are_counted_from_the_traces_events(tmp_path, ctx, capsys):
    """Calls and seconds a step from the events inside the two whole
    steps, the held rows from the window's counters; the share is the
    calls' least time over their time, and the run's output says the
    count."""
    man = manifest.Manifest(ROOT)
    publish(20480)
    assert S.kernel_calls(ctx) is None            # no trace on the disk yet
    lrn_test.write_xplane(tmp_path, OPS)
    S._kernel_events.cache_clear()
    assert S.kernel_calls(ctx) == pytest.approx((3.0, 3.0))
    assert S.held_rows_counted() == 20480
    cfg = ctx["cell"]["config_data"]
    got = man.layer_metric(METRIC).read(ctx)
    assert got == pytest.approx(
        100 * 3 * (20480 + 32768) * 2048 * 2 / 1e9 / 3.0, rel=1e-6)
    assert got == pytest.approx(
        100 * 3 * S.call_seconds_at_peak(cfg, 20480, PEAK) / 3.0, rel=1e-6)
    assert "seg_sum: 3.00 calls a step" in capsys.readouterr().out
    # fewer rows counted held: less work in the same time
    publish(10240)
    assert man.layer_metric(METRIC).read(ctx) == pytest.approx(
        100 * (10240 + 32768) * 2048 * 2 / 1e9, rel=1e-6)
    with pytest.raises(KeyError, match="not in peaks.json"):
        man.layer_metric(METRIC).read(
            {**ctx, "device_kind": "TPU v9 imaginary"})


def test_a_call_at_hbms_own_time_reads_100_and_no_more(tmp_path, ctx,
                                                       monkeypatch):
    from veles_tpu import caches
    publish(20480)
    least = S.call_seconds_at_peak(ctx["cell"]["config_data"], 20480, PEAK)
    assert 0.1 < least < 0.5
    for slower, want in ((1.0, 100.0), (4.0, 25.0)):
        out = tmp_path / f"slower{slower}"
        out.mkdir()
        lrn_test.write_xplane(out, [
            (custom_call(S.KERNEL, ".1"), 10, 10 + least * slower),
            (custom_call(S.KERNEL, ".2"), 20, 20 + least * slower)])
        monkeypatch.setattr(caches, "cache_path",
                            lambda *parts, _o=out: str(_o))
        S._kernel_events.cache_clear()
        got = S.seg_sum_roofline(ctx)
        assert got == pytest.approx(want, rel=1e-6) and got <= 100 + 1e-6


@pytest.mark.parametrize("cell", CELLS)
def test_the_reader_finds_nothing_without_a_trace_or_the_kernel(
        tmp_path, ctx, cell):
    """On the parent commit the files lie over a program whose step
    gathers the slots: no event bears the name. An untraced run has no
    trace. None, no raise."""
    man = manifest.Manifest(ROOT)
    ctx = {**ctx, "cell": man.cell(cell)}
    read = man.layer_metric(METRIC).read
    publish(20480)
    lrn_test.write_xplane(
        tmp_path, [row for row in OPS if "%veles_seg_sum." not in row[0]
                   and "%veles_seg_sum " not in row[0]])
    assert read(ctx) is None
    assert read({**ctx, "trace": None}) is None


def test_the_reader_finds_nothing_without_the_programs_counters(
        tmp_path, ctx):
    """A program without an expert layer's counters: nothing to divide."""
    from veles_tpu.telemetry import metrics
    metrics.reset_default_registry()
    lrn_test.write_xplane(tmp_path, OPS)
    assert S.kernel_calls(ctx) == pytest.approx((3.0, 3.0))
    assert S.seg_sum_roofline(ctx) is None


def _entry(man):
    """The metric's place in `per_layer` and its entry. Later PRs append
    after it, so nothing here asks for it to be the last."""
    names = [m["name"] for m in man.data["per_layer"]]
    at = names.index(METRIC)
    return at, man.data["per_layer"][at]


def test_the_manifest_gained_the_one_entry():
    man = manifest.Manifest(ROOT)
    assert manifest.problems(man) == []
    _at, m = _entry(man)
    assert m == {"name": METRIC, "unit": "%", "better": "higher",
                 "source": "device_trace", "layer": "ops and kernels",
                 "moves": "train_samples_per_s_per_chip",
                 "workloads": list(CELLS)}
    assert man.layer_metric(METRIC).__doc__
    for cell in CELLS:
        assert METRIC in {e["name"] for e in man.metrics("per_layer", cell)}
    for cell in ("alexnet.step", "vgg16.step", "alexnet.feed", "vgg16.dp4"):
        assert METRIC not in {e["name"]
                              for e in man.metrics("per_layer", cell)}
    for what, text in q3_test._one_line_texts(man.data):
        assert 1 <= len(text) <= 200 and text.isprintable(), what


def test_the_entry_stands_after_what_the_parent_had():
    """Appended, nothing before it moved: it follows PR 42's two
    rooflines, which follow PR 41's last (the order that
    test_benchmark_gdn.py's stale `[-2:]` stood for), and everything up
    to it is the parent's file where git has that."""
    man = manifest.Manifest(ROOT)
    at, _m = _entry(man)
    names = [m["name"] for m in man.data["per_layer"]]
    assert names[at - 3:at] == [
        "step_gattn_ms", "veles_gdn_chunk_fwd_roofline",
        "veles_gdn_chunk_bwd_roofline"]
    try:
        before = json.loads(subprocess.run(
            ["git", "show", PARENT + ":BENCHMARK.json"], cwd=ROOT,
            capture_output=True, check=True, timeout=60).stdout)
    except (OSError, subprocess.SubprocessError):
        pytest.skip("no history to read the parent's BENCHMARK.json from")
    now = json.loads(json.dumps(man.data))
    assert at == len(before["per_layer"])
    assert now["per_layer"][:at] == before["per_layer"]
    # every other list begins with the parent's, entry for entry
    for key, was in before.items():
        if key == "per_layer":
            continue
        if isinstance(was, list):
            assert now[key][:len(was)] == was, key
        else:
            assert now[key] == was, key
