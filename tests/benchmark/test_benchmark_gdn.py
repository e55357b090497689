"""The two kernels of the chunked Gated DeltaNet's operand stage in the
benchmark (ISSUE 42): the stage's interface bytes and matrix operations
`benchmark/qwen3next_gdn_count.py` counts are the shapes the program
passes, the two rooflines read the kernels' own time and count their calls
from the trace's events, a call at the HBM peak's own time reads 100 and
no more, and on a program without the kernels the readers find nothing."""

import pytest

from bench_paths import ROOT

import test_benchmark_lrn_roofline as lrn_test
import test_benchmark_qwen3next as q3_test
from benchmark import manifest
from benchmark import qwen3next_gdn_count as G

REAL = "qwen3next_ep16.seq8k"
FWD, BWD = G.KERNELS


def cfg_of():
    return manifest.Manifest(ROOT).cell(REAL)["config_data"]


def test_the_counts_constants_are_the_programs():
    from veles_tpu.ops import linear_attention as la
    from veles_tpu.ops import pallas_kernels as pk
    assert G.INVERSE_BLOCK == la.INVERSE_BLOCK
    assert set(G.KERNELS) == {
        v for k, v in pk.KERNEL_NAMES.items() if k.startswith("_gdn_chunk")}
    # ten products at 64, as `_inverse_of` multiplies: three squarings and
    # three products inside a block of 16, two a doubling above it
    assert [G.inverse_products(c) for c in (16, 32, 64, 128)] \
        == [6, 8, 10, 12]


def test_a_call_covers_one_group_of_one_layer():
    """2 of the step's 4 sequences, 128 chunks of 64, 32 value heads."""
    cfg = cfg_of()
    assert G.chunk_heads(cfg) == 2 * 128 * 32 == 8192
    assert G.chunk_heads({**cfg, "scan_groups": 1}) == 16384
    # a sequence that is no multiple of the chunk is filled up
    assert G.chunk_heads({**cfg, "seq_len": 8193}) == 2 * 129 * 32


def test_the_interface_bytes_are_the_shapes_the_program_passes():
    """The arrays `linear_attention._chunk_operands` hands to and takes
    from the kernels for ONE chunk-head, by `jax.eval_shape`: nothing
    padded, nothing counted twice; the backward moves the inputs, the six
    cotangents and the five gradients."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops import linear_attention as la
    cfg = cfg_of()
    c, dk, dv = cfg["chunk"], cfg["linear_key_head_dim"], \
        cfg["linear_value_head_dim"]
    bf, f32 = jnp.bfloat16, jnp.float32
    ins = [jax.ShapeDtypeStruct((1, 1, c, d), bf) for d in (dk, dk, dv)] \
        + [jax.ShapeDtypeStruct((1, 1, c), f32)] * 2
    outs = jax.eval_shape(
        lambda *a: la._operands_xla(bf, *a)[:6], *ins)

    def nbytes(arrays):
        return sum(a.size * a.dtype.itemsize for a in arrays)
    assert [o.shape for o in outs] == [(1, 1, c, dk), (1, 1, c, dv),
                                       (1, 1, c, dk), (1, 1), (1, 1, c, c),
                                       (1, 1, c, dk)]
    assert G.interface_bytes(cfg, FWD) == nbytes(ins) + nbytes(outs) \
        == 123396
    assert G.interface_bytes(cfg, BWD) == 2 * nbytes(ins) + nbytes(outs) \
        == 173060
    with pytest.raises(KeyError):
        G.interface_bytes(cfg, "veles_gdn_chain")


def test_a_calls_work_against_a_hand_count():
    """ISSUE 42's arithmetic: ten 64^3 products and four of 64 x 64 x 128
    forward, 9.4 MFLOP a chunk-head; 1.01 GB a call, 1.23 ms at 819 GB/s
    against 0.39 at 197 TFLOP/s: HBM's time is the longer, both ways."""
    cfg = cfg_of()
    square, wide = 2 * 64 ** 3, 2 * 64 * 64 * 128
    assert G.matrix_flops(cfg, FWD) == 10 * square + 4 * wide == 9437184
    assert G.matrix_flops(cfg, BWD) == 12 * square + 10 * wide
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    fwd, bwd = (G.call_seconds_at_peak(cfg, k, peak) for k in G.KERNELS)
    assert fwd == pytest.approx(8192 * 123396 / 819e9) \
        == pytest.approx(1.2343e-3, rel=1e-4)
    assert bwd == pytest.approx(8192 * 173060 / 819e9) \
        == pytest.approx(1.7310e-3, rel=1e-4)
    products = 8192 * G.matrix_flops(cfg, FWD) / 197e12
    assert products == pytest.approx(0.3924e-3, rel=1e-3) and products < fwd
    # a chip whose memory were a hundred times faster: the products bound
    fast = {**peak, "hbm_bytes_per_s": 819e11}
    assert G.call_seconds_at_peak(cfg, FWD, fast) \
        == pytest.approx(8192 * 9437184 / 197e12)


def custom_call(kernel, number):
    return (f"%{kernel}{number} = (bf16[4096,128,128]{{2,1,0:T(8,128)(2,1)}}"
            ") custom-call(bf16[4096,128,128]{2,1,0:T(8,128)(2,1)} "
            "%bitcast.7)")


#: device 0, seconds. Four runs of the step; the two whole ones run from 10
#: to 30. The forward kernel runs at 4 sites a step (8 events of 1 s), the
#: backward at 2 (4 events of 2 s). The events of the clipped runs do not
#: count, nor does another family's kernel.
OPS = [(custom_call(FWD, f".{i % 4 + 1}"), lo, lo + 1)
       for i, lo in enumerate((10, 11, 12, 13, 20, 21, 22, 23))] \
    + [(custom_call(BWD, "" if i % 2 else ".1"), lo, lo + 2)
       for i, lo in enumerate((14, 16, 24, 26))] \
    + [(custom_call(FWD, ".1"), 8, 9), (custom_call(BWD, ".1"), 30.5, 31),
       (custom_call("veles_flash_fwd", ".1"), 18, 19),
       ("%convolution.3 = bf16[8]{0} convolution(bf16[8]{0} %p)", 19, 20)]
MODULES = [("jit_train_step(7)", 8, 10), ("jit_train_step(7)", 10, 20),
           ("jit_train_step(7)", 20, 30), ("jit_train_step(7)", 30, 31)]
PEAK = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e15}


@pytest.fixture
def ctx(tmp_path, monkeypatch):
    from veles_tpu import caches
    monkeypatch.setattr(caches, "cache_path", lambda *parts: str(tmp_path))
    monkeypatch.setattr(lrn_test, "MODULES", MODULES)
    G._kernel_events.cache_clear()
    man = manifest.Manifest(ROOT)
    return {"cell": man.cell(REAL), "counters": {}, "trace": {},
            "peaks": {"a chip": PEAK}, "device_kind": "a chip"}


def test_the_calls_are_counted_from_the_traces_events(tmp_path, ctx):
    """Calls and seconds a step from the events inside the two whole
    steps; the share is the calls' least time over their time: a step that
    ran the forward stage twice as often reads the same share."""
    man = manifest.Manifest(ROOT)
    assert G.kernel_calls(ctx, FWD) is None       # no trace on the disk yet
    lrn_test.write_xplane(tmp_path, OPS)
    G._kernel_events.cache_clear()
    assert G.kernel_calls(ctx, FWD) == (4.0, 4.0)
    assert G.kernel_calls(ctx, BWD) == (2.0, 4.0)
    cfg = ctx["cell"]["config_data"]
    for kernel, calls, seconds in ((FWD, 4, 4.0), (BWD, 2, 4.0)):
        got = man.layer_metric(kernel + "_roofline").read(ctx)
        assert got == pytest.approx(
            100 * calls * G.call_seconds_at_peak(cfg, kernel, PEAK)
            / seconds, rel=1e-9)
    assert man.layer_metric(FWD + "_roofline").read(ctx) \
        == pytest.approx(100 * 8192 * 123396 / 1e9 / 1.0, rel=1e-9)
    with pytest.raises(KeyError, match="not in peaks.json"):
        man.layer_metric(FWD + "_roofline").read(
            {**ctx, "device_kind": "TPU v9 imaginary"})


@pytest.mark.parametrize("bound", ["hbm", "mxu"])
@pytest.mark.parametrize("kernel", G.KERNELS)
def test_a_call_at_the_peaks_own_time_reads_100_and_no_more(
        tmp_path, ctx, monkeypatch, kernel, bound):
    """Whichever of the two peaks bounds the call, an event that lasts
    exactly the least time reads 100; one that lasts longer, less."""
    from veles_tpu import caches
    cfg = ctx["cell"]["config_data"]
    peak = PEAK if bound == "hbm" else {"hbm_bytes_per_s": 1e15,
                                        "bf16_flops_per_s": 1e11}
    least = G.call_seconds_at_peak(cfg, kernel, peak)
    assert 0.5 < least < 2.5
    for slower, want in ((1.0, 100.0), (4.0, 25.0)):
        out = tmp_path / f"slower{slower}"
        out.mkdir()
        lrn_test.write_xplane(out, [
            (custom_call(kernel, ".1"), 10, 10 + least * slower),
            (custom_call(kernel, ".2"), 20, 20 + least * slower)])
        monkeypatch.setattr(caches, "cache_path",
                            lambda *parts, _o=out: str(_o))
        G._kernel_events.cache_clear()
        got = G.gdn_kernel_roofline({**ctx, "peaks": {"a chip": peak}},
                                    kernel)
        assert got == pytest.approx(want, rel=1e-6) and got <= 100 + 1e-6


def test_the_readers_find_nothing_without_a_trace_or_the_kernels(
        tmp_path, ctx):
    """On the parent commit the files lie over a program whose step runs
    no such kernel, and an untraced run has no trace: None, no raise."""
    man = manifest.Manifest(ROOT)
    lrn_test.write_xplane(
        tmp_path, [row for row in OPS if "veles_gdn" not in row[0]])
    for kernel in G.KERNELS:
        read = man.layer_metric(kernel + "_roofline").read
        assert read(ctx) is None
        assert read({**ctx, "trace": None}) is None


def test_the_manifest_names_the_two_rooflines_in_the_cell():
    man = manifest.Manifest(ROOT)
    assert manifest.problems(man) == []
    entries = {m["name"]: m for m in man.data["per_layer"]}
    for kernel in G.KERNELS:
        m = entries[kernel + "_roofline"]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"], m["workloads"]) == (
            "%", "higher", "device_trace", "ops and kernels",
            "train_samples_per_s_per_chip", [REAL])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert man.layer_metric(kernel + "_roofline").__doc__
    # appended, nothing before them moved: the last two entries
    assert [m["name"] for m in man.data["per_layer"][-2:]] \
        == [k + "_roofline" for k in G.KERNELS]
    for what, text in q3_test._one_line_texts(man.data):
        assert 1 <= len(text) <= 200 and text.isprintable(), what
