"""The indexer's two kernels in the benchmark (ISSUE 36): the tile sizes
and the calls `benchmark/keye2_index_count.py` counts by are the
program's, the visited pairs are what brute force counts, the two
rooflines read the kernels' own time, and on a program without the
kernels the readers find nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import ROOT

from benchmark import keye2_index_count as K
from benchmark import keye2_ops_count, keye2_scopes, manifest

REAL = "keye2_ep8.long16k"
KERNELS = ("veles_dsa_index_fwd", "veles_dsa_index_bwd")


def test_the_counts_tiles_are_the_programs():
    from veles_tpu.ops import pallas_kernels as pk
    assert K.INDEX_BLOCKS == (pk._DSA_INDEX_BLK_Q, pk._DSA_INDEX_BLK_K)
    assert set(K.INDEX_KERNEL_CALLS) == set(K.INDEX_KERNEL_PRODUCTS) == {
        v for k, v in pk.KERNEL_NAMES.items() if k.startswith("_dsa_index")}
    for seq in (128, 384, 4096, 12288, 16384):
        for blk in K.INDEX_BLOCKS:
            assert keye2_ops_count._fit(seq, blk) == pk.flash_fit_block(
                seq, blk)


@pytest.mark.parametrize("seq,bands,block", [
    (2048, 1, 256), (4096, 4, 256), (16384, 4, 256), (16384, 4, 1024),
    (1024, 2, 128)])
def test_the_visited_pairs_by_brute_force(seq, bands, block):
    per = seq // bands
    want = 0
    for b in range(bands):
        hi = (b + 1) * per
        bq = keye2_ops_count._fit(min(block, per), K.INDEX_BLOCKS[0])
        bk = keye2_ops_count._fit(hi, K.INDEX_BLOCKS[1])
        for i in range(per // bq):
            for j in range(hi // bk):
                if j * bk <= b * per + i * bq + bq - 1:
                    want += bq * bk
    assert K.pairs_visited(seq, bands, block) == want
    assert want >= keye2_ops_count.pairs_causal(seq)
    if (seq, bands) == (16384, 4):
        # at tiles of 512 keys; ISSUE 36 counts 142.6 M at 1,024
        assert want == 138412032


def test_the_calls_counted_are_the_calls_traced():
    """One attention sub-layer's value and gradient through the
    `pallas_flash` lowering, two bands: the forward kernel three times a
    block of a band (each in the body of its loop), the backward once."""
    from veles_tpu.ops import attention as oa
    c, h, kv, d, hi, di = 32, 2, 1, 128, 4, 8
    rng = np.random.default_rng(0)
    g = lambda *sh: jnp.asarray(rng.normal(size=sh) * 0.3, jnp.float32)  # noqa: E731
    p = dict(w_q=g(c, h * d), w_k=g(c, kv * d), w_v=g(c, kv * d),
             q_norm=1 + g(d), k_norm=1 + g(d), w_o=g(h * d, c),
             idx_w_q=g(c, hi * di), idx_w_k=g(c, di), idx_k_norm=1 + g(di),
             idx_k_bias=g(di), idx_w_w=g(c, hi))

    def f(p, x):
        y, ex = oa.indexed_attention(
            p, x, n_heads=h, kv_heads=kv, head_dim=d, index_heads=hi,
            index_dim=di, topk=24, rope_theta=1e4, query_block=64,
            key_bands=2, lowering="pallas_flash", interpret=True)
        return y.sum() + ex["index_loss"]
    calls = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                calls.append(e.params["name"])
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jax.make_jaxpr(jax.grad(f))(p, g(1, 256, c)).jaxpr)
    for kernel in KERNELS:
        assert calls.count(kernel) == 2 * K.INDEX_KERNEL_CALLS[kernel], calls


def test_a_roofline_is_the_kernels_work_over_its_time(monkeypatch):
    """At 16,384 tokens in four bands under 16 heads of 64, six layers:
    the forward 3 x 2 x 16 x 64 x 138.4 M a layer in 60 ms, the backward
    three products in 60 ms: 43.2 % of a v5e's 197 TFLOP/s each."""
    man = manifest.Manifest(ROOT)
    ctx = {"cell": man.cell(REAL), "counters": {}, "trace": {},
           "peaks": man.peaks(), "device_kind": "TPU v5 lite"}
    seconds = {"veles_dsa_index_fwd": 0.060, "veles_dsa_index_bwd": 0.060}
    monkeypatch.setattr(keye2_scopes, "kernel_seconds",
                        lambda ctx, kernel: seconds.get(kernel))
    work = 6 * 3 * 2 * 16 * 64 * 138412032
    for kernel in KERNELS:
        assert K.index_kernel_flops(ctx["cell"]["config_data"], kernel,
                                    1) == work
        got = man.layer_metric(kernel + "_roofline").read(ctx)
        assert got == pytest.approx(100 * work / 0.060 / 197e12, rel=1e-6)
        assert 43 < got < 44
    assert K.index_kernel_roofline(ctx, "veles_dsa_pmean") is None


def test_the_readers_find_nothing_without_a_trace_or_the_kernels(
        monkeypatch):
    """On the parent commit the files lie over a program whose step runs
    no such kernel, and an untraced run has no trace: None, no raise."""
    man = manifest.Manifest(ROOT)
    ctx = {"cell": man.cell(REAL), "counters": {}, "trace": None,
           "peaks": man.peaks(), "device_kind": "TPU v5 lite"}
    for kernel in KERNELS:
        assert man.layer_metric(kernel + "_roofline").read(ctx) is None
    monkeypatch.setattr(keye2_scopes, "kernel_seconds",
                        lambda ctx, kernel: None)
    ctx["trace"] = {}
    for kernel in KERNELS:
        assert man.layer_metric(kernel + "_roofline").read(ctx) is None


def test_the_manifest_names_both_rooflines_in_the_cell():
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
