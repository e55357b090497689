"""The indexed-attention language model's units and ops against the plain
reference (`benchmark/keye2_reference.py`, which imports nothing of the
program) at a size the CPU holds: the whole step, the selection alone,
the share test that ties a chip's share to the uncut model, the loss
terms a block hands to the head, the wide counters."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import keye2_ops_count, keye2_reference, keye2_seeded  # noqa: E402
from veles_tpu.ops import attention as oa  # noqa: E402
from veles_tpu.ops import lm as ol  # noqa: E402
from veles_tpu.ops import moe as om  # noqa: E402
from veles_tpu.ops import variants  # noqa: E402
from veles_tpu.samples.keye2 import TINY, layer_table  # noqa: E402

OPT = {"learning_rate": 0.01, "gradient_moment": 0.9,
       "weights_decay": 0.0005, "learning_rate_bias": 2.0}


def tiny(**over):
    """A configuration as the benchmark states one: TINY with every
    expert held unless `over` says otherwise."""
    cfg = dict(TINY, name="t", batch_per_chip=2, compute_dtype="float32",
               master_dtype="float32", optimizer=dict(OPT), init_std=0.2,
               loss_chunk=8, held_experts_first=0,
               published={"num_experts": TINY["num_experts"]})
    cfg.update(over)
    cfg["n_params"] = keye2_ops_count.n_params(cfg)
    return cfg


def session_of(cfg, seed=11, sabotage=None):
    from benchmark.manifest import Manifest
    cell = {"name": "t.step", "chips": 1, "config_data": cfg,
            "traffic_data": {"warmup_steps": 2, "steps_in_flight": 2}}
    mod = Manifest(ROOT).session({"config_data": {"session": "keye2_lm"}})
    return mod, mod.TrainSession(cell, seed, time.perf_counter(),
                                 lambda _line: None, sabotage)


#: the sessions this module has built, by their configuration's JSON, each
#: with the step and workflow that `free_program` takes from it
_SESSIONS = {}


def shared_session(cfg, seed=11):
    """The ONE session this module builds of `cfg`, put back at `seed`'s
    first step (`TrainSession.start_from`): a program the file already has
    is not compiled a second time for a test that reads nothing else of
    it. (A step that was released, as `free_program` does, compiles again
    at its next call: the test that leaves its step loaded stands
    first.)"""
    key = json.dumps(cfg, sort_keys=True)
    if key not in _SESSIONS:
        mod, ses = session_of(cfg, seed)
        _SESSIONS[key] = (mod, ses, ses.step, ses.wf)
    else:
        mod, ses, step, wf = _SESSIONS[key]
        ses.step, ses.wf = step, wf
        ses.start_from(seed)
    return mod, ses


#: the cases of the three steps against the reference: (first held expert,
#: held experts), queries a block, bands of keys. The first is the preset
#: as it stands, and the counters' test shares its session
CASES = [((0, 8), 8, 4), ((2, 2), 8, 1), ((4, 4), 16, 2), ((0, 8), 32, 4)]


def case(held, query_block, bands):
    return tiny(held_experts_first=held[0], num_experts=held[1],
                query_block=query_block, key_bands=bands)


def test_the_blocks_count_their_pairs_and_slots():
    from veles_tpu.znicz import lm
    cfg = case(*CASES[0])
    _mod, ses = shared_session(cfg)
    for _ in range(3):
        ses.dispatch()
    aux = jax.device_get(ses.state["aux"])
    s, k = cfg["seq_len"], cfg["sa_config"]["topk"]
    got = lm.dsa_counts(ses.step, aux)
    assert set(got) == {"L01", "L02"}
    for c in got.values():
        assert c["steps"] == 3
        assert c["causal"] == c["scored"] == 3 * 2 * s * (s + 1) // 2
        assert c["selected"] == 3 * 2 * keye2_ops_count.pairs_selected(s, k)
    moe = lm.moe_counts(ses.step, aux)
    assert all(c["slots"] == 3 * 2 * s * 2 and c["held"] == c["slots"]
               and c["dropped"] == 0 for c in moe.values())
    bits = np.unpackbits(aux[1]["selected"], axis=1)
    assert bits.shape == (2 * s, s)
    assert bits.sum() == 2 * keye2_ops_count.pairs_selected(s, k)
    assert not np.triu(bits[:s], 1).any()


@pytest.mark.parametrize("held,query_block,bands", CASES)
def test_three_steps_of_the_program_follow_the_reference(held, query_block,
                                                         bands):
    """The three terms of the loss, every leaf's first gradient and the
    parameters after three steps, the selected experts and the selected
    keys: float32 against float32 at `highest` reads 1e-6; the limits
    leave two orders. Weights at 0.2, so that the indexer, the router and
    the softmaxes are far from uniform. Whatever the tiling."""
    cfg = case(held, query_block, bands)
    mod, ses = shared_session(cfg)
    prog = ses.first_steps()
    ses.free_program()
    prog, ref, _ = ses.readings(prog)
    rows = {r["name"]: r["value"] for r in keye2_reference.compare(
        cfg, prog, ref, dict.fromkeys(mod.LIMITS, 0.0))}
    assert rows["loss_rel_gap"] < 1e-5, rows     # each of the three terms
    assert rows["grad_rel_err"] < 1e-4, rows     # every leaf's gradient
    assert rows["grad_norm_gap"] < 1e-4 and rows["dparam_norm_gap"] < 1e-4
    assert rows["route_mismatch_share"] == 0 and rows["slots_dropped"] == 0
    assert rows["select_mismatch_share"] == 0
    # every term is there and is no constant
    assert all(v > 0 for t in keye2_reference.TERMS for v in prog[t])
    assert len(set(prog["loss_index"])) == 3
    # the indexer and the router are trained by their own terms
    for name in ("1.attn_idx_w_q", "1.attn_idx_w_k", "1.attn_idx_k_bias",
                 "2.attn_idx_w_w", "2.moe_w_router"):
        assert ref["grad_norm"][name] > 1e-4, name


def test_three_steps_through_the_kernels_follow_the_reference():
    """The same through the `pallas_flash` lowering, interpreted: heads of
    128 and a sequence of 256, which the kernels take, in tiles of 128 so
    that tiles above the diagonal are passed over."""
    from veles_tpu.ops import pallas_kernels as pk
    from veles_tpu.ops import variants
    cfg = tiny(hidden_size=32, num_attention_heads=2, num_key_value_heads=1,
               head_dim=128, seq_len=256, batch_per_chip=1, query_block=64,
               num_hidden_layers=1, vocab_size=32,
               sa_config=dict(TINY["sa_config"], topk=24))
    blocks = pk._DSA_BLK_Q, pk._DSA_BLK_K
    pk._DSA_BLK_Q = pk._DSA_BLK_K = 128
    try:
        with variants.pallas_interpret():
            mod, ses = session_of(cfg)
            assert ses.step.variant_table()["dsa"] == "pallas_flash"
            prog = ses.first_steps()
            ses.free_program()
    finally:
        pk._DSA_BLK_Q, pk._DSA_BLK_K = blocks
    prog, ref, _ = ses.readings(prog)
    rows = {r["name"]: r["value"] for r in keye2_reference.compare(
        cfg, prog, ref, dict.fromkeys(mod.LIMITS, 0.0))}
    assert rows["loss_rel_gap"] < 1e-5, rows
    assert rows["grad_rel_err"] < 1e-4, rows
    assert rows["grad_norm_gap"] < 1e-4 and rows["dparam_norm_gap"] < 1e-4
    assert rows["select_mismatch_share"] == 0
    assert rows["route_mismatch_share"] == 0


@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (256, 256)])
def test_the_kernels_agree_with_the_xla_lowering(blocks):
    """Output, index loss, selection and every gradient of one attention
    sub-layer, 4 query heads on 2 key-value heads, float32, interpreted."""
    from veles_tpu.ops import pallas_kernels as pk
    c, h, kv, d, hi, di = 64, 4, 2, 128, 16, 8
    rng = np.random.default_rng(0)
    g = lambda *sh: jnp.asarray(rng.normal(size=sh) * 0.3, jnp.float32)  # noqa: E731
    p = dict(w_q=g(c, h * d), w_k=g(c, kv * d), w_v=g(c, kv * d),
             q_norm=1 + g(d), k_norm=1 + g(d), w_o=g(h * d, c),
             idx_w_q=g(c, hi * di), idx_w_k=g(c, di), idx_k_norm=1 + g(di),
             idx_k_bias=g(di), idx_w_w=g(c, hi))
    x = g(1, 256, c)
    kw = dict(n_heads=h, kv_heads=kv, head_dim=d, index_heads=hi,
              index_dim=di, topk=40, rope_theta=1e4, query_block=64,
              key_bands=2)
    saved = pk._DSA_BLK_Q, pk._DSA_BLK_K
    pk._DSA_BLK_Q, pk._DSA_BLK_K = blocks
    try:
        def run(lowering):
            def f(p, x):
                y, ex = oa.indexed_attention(p, x, lowering=lowering,
                                             interpret=True, **kw)
                return (y * jnp.cos(y)).sum() + 3 * ex["index_loss"], (y, ex)
            return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(p, x)
        (la, (ya, ea)), ga = run("xla")
        (lb, (yb, eb)), gb = run("pallas_flash")
    finally:
        pk._DSA_BLK_Q, pk._DSA_BLK_K = saved
    np.testing.assert_allclose(ya, yb, atol=2e-5)
    np.testing.assert_allclose(ea["index_loss"], eb["index_loss"], rtol=1e-5)
    assert np.array_equal(ea["selected"], eb["selected"])
    assert int(ea["pairs_selected"]) == int(eb["pairs_selected"])
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(
            np.abs(a).max() + 1))


def test_the_kernels_counts_are_the_kernels():
    """The benchmark's copy of the tile sizes and of the rule that fits
    them to a sequence is the program's; the pairs of the visited tiles by
    brute force."""
    from veles_tpu.ops import pallas_kernels as pk
    assert keye2_ops_count.DSA_BLOCKS == (pk._DSA_BLK_Q, pk._DSA_BLK_K)
    for seq in (128, 384, 1024, 4608, 16384):
        for blk in keye2_ops_count.DSA_BLOCKS:
            assert keye2_ops_count._fit(seq, blk) == pk.flash_fit_block(
                seq, blk)
    # (the main attention's kernels; the indexer's count themselves,
    # `benchmark/keye2_index_count.py`)
    assert set(keye2_ops_count.DSA_KERNEL_CALLS) == {
        v for k, v in pk.KERNEL_NAMES.items()
        if k.startswith("_dsa") and not k.startswith("_dsa_index")}
    for seq, bands in ((2048, 1), (4096, 4), (16384, 4)):
        per = seq // bands
        want = 0
        for b in range(bands):
            hi = (b + 1) * per
            bq, bk = pk.flash_fit_block(per, 512), pk.flash_fit_block(hi, 1024)
            for i in range(per // bq):
                for j in range(hi // bk):
                    if j * bk <= b * per + i * bq + bq - 1:
                        want += bq * bk
        assert keye2_ops_count.pairs_visited(seq, bands) == want
        assert want >= keye2_ops_count.pairs_causal(seq)


# (queries, keys, first position, index heads, width of one, the tiles asked
# for): tiles that divide; tiles that have to shrink to divide (384 keys
# under 256, 192 queries under 128: down to 128); a band that starts at
# `q0 > 0`, in several rows of tiles, whose tiles above the diagonal are
# passed over; the widths of the published indexer, one tile
INDEX_CASES = {
    "tiles_divide": (256, 256, 0, 16, 8, (128, 128)),
    "tiles_shrink": (384, 384, 0, 16, 8, (256, 256)),
    "band_from_q0": (256, 512, 256, 4, 16, (128, 128)),
    "band_wide_key_tiles": (256, 768, 256, 16, 8, (128, 256)),
    "published_widths": (128, 256, 128, 16, 64, (512, 1024)),
}


@pytest.mark.parametrize("case", sorted(INDEX_CASES))
def test_the_index_kernels_are_the_index_scores(case):
    """`index_scores_pallas` and its three gradients against
    `index_scores` and `jax.vjp(index_scores)`, float32, interpreted, at
    the causal pairs (a tile wholly above the diagonal is not computed: it
    reads 0 and takes no cotangent). Weights of either sign; a key of
    zeros, whose scores are exactly 0 and hand no gradient on."""
    from veles_tpu.ops import pallas_kernels as pk
    tq, keys, q0, hi, di, blocks = INDEX_CASES[case]
    assert pk.dsa_index_view(keys, hi, di)
    rng = np.random.default_rng(len(case))
    g = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)  # noqa: E731
    qi, w, ki, ct = g(tq, hi, di), g(tq, hi), g(keys, di), g(tq, keys)
    assert (np.asarray(w) < 0).any()
    ki = ki.at[3].set(0.0)
    causal = np.arange(keys)[None, :] <= (q0 + np.arange(tq))[:, None]
    ct = jnp.where(causal, ct, 0.0)
    saved = pk._DSA_INDEX_BLK_Q, pk._DSA_INDEX_BLK_K
    pk._DSA_INDEX_BLK_Q, pk._DSA_INDEX_BLK_K = blocks
    try:
        bq = pk.flash_fit_block(tq, blocks[0])
        bk = pk.flash_fit_block(keys, blocks[1])
        assert (case == "tiles_shrink") == ((bq, bk) != tuple(
            min(b, n) for b, n in zip(blocks, (tq, keys))))
        want, vjp = jax.vjp(oa.index_scores, qi, w, ki)
        got, vjp_k = jax.vjp(
            lambda *a: pk.index_scores_pallas(*a, q0, True), qi, w, ki)
        grads, grads_k = vjp(ct), vjp_k(ct)
    finally:
        pk._DSA_INDEX_BLK_Q, pk._DSA_INDEX_BLK_K = saved
    want, got = np.asarray(want), np.asarray(got)
    np.testing.assert_allclose(got[causal], want[causal], rtol=1e-5,
                               atol=1e-5)
    assert (want[:, 3] == 0).all() and (got[:, 3][causal[:, 3]] == 0).all()
    # a tile above the diagonal is written 0, never left as it was
    above = np.zeros_like(causal)
    for i in range(0, tq, bq):
        for j in range(0, keys, bk):
            above[i:i + bq, j:j + bk] = j > q0 + i + bq - 1
    assert above.any() == (case.startswith("band") or case in (
        "tiles_divide", "tiles_shrink")) and not got[above].any()
    for a, b, name in zip(grads, grads_k, ("dqi", "dw", "dki")):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-4 * float(
            np.abs(a).max()), err_msg=name)
    # relu's gradient at a score of exactly 0 is 0, as `jax.nn.relu`'s
    assert not np.asarray(grads_k[2])[3].any()
    assert not np.asarray(grads[2])[3].any()


def test_a_shape_off_the_index_kernels_view_traces_the_index_scores():
    """An index head of 12 is no whole sublane tile: inside the
    `pallas_flash` lowering such an indexer is scored by `index_scores`
    (no kernel of the indexer's in the trace), the main attention's
    kernels as before, and the result is the `xla` lowering's."""
    from veles_tpu.ops import pallas_kernels as pk
    assert not pk.dsa_index_view(256, 16, 12)
    assert not pk.dsa_index_view(200, 16, 8)
    assert pk.dsa_index_view(16384, 16, 64) and pk.dsa_index_view(256, 16, 8)
    c, h, kv, d, hi = 32, 2, 1, 128, 4

    def run(di, lowering):
        rng = np.random.default_rng(5)
        g = lambda *sh: jnp.asarray(rng.normal(size=sh) * 0.3,  # noqa: E731
                                    jnp.float32)
        p = dict(w_q=g(c, h * d), w_k=g(c, kv * d), w_v=g(c, kv * d),
                 q_norm=1 + g(d), k_norm=1 + g(d), w_o=g(h * d, c),
                 idx_w_q=g(c, hi * di), idx_w_k=g(c, di),
                 idx_k_norm=1 + g(di), idx_k_bias=g(di), idx_w_w=g(c, hi))
        x = g(1, 128, c)

        def f(p, x):
            y, ex = oa.indexed_attention(
                p, x, n_heads=h, kv_heads=kv, head_dim=d, index_heads=hi,
                index_dim=di, topk=24, rope_theta=1e4, query_block=64,
                key_bands=1, lowering=lowering, interpret=True)
            return y.sum() + 3 * ex["index_loss"]
        jaxpr = str(jax.make_jaxpr(jax.grad(f))(p, x))
        return jaxpr, jax.grad(f)(p, x)

    jaxpr, grads = run(12, "pallas_flash")
    assert "veles_dsa_attend_fwd" in jaxpr and "veles_dsa_pmean" in jaxpr
    assert "veles_dsa_index" not in jaxpr
    _, want = run(12, "xla")
    for name in want:
        np.testing.assert_allclose(grads[name], want[name], atol=2e-5 * float(
            np.abs(want[name]).max() + 1), err_msg=name)
    jaxpr, _ = run(16, "pallas_flash")
    assert "veles_dsa_index_fwd" in jaxpr and "veles_dsa_index_bwd" in jaxpr


def test_the_float8_control_fails_the_heads_gradient():
    cfg = tiny()
    key = jax.random.key(3)
    params0 = lambda: keye2_seeded.make_params(cfg, key)  # noqa: E731
    batches = [keye2_seeded.make_batch(cfg, 2, key, k) for k in range(3)]
    low = keye2_reference.reference_steps(
        cfg, params0(), batches, precision="float8", keep_first_grad=True)
    low["slots_dropped"] = 0
    ref = keye2_reference.reference_steps(
        cfg, params0(), batches,
        first_grad_of_program=low.pop("first_grad"))
    rows = {r["name"]: r["value"] for r in keye2_reference.compare(
        cfg, low, ref, dict.fromkeys(("x",), 0.0) | dict.fromkeys(
            ("loss_rel_gap", "grad_norm_gap", "grad_rel_err",
             "head_grad_rel_err", "dparam_norm_gap", "route_mismatch_share",
             "select_mismatch_share", "slots_dropped"), 0.0))}
    assert rows["head_grad_rel_err"] > 1e-2, rows
    assert rows["select_mismatch_share"] > 0, rows


# -- the selection alone ------------------------------------------------------------

def _scores(rows=24, keys=40, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(rows, keys)), jnp.float32)


def test_the_order_key_keeps_the_order_of_floats():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf])
    key = np.asarray(oa.float_order_key(x)).astype(np.int64)
    assert (np.diff(key) >= 0).all() and key.min() > 0
    assert key[3] != key[4] or True         # -0.0 and 0.0 may differ: fine
    assert (np.diff(np.delete(key, 3)) > 0).all()


@pytest.mark.parametrize("k", [1, 7, 12, 40])
@pytest.mark.parametrize("digit_bits", [1, 2, 4, 8])
def test_the_threshold_is_the_kth_largest_key(k, digit_bits):
    key = oa.float_order_key(_scores())
    got = oa.kth_largest_key(key, k, digit_bits)
    want = np.sort(np.asarray(key), axis=-1)[:, -k]
    assert np.array_equal(np.asarray(got), want)


def test_fewer_keys_than_topk_selects_them_all():
    index = _scores(8, 40)
    causal = jnp.arange(40)[None, :] <= jnp.arange(8)[:, None]
    mask, thr = oa.select_topk(index, causal, 12)
    assert np.array_equal(np.asarray(mask), np.asarray(causal))
    assert not np.asarray(thr).any()


def test_the_threshold_form_equals_top_k_on_distinct_scores():
    index = _scores(40, 40, seed=1)
    pos = jnp.arange(40)
    causal = pos[None, :] <= pos[:, None]
    mask, thr = oa.select_topk(index, causal, 12)
    _, idx = lax.top_k(jnp.where(causal, index, -jnp.inf), 12)
    want = np.zeros((40, 40), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    want &= np.asarray(causal)
    assert np.array_equal(np.asarray(mask), want)
    assert (np.asarray(mask).sum(axis=1) == np.minimum(np.arange(40) + 1,
                                                       12)).all()
    # given the threshold, the same selection and no search
    again, _ = oa.select_topk(index, causal, 12, thr)
    assert np.array_equal(np.asarray(again), want)


def test_a_score_above_the_diagonal_is_never_selected():
    """Poisoned: the largest scores, +inf and NaN among them, lie above
    the diagonal."""
    index = np.array(_scores(40, 40, seed=2))
    upper = np.triu(np.ones((40, 40), bool), 1)
    index[upper] = 1e9
    index[0, 5], index[3, 9] = np.inf, np.nan
    pos = jnp.arange(40)
    causal = pos[None, :] <= pos[:, None]
    mask, _ = oa.select_topk(jnp.asarray(index), causal, 12)
    mask = np.asarray(mask)
    assert not (mask & upper).any()
    assert (mask.sum(axis=1) == np.minimum(np.arange(40) + 1, 12)).all()


def test_the_tiling_holds_whole_blocks():
    assert oa.dsa_tiling(16384, 256, 4) == (256, 4)
    assert oa.dsa_tiling(32, 8, 4) == (8, 4)
    assert oa.dsa_tiling(48, 8, 4) == (8, 3)
    assert oa.dsa_tiling(30, 8, 4) == (30, 1)


# -- the held experts' grouped products as kernels -----------------------------------

def test_the_work_list_names_every_tile_a_group_touches():
    """Rows 0-699, none, 700-999, 1000-1002 of 1,536 in tiles of 512: the
    first group's two tiles, then the second tile once for each of the
    others, the empty one among them (its zeros have to be written); past
    the list's end the last item again."""
    from veles_tpu.ops import pallas_kernels as pk
    group, tile, lo, hi, n = pk.gmm_items(
        jnp.asarray([700, 0, 300, 3], jnp.int32), 1536, 512)
    assert int(n[0]) == 5 and group.shape == (3 + 4 - 1,)
    assert group.tolist() == [0, 0, 1, 2, 3, 3]
    assert tile.tolist() == [0, 1, 1, 1, 1, 1]
    assert lo.tolist() == [0, 700, 700, 1000]
    assert hi.tolist() == [700, 700, 1000, 1003]
    # more rows than the buffer holds end at the buffer's end
    _, tile, _, hi, n = pk.gmm_items(jnp.asarray([600, 600], jnp.int32),
                                     1024, 512)
    assert hi.tolist() == [600, 1024] and int(n[0]) == 3
    assert tile.tolist() == [0, 1, 1]


@pytest.mark.parametrize("rows,a,b,sizes,dtype", [
    (64, 128, 256, [10, 0, 30, 5], "float32"),
    (64, 256, 128, [0, 0, 64, 0], "float32"),
    (2048, 128, 128, [0, 600, 0, 1, 511, 512, 0, 100], "float32"),
    (2048, 256, 128, [0, 0, 0], "float32"),
    (1536, 128, 128, [700, 0, 300, 3], "bfloat16"),
], ids=lambda v: str(v).replace(" ", ""))
def test_the_grouped_kernels_agree_with_ragged_dot(rows, a, b, sizes,
                                                   dtype):
    """`veles_gmm` and `veles_tgmm`, interpreted, against `lax.ragged_dot`
    and its gradients: groups that share a tile, empty groups (their
    weights' gradient is ZERO, not what the block held), a buffer with
    nothing in it; the rows that are no group's are the caller's to
    mask."""
    from veles_tpu.ops import pallas_kernels as pk
    rng = np.random.default_rng(3)
    sizes = jnp.asarray(sizes, jnp.int32)
    x, w, dy = (jnp.asarray(rng.normal(size=sh), dtype) for sh in (
        (rows, a), (len(sizes), a, b), (rows, b)))
    tile = pk.gmm_view(rows, a, b, x.dtype.itemsize)
    assert tile == min(rows, 512)
    items = pk.gmm_items(sizes, rows, tile)
    live = (jnp.arange(rows) < sizes.sum())[:, None]
    (y, dx, dw), (y0, dx0, dw0) = ((
        lambda out, vjp: (out, *vjp(dy)))(*jax.vjp(
            lambda x, w: jnp.where(live, f(x, w), 0), x, w)) for f in (
        lambda x, w: pk.grouped_matmul(x, w, *items, True),
        lambda x, w: lax.ragged_dot(x, w, sizes)))
    tol = 1e-5 if dtype == "float32" else 2e-2
    for got, want in ((y, y0), (jnp.where(live, dx, 0),
                                jnp.where(live, dx0, 0)), (dw, dw0)):
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        np.testing.assert_allclose(got, want, atol=tol * (
            np.abs(want).max() + 1))


def test_the_sorted_buffer_is_sized_by_what_balances_the_router():
    """1.5 times the even share's rows under the selection-bias rule, 3
    times for softmax scores that nothing holds to the even load: 24,576
    rows of the 131,072 would send a tenth of this cell's layers' steps to
    the whole buffer."""
    from veles_tpu.znicz.lm import BlockSpec
    kw = dict(features=2048, n_heads=32, ffn="experts", width=768,
              n_experts=128, held=(0, 16), top_k=8)
    assert BlockSpec(scoring="softmax", shared=False, residual="plain",
                     attention="indexed", **kw).fast_rows(16384) == 49152
    assert BlockSpec(scoring="sigmoid_bias", **kw).fast_rows(16384) == 24576
    with pytest.raises(ValueError, match="grouped"):
        BlockSpec(grouped="megablox", **kw)


def test_the_kernels_have_no_view_of_a_width_off_the_lanes():
    from veles_tpu.ops import pallas_kernels as pk
    assert pk.gmm_view(24576, 2048, 768, 2) == 512
    assert pk.gmm_view(24576, 64, 32, 4) is None
    assert pk.gmm_view(24576, 8192, 8192, 2) is None    # no room for a matrix


def test_the_combines_gather_is_the_hand_sum_of_the_live_rows():
    """`_sum_rows` without a kernel: every token's slots gathered from the
    whole width of the buffer, the live ones summed."""
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    slot = jnp.asarray(rng.permutation(128).reshape(32, 4), jnp.int32)
    whole = om._sum_rows(y, None, slot, 40)
    want = np.zeros((32, 256), np.float32)
    for t, row in enumerate(np.asarray(slot)):
        want[t] = sum(np.asarray(y)[r] for r in row if r < 40)
    np.testing.assert_allclose(whole, want, atol=1e-6)


@pytest.mark.parametrize("fast_rows", [None, 64, 128])
def test_the_held_experts_through_the_kernels_are_the_held_experts(
        fast_rows):
    """Value and every gradient of `held_experts_swiglu` with the kernels
    (interpreted) against `lax.ragged_dot`, on the whole buffer, on the
    fast rows and past them."""
    rng = np.random.default_rng(1)
    t, k, c, h, e, held = 64, 4, 128, 256, 16, (4, 6)
    g = lambda *sh: jnp.asarray(rng.normal(size=sh) * 0.1, jnp.float32)  # noqa: E731
    x, ws = 10 * g(t, c), (g(held[1], c, h), g(held[1], c, h),
                           g(held[1], h, c))
    _, idx, gates = om.softmax_topk_gates(
        10 * g(t, e) + jnp.linspace(2, 0, e), k)
    assert fast_rows is None or int(((idx >= 4) & (idx < 10)).sum()) > 64

    def run(kernels):
        def loss(x, gates, *ws):
            y, dropped = om.held_experts_swiglu(
                x, idx, gates, *ws, held, fast_rows, "pallas", kernels)
            return (y * jnp.cos(jnp.arange(c))).sum(), dropped
        with variants.pallas_interpret():
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True)(x, gates, *ws)
    (la, da), ga = run(False)
    (lb, db), gb = run(True)
    assert int(da) == int(db) == 0
    np.testing.assert_allclose(la, lb, rtol=1e-5)
    for u, v in zip(ga, gb):
        np.testing.assert_allclose(u, v, atol=1e-5 * float(
            np.abs(u).max() + 1))


def test_three_steps_through_the_grouped_kernels_follow_the_reference(
        monkeypatch):
    """The whole step with `grouped: pallas` in the layer table, widths the
    kernels take (128 and 256), interpreted: the reference's losses,
    gradients and routes; off a TPU and unasked the same table traces
    `lax.ragged_dot`."""
    from veles_tpu.ops import pallas_kernels as pk
    from veles_tpu.ops import variants
    cfg = tiny(hidden_size=128, moe_intermediate_size=256, seq_len=32,
               batch_per_chip=1, num_hidden_layers=1, vocab_size=32,
               grouped="pallas")
    assert layer_table(cfg)[1]["grouped"] == "pallas"
    calls = []
    for name in ("gmm_pallas", "tgmm_pallas"):
        monkeypatch.setattr(pk, name, (lambda f, name: lambda *a, **kw: (
            calls.append(name), f(*a, **kw))[1])(getattr(pk, name), name))
    mod, ses = session_of(cfg)
    spec = ses.step.forwards[1].spec
    assert spec.grouped == "pallas" and not variants.kernels_ok(spec)
    ses.free_program()
    with variants.pallas_interpret():
        mod, ses = session_of(cfg)
        assert variants.kernels_ok(ses.step.forwards[1].spec)
        prog = ses.first_steps()
        ses.free_program()
    # forward, the forward `jax.checkpoint` traces again, and the
    # backward's product for the rows: three for one for the weights
    assert calls.count("gmm_pallas") == 3 * calls.count("tgmm_pallas") > 0
    prog, ref, _ = ses.readings(prog)
    rows = {r["name"]: r["value"] for r in keye2_reference.compare(
        cfg, prog, ref, dict.fromkeys(mod.LIMITS, 0.0))}
    assert rows["loss_rel_gap"] < 1e-5, rows
    assert rows["grad_rel_err"] < 1e-4, rows
    assert rows["grad_norm_gap"] < 1e-4 and rows["dparam_norm_gap"] < 1e-4
    assert rows["route_mismatch_share"] == 0 == rows["slots_dropped"]


# -- the share of a deployment ------------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """8 experts over 4 chips, 2 a chip: the parts the shares give, each
    through the PROGRAM's held-experts path, are the uncut reference's
    layer; attention and the router are whole on every chip and counted
    once; the balance loss is the router's, the same on every chip."""
    cfg = tiny(batch_per_chip=1)
    params = jax.tree.map(jnp.asarray, keye2_seeded.make_params(
        cfg, jax.random.key(5)))
    p = params[1]
    x = 0.7 * jax.random.normal(jax.random.key(6), (cfg["seq_len"],
                                                    cfg["hidden_size"]))
    prec = keye2_reference.Precision("float32")
    with jax.default_matmul_precision("highest"):
        whole, balance, idx = keye2_reference.expert_layer(cfg, p, x, 0,
                                                           prec)
        hn = ol.rms_norm(x, p["moe_norm"], cfg["rms_norm_eps"])
        r, pidx, gates = om.softmax_topk_gates(hn @ p["moe_w_router"], 2)
        assert np.array_equal(np.sort(pidx, 1), np.sort(idx, 1))
        np.testing.assert_allclose(gates.sum(axis=1), 1.0, rtol=1e-6)
        np.testing.assert_allclose(om.balance_loss(r, pidx), balance,
                                   rtol=1e-6)
        total = jnp.zeros_like(whole)
        for first in range(0, 8, 2):
            cut = slice(first, first + 2)
            part, dropped = om.held_experts_swiglu(
                hn, pidx, gates, p["moe_experts_gate"][cut],
                p["moe_experts_up"][cut], p["moe_experts_down"][cut],
                (first, 2))
            assert int(dropped) == 0
            total = total + part
            one = keye2_reference.expert_layer(
                cfg, {k: (v[cut] if k.startswith("moe_experts") else v)
                      for k, v in p.items()}, x, first, prec)[0]
            np.testing.assert_allclose(part, one, atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(total, whole, atol=2e-6, rtol=1e-5)
    assert int(om.expert_loads(pidx, 8).sum()) == 2 * x.shape[0]


def test_the_balance_loss_is_top_k_at_balance_and_of_each_sequence():
    r = jnp.full((16, 8), 1 / 8)
    idx = jnp.stack([jnp.arange(16) % 8, (jnp.arange(16) + 4) % 8], axis=1)
    assert float(om.balance_loss(r, idx)) == pytest.approx(2.0)
    # two sequences, each routed to its own half of the experts: the mean
    # of the sequences' losses, not the loss of their union
    r2 = jnp.concatenate([jnp.tile(jnp.asarray([[.25] * 4 + [0.] * 4]), (8, 1)),
                          jnp.tile(jnp.asarray([[0.] * 4 + [.25] * 4]), (8, 1))])
    idx2 = jnp.concatenate([jnp.tile(jnp.asarray([[0, 1]]), (8, 1)),
                            jnp.tile(jnp.asarray([[4, 5]]), (8, 1))])
    assert float(om.balance_loss(r2, idx2, batch=2)) == pytest.approx(4.0)
    assert float(om.balance_loss(r2, idx2, batch=1)) == pytest.approx(2.0)


# -- what a block hands to the head, and what it counts ------------------------------

def test_the_index_loss_trains_the_indexer_alone_and_nothing_else_does():
    """No gradient of the index loss reaches the main model, none of the
    language-model loss the indexer: read from the program's first
    gradient with either weight at 0."""
    grads = {}
    for name, over in (("index", {"router_aux_loss_coef": 0.0}),
                       ("lm", {"index_loss_weight": 0.0,
                               "router_aux_loss_coef": 0.0})):
        _mod, ses = session_of(tiny(**over))
        ses.dispatch()
        vel = jax.device_get(ses.state["vel"])
        p0 = jax.device_get(keye2_seeded.make_params(ses.cfg, ses.wkey))
        ses.vel1 = vel
        grads[name] = ses.first_grad(p0)
    for layer in (1, 2):
        for leaf, g in grads["lm"][layer].items():
            if "_idx_" in leaf:
                # (read back from the velocity: 0 but for its rounding)
                assert np.abs(g).max() < 1e-9, leaf
                assert np.abs(grads["index"][layer][leaf]).max() > 1e-6, leaf
            else:
                # the index loss adds nothing to any other leaf
                np.testing.assert_allclose(
                    grads["index"][layer][leaf], g, atol=1e-7, err_msg=leaf)


def test_the_pair_counters_hold_more_than_int32():
    from veles_tpu.znicz import lm
    acc = jnp.zeros((2,), jnp.int32)
    total = 0
    for n in (134225920, 31458304, 2 ** 31 - 1, 5):
        for _ in range(40):
            acc = lm._add_wide(acc, n if n == 5 else jnp.int32(n))
            total += n
    assert lm._wide(np.asarray(acc)) == total > 2 ** 36
    assert int(acc[1]) < 2 ** lm.WIDE


def test_a_second_kind_of_block_is_one_spec_not_two():
    """What a spec says: residual path, attention kind, scoring rule and
    shared expert, each apart."""
    from veles_tpu.znicz.lm import BlockSpec
    base = dict(features=64, n_heads=4, ffn="experts", width=32,
                n_experts=8, held=(0, 8), top_k=2)
    latent = dict(q_rank=24, kv_rank=16, nope=8, rope=8, v_dim=8)
    indexed = dict(attention="indexed", kv_heads=2, head_dim=16,
                   index_heads=4, index_dim=8, index_topk=8)
    a = BlockSpec(streams=2, **base, **latent)
    b = BlockSpec(residual="plain", scoring="softmax", shared=False,
                  **base, **indexed)
    c = BlockSpec(streams=2, scoring="softmax", **base, **indexed)
    assert any(k.startswith("hca_") for k in a.shapes())
    assert not any(k.startswith("hc") for k in b.shapes())
    assert "moe_shared_up" in a.shapes() and "moe_shared_up" not in b.shapes()
    assert "bias" in a.aux_shapes() and "bias" not in b.aux_shapes()
    assert {"attn_idx_w_q", "hcm_p_res", "moe_shared_up"} <= set(c.shapes())
    assert "hc" not in b.lowerings(1, 64)
    assert a.lowerings(1, 64)["hc"] == "xla"
    with pytest.raises(ValueError):
        BlockSpec(residual="plain", streams=2, **base, **indexed)
    with pytest.raises(ValueError):
        BlockSpec(attention="windowed", **base)


def test_the_published_layer_table_is_the_counted_model():
    """The real configuration's layer table has the counted shapes, leaf
    for leaf, without a unit being built; the operation counts are the
    issue's."""
    from veles_tpu.znicz.lm import BlockSpec
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye2_ep8.json")) as f:
        cfg = json.load(f)
    table = layer_table(cfg)
    assert [s["type"] for s in table] == ["token_embedding"] \
        + ["hc_block"] * 6 + ["lm_head"]
    counted = keye2_ops_count.shapes_of(cfg)
    for spec, want in zip(table[1:-1], counted[1:-1]):
        got = BlockSpec(features=cfg["hidden_size"],
                        **{k: v for k, v in spec.items() if k != "type"})
        assert got.shapes() == want
    assert table[1]["held"] == (0, 16) and table[1]["n_experts"] == 128
    assert sum(int(np.prod(s)) for s in counted[1].values()) == 96899456
    assert keye2_ops_count.n_params(cfg) == cfg["n_params"] == 659190016
    assert keye2_ops_count.pairs_causal(16384) == 134225920
    assert keye2_ops_count.pairs_selected(16384, 2048) == 31458304
    assert keye2_ops_count.pair_flops(cfg) == 4 * 32 * 128
    assert keye2_ops_count.train_flops_per_step(cfg, 1) \
        == pytest.approx(33.0e12, rel=0.01)


def test_the_sample_trains_through_the_normal_entry(tmp_path):
    """`python -m veles_tpu veles_tpu/samples/keye2.py --fused`, tiny
    preset, CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu",
         os.path.join(ROOT, "veles_tpu", "samples", "keye2.py"), "--fused",
         "-v"], cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "epoch 3" in out.stderr + out.stdout
