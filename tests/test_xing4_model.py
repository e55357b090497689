"""The sparse-expert language model's units and ops against the plain
reference (`benchmark/xing4_reference.py`, which imports nothing of the
program) at a size the CPU holds: the whole step, the share test that
ties a chip's share to the uncut model, the dropless expert path, the
Sinkhorn projection and the yarn frequencies."""

import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import xing4_ops_count, xing4_reference, xing4_seeded  # noqa: E402
from veles_tpu.ops import attention as oa  # noqa: E402
from veles_tpu.ops import lm as ol  # noqa: E402
from veles_tpu.ops import moe as om  # noqa: E402
from veles_tpu.samples.xing4 import TINY, layer_table  # noqa: E402

OPT = {"learning_rate": 0.01, "gradient_moment": 0.9,
       "weights_decay": 0.0005, "learning_rate_bias": 2.0}


def tiny(**over):
    """A configuration as the benchmark states one: TINY with every head
    and expert held unless `over` says otherwise."""
    cfg = dict(TINY, name="t", dense_layers_held=1, batch_per_chip=2,
               compute_dtype="float32", master_dtype="float32",
               optimizer=dict(OPT), bias_update_speed=0.01,
               mtp_loss_weight=0.3, init_std=0.02, loss_chunk=8,
               held_experts_first=0,
               published={"n_routed_experts": TINY["n_routed_experts"]})
    cfg.update(over)
    cfg["n_params"] = xing4_ops_count.n_params(cfg)
    return cfg


#: the cases of the three steps against the reference: residual streams,
#: (first held expert, held experts). The tests that read no more of a
#: configuration than its step share the first one's session
#: (`shared_session`)
CASES = [(2, (2, 2)), (4, (2, 2)), (4, (0, 8))]


def case(streams, held):
    return tiny(hc_mult=streams, held_experts_first=held[0],
                n_routed_experts=held[1], num_attention_heads=2)


def session_of(cfg, seed=11, sabotage=None):
    from benchmark.manifest import Manifest
    cell = {"name": "t.step", "chips": 1, "config_data": cfg,
            "traffic_data": {"warmup_steps": 2, "steps_in_flight": 2,
                             "balance_last": 1, "balance_band": 0.02}}
    mod = Manifest(ROOT).session({"config_data": {"session": "xing4_lm"}})
    return mod, mod.TrainSession(cell, seed, time.perf_counter(),
                                 lambda _line: None, sabotage)


#: the sessions this module has built, by their configuration's JSON, each
#: with the step and workflow that `free_program` takes from it
_SESSIONS = {}


def shared_session(cfg, seed=11, fresh=False):
    """The ONE session this module builds of `cfg`, put back at `seed`'s
    first step (`TrainSession.start_from`): a program the file already has
    is not compiled a second time for a test that reads nothing else of
    it. `fresh` builds it anew, for a test that reads its TRACE, and keeps
    it for the tests after. (A step that was released, as `free_program`
    does, compiles again at its next call: the tests that leave theirs
    loaded stand first.)"""
    key = json.dumps(cfg, sort_keys=True)
    if fresh or key not in _SESSIONS:
        mod, ses = session_of(cfg, seed)
        _SESSIONS[key] = (mod, ses, ses.step, ses.wf)
    else:
        mod, ses, step, wf = _SESSIONS[key]
        ses.step, ses.wf = step, wf
        ses.start_from(seed)
    return mod, ses


def test_only_a_unit_that_says_so_gets_its_input_as_it_came():
    """Token ids reach the embedding as int32; any other first unit's
    integer input (a uint8 wire with no normaliser) is cast to the
    compute dtype as before (float32 here, as bfloat16: a cast of the ids
    to either would show)."""
    from veles_tpu.znicz import lm
    from veles_tpu.znicz.nn_units import Forward
    assert lm.TokenEmbedding.fused_integer_input is True
    assert not hasattr(Forward, "fused_integer_input")
    _mod, ses = shared_session(case(*CASES[0]), fresh=True)
    seen = []
    emb = ses.step.forwards[0]
    inner = emb.fused_apply
    emb.fused_apply = lambda p, x, **kw: (seen.append(x.dtype),
                                          inner(p, x, **kw))[1]
    try:
        ses.dispatch()
    finally:
        del emb.fused_apply
    assert seen and all(d == jnp.int32 for d in seen)


def test_a_released_step_compiles_again_and_gives_the_same_step():
    """`FusedTrainStep.release` unloads the compiled programs (the
    benchmark's reference needs the device after the window); the step
    object stays usable."""
    _mod, ses = shared_session(case(*CASES[0]))
    first = float(ses.dispatch()[0])
    assert ses.step._train_fn is not None
    ses.step.release()
    assert ses.step._train_fn is None and ses.step._eval_fn is None
    ses.start_from(ses.seed)
    assert float(ses.dispatch()[0]) == first


@pytest.mark.parametrize("streams,held", CASES)
def test_three_steps_of_the_program_follow_the_reference(streams, held):
    """Loss, every leaf's first gradient, the parameters and the selection
    bias after three steps: float32 against float32 at `highest` reads
    1e-6; the limits leave two orders."""
    cfg = case(streams, held)
    mod, ses = shared_session(cfg)
    prog = ses.first_steps()
    ses.free_program()
    prog, ref, _ = ses.readings(prog)
    rows = {r["name"]: r["value"] for r in xing4_reference.compare(
        cfg, prog, ref, dict.fromkeys(mod.LIMITS, 0.0))}
    assert rows["loss_rel_gap"] < 1e-5, rows
    assert rows["grad_rel_err"] < 1e-4, rows       # every leaf's gradient
    assert rows["grad_norm_gap"] < 1e-4 and rows["dparam_norm_gap"] < 1e-4
    assert rows["route_mismatch_share"] == 0 and rows["slots_dropped"] == 0
    assert rows["balance_bias_gap"] == 0
    # the bias moved on every expert layer, by the sign rule alone
    for b in prog["bias"]:
        steps = np.round(np.asarray(b) / cfg["bias_update_speed"])
        assert np.abs(steps).max() == 3 or np.abs(steps).max() >= 1
        assert np.allclose(steps * cfg["bias_update_speed"], b, atol=1e-7)
    assert len(prog["bias"]) == len(xing4_ops_count.expert_layers(cfg)) == 3


@pytest.mark.parametrize("vanishing", ["2.hca_a_post", "3.hcm_b_res"])
def test_a_scalar_leaf_that_vanishes_by_chance_is_read_with_its_fellows(
        vanishing):
    """`compare` reads a hyper-connection's scalars and biases as one
    leaf: one of them fifty times smaller than its fellows on some seed
    (and still above the median leaf, so no floor holds it) does not make
    the worst relative error, and one of them WRONG still shows."""
    from benchmark.reference import _worst_leaf
    pooled = xing4_reference.pooled
    small = [f"{i}.{hc}_{k}" for i in (2, 3) for hc in ("hca", "hcm")
             for k in ("a_pre", "a_post", "a_res", "b_pre", "b_post",
                       "b_res")]
    ref = {**dict.fromkeys(small, 1.0),
           **{f"{i}.attn_w_{k}": 0.01 for i in range(1, 9)
              for k in ("o", "dq", "uq", "dkv")}, "2.hca_p_res": 0.01}
    ref[vanishing] = 0.02
    diff = {n: 0.01 * (1.0 if n in small else r) for n, r in ref.items()}
    assert set(pooled(ref)) == (set(ref) - set(small)) | {
        "2.hca_ab", "2.hcm_ab", "3.hca_ab", "3.hcm_ab"}
    assert _worst_leaf(diff, ref) == (0.5, vanishing)
    assert _worst_leaf(pooled(diff), pooled(ref))[0] < 0.012
    wrong = dict(diff, **{vanishing: 1.0})
    assert _worst_leaf(pooled(wrong), pooled(ref))[0] > 0.3


def _layer_inputs(cfg, seed=5):
    key = jax.random.key(seed)
    params = xing4_seeded.make_params(cfg, key)
    h = jax.random.normal(jax.random.fold_in(key, 99),
                          (cfg["seq_len"], cfg["hidden_size"]), jnp.float32)
    return params, h


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """8 experts over 4 chips, 2 a chip: the parts the shares give, each
    through the PROGRAM's held-experts path, with the shared expert
    counted once, are the uncut reference's layer."""
    cfg = tiny()
    params, h = _layer_inputs(cfg)
    p = params[2]                                   # an expert block
    bias = 0.05 * jnp.arange(8, dtype=jnp.float32)  # a bias that matters
    prec = xing4_reference.Precision("float32")
    with jax.default_matmul_precision("highest"):
        whole, idx = xing4_reference.expert_layer(cfg, p, h, bias, 0, prec)
        hn = ol.rms_norm(h, p["moe_norm"], cfg["rms_norm_eps"])
        scores = jax.nn.sigmoid(hn @ p["moe_w_router"])
        pidx, picked = om.route_topk(scores, bias, 2)
        assert np.array_equal(np.sort(pidx, 1), np.sort(idx, 1))
        gates = cfg["routed_scaling_factor"] * picked \
            / (picked.sum(-1, keepdims=True) + 1e-20)
        total = ol.swiglu(hn, p["moe_shared_gate"], p["moe_shared_up"],
                          p["moe_shared_down"])
        for first in range(0, 8, 2):
            cut = slice(first, first + 2)
            part, dropped = om.held_experts_swiglu(
                hn, pidx, gates, p["moe_experts_gate"][cut],
                p["moe_experts_up"][cut], p["moe_experts_down"][cut],
                (first, 2))
            assert int(dropped) == 0
            total = total + part
    np.testing.assert_allclose(total, whole, atol=2e-6, rtol=1e-5)
    assert int(om.expert_loads(pidx, 8).sum()) == 2 * h.shape[0]


@pytest.mark.parametrize("query_block", [1024, 4])
def test_the_shares_of_the_heads_add_up_to_the_uncut_attention(
        query_block, monkeypatch):
    """(Whole, and in four blocks of queries, each against the keys up to
    its own end.) 4 heads over 4 chips: each share keeps W_DQ, W_DKV and the norms
    whole and its own head's columns of W_UQ, W_UKV and rows of W_O."""
    monkeypatch.setattr(oa, "LATENT_QUERY_BLOCK", query_block)
    cfg = tiny()
    params, h = _layer_inputs(cfg)
    p = params[1]
    nope, rope, vd = 8, 8, 8
    prec = xing4_reference.Precision("float32")
    rs = cfg["rope_scaling"]
    inv = ol.yarn_inv_freq(rope, cfg["rope_theta"], rs["factor"],
                           rs["original_max_position_embeddings"],
                           rs["beta_fast"], rs["beta_slow"])
    cos, sin = ol.rope_tables(cfg["seq_len"], inv)
    m = ol.yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    with jax.default_matmul_precision("highest"):
        whole = xing4_reference.attention(cfg, p, h, prec)
        hn = ol.rms_norm(h, p["attn_norm"], cfg["rms_norm_eps"])
        total = 0.0
        for head in range(4):
            share = {
                "w_dq": p["attn_w_dq"], "q_norm": p["attn_q_norm"],
                "w_dkv": p["attn_w_dkv"], "kv_norm": p["attn_kv_norm"],
                "w_uq": p["attn_w_uq"][:, head * 16:(head + 1) * 16],
                "w_ukv": p["attn_w_ukv"][:, head * 16:(head + 1) * 16],
                "w_o": p["attn_w_o"][head * vd:(head + 1) * vd]}
            total = total + oa.latent_attention(
                share, hn[None], n_heads=1, nope=nope, rope=rope, v_dim=vd,
                cos=cos, sin=sin, scale=(nope + rope) ** -0.5 * m * m)[0]
    np.testing.assert_allclose(total, whole, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("fast_rows", [None, 8, 48])
@pytest.mark.parametrize("second", ["absent", "held", "same_group"])
def test_no_slot_is_dropped_when_every_token_goes_to_one_held_expert(
        second, fast_rows):
    """A router forced to send every token to one held expert (and its
    second slot to an absent expert, to the other held one, or to experts
    on both sides) is computed whole, whether the held pairs fit the fast
    rows or spill past them: the whole buffer holds every pair there can
    be."""
    t, c, w = 64, 16, 8
    key = jax.random.key(1)
    h = jax.random.normal(key, (t, c), jnp.float32)
    wg, wu = (0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                      (2, c, w)) for i in (1, 2))
    wd = 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (2, w, c))
    other = {"absent": jnp.arange(t) % 4, "held": jnp.full((t,), 4),
             "same_group": jnp.where(jnp.arange(t) % 2 == 0, 4, 7)}[second]
    idx = jnp.stack([jnp.full((t,), 5), other], axis=1)
    gates = jnp.stack([jnp.full((t,), 0.7), jnp.full((t,), 0.3)], axis=1)

    def want_of(hh):
        on_first = 0.3 * (other == 4)[:, None] \
            * ol.swiglu(hh, wg[0], wu[0], wd[0])
        return 0.7 * ol.swiglu(hh, wg[1], wu[1], wd[1]) + on_first

    with jax.default_matmul_precision("highest"):
        y, dropped = jax.jit(
            lambda *a: om.held_experts_swiglu(*a, held=(4, 2),
                                              fast_rows=fast_rows))(
            h, idx, gates, wg, wu, wd)
        assert int(dropped) == 0
        np.testing.assert_allclose(y, want_of(h), atol=1e-5, rtol=1e-5)
        # and the gradient reaches the tokens and the experts' weights
        g = jax.grad(lambda hh, w: om.held_experts_swiglu(
            hh, idx, gates, w, wu, wd, (4, 2), fast_rows)[0].sum(),
            (0, 1))(h, wg)
        gw = jax.grad(lambda hh, w: (
            0.7 * ol.swiglu(hh, w[1], wu[1], wd[1])
            + 0.3 * (other == 4)[:, None]
            * ol.swiglu(hh, w[0], wu[0], wd[0])).sum(), (0, 1))(h, wg)
    np.testing.assert_allclose(g[0], gw[0], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(g[1], gw[1], atol=1e-4, rtol=1e-4)


def test_what_a_grouped_product_leaves_past_its_groups_reaches_nothing(
        monkeypatch):
    """On a TPU the rows of `lax.ragged_dot`'s result past the last group
    are whatever lay in memory (the first chip run of PR 32 trained to
    NaN after one step through the gate's gradient). Here they are made
    NaN on purpose: the layer's output and every gradient stay finite
    and equal to the clean ones."""
    t, c, w = 32, 16, 8
    key = jax.random.key(7)
    h = jax.random.normal(key, (t, c), jnp.float32)
    wg, wu = (0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                      (2, c, w)) for i in (1, 2))
    wd = 0.3 * jax.random.normal(jax.random.fold_in(key, 3), (2, w, c))
    idx = jnp.stack([jnp.arange(t) % 8, (jnp.arange(t) + 3) % 8], axis=1)
    gates = jax.random.uniform(jax.random.fold_in(key, 4), (t, 2))

    def run():
        return jax.value_and_grad(
            lambda *a: om.held_experts_swiglu(a[0], idx, a[1], a[2], a[3],
                                              a[4], (4, 2), 16)[0].sum(),
            argnums=(0, 1, 2, 3, 4))(h, gates, wg, wu, wd)

    clean = run()
    plain = om.lax.ragged_dot

    def dirty(lhs, rhs, sizes, **kw):
        out = plain(lhs, rhs, sizes, **kw)
        dead = (jnp.arange(out.shape[0]) >= sizes.sum())[:, None]
        return jnp.where(dead, jnp.nan, out)

    monkeypatch.setattr(om.lax, "ragged_dot", dirty)
    soiled = run()
    for a, b in zip(jax.tree.leaves(clean), jax.tree.leaves(soiled)):
        assert np.isfinite(np.asarray(b)).all()
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("residual", ["hc", "plain"])
def test_a_checkpointed_block_runs_its_held_experts_twice(residual):
    """Under the unit's own `fused_remat_policy` the compiled gradient of a
    checkpointed expert block holds TWO `conditional`s of the held experts
    (`_held_swiglu`: the first forward, the backward with its own
    recomputation), whatever the residual path. A hyper-connection's
    backward asks for its sub-layer's output: under a policy that does not
    save `ops.moe.MOE_SAVED` the checkpoint runs the held experts a third
    time for it; `x + y` asks for nothing, and XLA drops that copy by
    itself. The saved value is the value computed again: the gradients are
    those of the block without `jax.checkpoint`, bit for bit."""
    from veles_tpu.znicz import lm
    cfg = tiny(held_experts_first=2, n_routed_experts=2)
    spec = lm.BlockSpec(**{
        **{k: v for k, v in layer_table(cfg)[2].items() if k != "type"},
        "features": cfg["hidden_size"], "residual": residual,
        **({"streams": 1} if residual == "plain" else {})})
    batch, seq = 2, cfg["seq_len"]
    assert spec.fast_rows(batch * seq) < batch * seq * 2    # a `cond` each
    seeded = _layer_inputs(cfg)[0][2]       # an expert block's leaves
    p = {k: seeded[k] for k in spec.shapes()}
    key = jax.random.key(3)
    x = jax.random.normal(key, (batch, seq, spec.n * spec.c), jnp.float32)
    dy = jax.random.normal(jax.random.fold_in(key, 1), x.shape)

    def grad_of(block):     # with the value: a step reads its loss too
        return jax.jit(jax.value_and_grad(
            lambda p, x: (block(p, x, None)[0] * dy).sum(), argnums=(0, 1)))

    def conds(policy):
        f = grad_of(jax.checkpoint(spec.apply, policy=policy))
        text = f.lower(p, x).compile().as_text()
        return f, len(re.findall(r" conditional\(", text))

    unnamed = jax.checkpoint_policies.save_only_these_names(
        *oa.DSA_SAVED, *oa.FLASH_SAVED)
    assert conds(unnamed)[1] == (3 if residual == "hc" else 2)
    saved, n = conds(lm.HCBlock.fused_remat_policy)
    assert n == 2
    for a, b in zip(jax.tree.leaves(saved(p, x)),
                    jax.tree.leaves(grad_of(spec.apply)(p, x))):
        assert np.abs(np.asarray(a)).max() > 0
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n", [2, 4])
def test_sinkhorn_gives_a_doubly_stochastic_matrix_and_its_gradient(n):
    key = jax.random.key(n)
    # the model's regime: `b_res` (8 on the diagonal) plus a token's part
    logits = 0.5 * jax.random.normal(key, (n, n, 3), jnp.float32) \
        + 8.0 * jnp.eye(n)[:, :, None] * jnp.asarray([1.0, 0.0, 0.25])
    m = ol.sinkhorn(logits, 20, 1e-6)
    # the columns were normalised last; the rows are as close as twenty
    # iterations bring them: 1e-5 on moderate logits, less beside a
    # diagonal of e^8, which converges slowly (5e-3 holds)
    np.testing.assert_allclose(m.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(axis=1)[:, 1:], 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=5e-3)
    # the reference's layout (..., n, n) gives the same matrices
    ref = xing4_reference.sinkhorn(jnp.moveaxis(logits, -1, 0), 20, 1e-6)
    np.testing.assert_allclose(jnp.moveaxis(m, -1, 0), ref, atol=1e-6)
    # the backward is differentiated through: against central differences
    weight = jax.random.normal(jax.random.fold_in(key, 1), m.shape)
    f = lambda z: (ol.sinkhorn(z, 20, 1e-6) * weight).sum()  # noqa: E731
    with jax.enable_x64(True):
        z = np.asarray(logits, np.float64)
        w64 = np.asarray(weight, np.float64)
        f64 = lambda zz: float((ol.sinkhorn(jnp.asarray(zz), 20, 1e-6)  # noqa: E731
                                * w64).sum())
        grad = np.asarray(jax.grad(
            lambda zz: (ol.sinkhorn(zz, 20, 1e-6) * w64).sum())(
                jnp.asarray(z)))
        for at in [(0, 0, 0), (n - 1, 0, 1), (1, n - 1, 2)]:
            d = np.zeros_like(z)
            d[at] = 1e-5
            fd = (f64(z + d) - f64(z - d)) / 2e-5
            assert abs(fd - grad[at]) < 1e-6 * max(1.0, abs(fd)), at
    g32 = jax.grad(f)(logits)
    np.testing.assert_allclose(g32, grad, atol=1e-4, rtol=1e-3)


def test_yarn_frequencies_are_the_closed_form():
    """64-wide rotary part, factor 64 over 4096, beta 32 and 1, theta
    10000: the program's vectorised form against the reference's one
    dimension at a time; the ends keep and divide their frequency."""
    got = ol.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32, 1)
    want = xing4_reference.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(1.0)                  # fast: kept
    assert got[-1] == pytest.approx(10000.0 ** (-62 / 64) / 64, rel=1e-5)
    assert np.all(np.diff(got) < 0)
    assert ol.yarn_mscale(64.0, 1.0) == pytest.approx(
        0.1 * np.log(64.0) + 1.0)
    assert xing4_reference.yarn_mscale(64.0, 1.0) \
        == pytest.approx(1.4159, abs=1e-4)


def test_the_published_layer_table_is_the_counted_model():
    """The real configuration's layer table has the counted shapes, leaf
    for leaf, without a unit being built."""
    import json

    from veles_tpu.znicz.lm import BlockSpec
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_ep8.json")) as f:
        cfg = json.load(f)
    table = layer_table(cfg)
    assert [s["type"] for s in table] == ["token_embedding"] \
        + ["hc_block"] * 5 + ["lm_head"]
    counted = xing4_ops_count.shapes_of(cfg)
    skip = ("type", "streams")
    for spec, want in zip(table[1:-1], counted[1:-1]):
        got = BlockSpec(features=cfg["hidden_size"], streams=spec["streams"],
                        **{k: v for k, v in spec.items() if k not in skip})
        assert got.shapes() == want
    assert table[2]["held"] == (0, 8) and table[2]["n_experts"] == 64
    flops = xing4_ops_count.forward_flops_per_token(cfg)
    assert sum(flops.values()) == pytest.approx(0.785e9, rel=0.01)


def test_the_sample_trains_through_the_normal_entry(tmp_path):
    """`python -m veles_tpu veles_tpu/samples/xing4.py --fused`, tiny
    preset, CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu",
         os.path.join(ROOT, "veles_tpu", "samples", "xing4.py"), "--fused"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
