"""The hybrid linear-attention language model's units and ops against the
plain reference (`benchmark/qwen3next_reference.py`, which imports nothing
of the program) at a size the CPU holds: the whole step, the chunked scan
against the token recurrence, the full layer with and without the flash
lowering, the share test that ties a chip's share to the uncut model, what
a spec says."""

import json
import os
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

from benchmark import (qwen3next_ops_count, qwen3next_reference,  # noqa: E402
                       qwen3next_seeded)
from veles_tpu.ops import attention as oa  # noqa: E402
from veles_tpu.ops import linear_attention as la  # noqa: E402
from veles_tpu.ops import lm as ol  # noqa: E402
from veles_tpu.ops import moe as om  # noqa: E402
from veles_tpu.samples.qwen3next import TINY, layer_table  # noqa: E402

OPT = {"learning_rate": 0.01, "gradient_moment": 0.9,
       "weights_decay": 0.0005, "learning_rate_bias": 2.0}


def tiny(**over):
    """A configuration as the benchmark states one: TINY (4 of 16 experts
    held) unless `over` says otherwise. Weights at 0.05: at 0.2, where the
    other two models' tests stand, the convolution's taps and the
    L2-normalisations put first gradients of norm 18 behind a rate of
    0.01, and float32's last bits grow a thousandfold in two steps."""
    cfg = dict(TINY, name="t", batch_per_chip=2, compute_dtype="float32",
               master_dtype="float32", optimizer=dict(OPT), init_std=0.05,
               loss_chunk=8, held_experts_first=0)
    cfg.update(over)
    cfg["n_params"] = qwen3next_ops_count.n_params(cfg)
    return cfg


def session_of(cfg, seed=11, sabotage=None):
    from benchmark.manifest import Manifest
    cell = {"name": "t.step", "chips": 1, "config_data": cfg,
            "traffic_data": {"warmup_steps": 2, "steps_in_flight": 2}}
    mod = Manifest(ROOT).session({"config_data": {"session": "qwen3next_lm"}})
    return mod, mod.TrainSession(cell, seed, time.perf_counter(),
                                 lambda _line: None, sabotage)


#: the ONE session this module builds of a configuration (as
#: `tests/test_keye2_model.py::shared_session`): a program the file already
#: has is not compiled a second time
_SESSIONS = {}


def shared_session(cfg, seed=11):
    key = json.dumps(cfg, sort_keys=True)
    if key not in _SESSIONS:
        mod, ses = session_of(cfg, seed)
        _SESSIONS[key] = (mod, ses, ses.step, ses.wf)
    else:
        mod, ses, step, wf = _SESSIONS[key]
        ses.step, ses.wf = step, wf
        ses.start_from(seed)
    return mod, ses


def test_the_blocks_count_their_tokens_chunks_and_slots():
    from veles_tpu.znicz import lm
    cfg = tiny()
    _mod, ses = shared_session(cfg)
    for _ in range(3):
        ses.dispatch()
    aux = jax.device_get(ses.state["aux"])
    got = lm.gdn_counts(ses.step, aux)
    assert set(got) == {"L01", "L02", "L03"}
    for c in got.values():
        assert (c["steps"], c["tokens"], c["chunks"]) == (3, 3 * 2 * 32,
                                                          3 * 2 * 4)
        assert c["state_rms"] > 0 and c["decay_min"] < 0
    moe = lm.moe_counts(ses.step, aux)
    assert set(moe) == {"L01", "L02", "L03", "L04"}
    assert all(c["slots"] == 3 * 2 * 32 * 2 and 0 < c["held"] < c["slots"]
               and c["dropped"] == 0 for c in moe.values())


@pytest.mark.parametrize("over", [
    {}, {"seq_len": 24, "chunk": 16, "held_experts_first": 12}],
    ids=["preset", "ragged_chunk_last_four"])
def test_three_steps_of_the_program_follow_the_reference(over):
    """The two terms of the loss, every leaf's first gradient and the
    parameters after three steps, the selected experts: float32 against
    float32 at `highest` reads 1e-6; the limits leave an order or two.
    Whatever the chunk (24 tokens in chunks of 16: the last is filled
    up) and whichever experts are held (the first four of 16, the last
    four)."""
    cfg = tiny(**over)
    mod, ses = shared_session(cfg)
    prog = ses.first_steps()
    ses.free_program()
    prog, ref, _ = ses.readings(prog)
    rows = {r["name"]: r["value"] for r in qwen3next_reference.compare(
        cfg, prog, ref, dict.fromkeys(mod.LIMITS, 0.0))}
    assert rows["loss_rel_gap"] < 1e-5, rows     # each of the two terms
    assert rows["grad_rel_err"] < 1e-4, rows     # every leaf's gradient
    assert rows["gdn_out_grad_rel_err"] < 1e-4, rows
    assert rows["gdn_state_rel_err"] < 1e-5, rows   # the final states
    assert rows["grad_norm_gap"] < 1e-4 and rows["dparam_norm_gap"] < 1e-4
    assert rows["route_mismatch_share"] == 0 and rows["slots_dropped"] == 0
    assert all(v > 0 for t in qwen3next_reference.TERMS for v in prog[t])
    # every new leaf is trained: the decay, the taps, both gates, the
    # zero-centred norms
    for name in ("1.attn_a_log", "1.attn_dt_bias", "2.attn_conv",
                 "3.attn_w_ba", "3.attn_o_norm", "4.attn_q_norm",
                 "4.attn_w_q", "2.moe_shared_mix", "4.moe_w_router",
                 "5.final_norm"):
        assert ref["grad_norm"][name] > 1e-6, name


# -- the chunked scan against the token recurrence ----------------------------------

def _scan_inputs(seq, heads=3, dk=8, dv=16, n=2, seed=0, alike=0.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = la.l2_normalize(jax.random.normal(ks[0], (n, seq, heads, dk))) \
        * dk ** -0.5
    k = la.l2_normalize(jax.random.normal(ks[1], (n, seq, heads, dk)) + alike)
    v = jax.random.normal(ks[2], (n, seq, heads, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (n, seq, heads), minval=-5.0,
                                    maxval=2.5))
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[4], (n, seq, heads)))
    ct = jax.random.normal(ks[5], (n, seq, heads, dv))
    return (q, k, v, g, beta), ct


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("seq,chunk", [(32, 8), (64, 64), (40, 16),
                                       (24, 64), (96, 32)])
def test_the_chunked_scan_is_the_token_recurrence(seq, chunk):
    """Outputs, final state and the gradient by every operand, in chunks
    that divide the sequence and in chunks that do not (40 in 16s, 24 in
    one chunk of 32 filled up): float32 reads 1e-6. Keys that lie close
    to one another, where the chunk's inverse has most to do."""
    args, ct = _scan_inputs(seq, alike=2.0)

    def chunked(*a):
        o, state, _ = la.gated_delta_chunked(*a, chunk=chunk)
        return jnp.sum(o * ct), (o, state)

    def tokens(*a):
        o, state = jax.vmap(qwen3next_reference.delta_rule)(*a)
        return jnp.sum(o * ct), (o, state)

    with jax.default_matmul_precision("highest"):
        (_, (o, state)), grads = jax.value_and_grad(
            chunked, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        (_, (o_ref, state_ref)), grads_ref = jax.value_and_grad(
            tokens, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    assert _rel(o, o_ref) < 1e-5 and _rel(state, state_ref) < 1e-5
    for got, want in zip(grads, grads_ref):
        assert _rel(got, want) < 2e-5


def test_the_chunks_inverse_stands_where_powers_alone_would_not():
    """(I + A)^-1 of the worst a chunk can hold, every entry below the
    diagonal 1 (equal keys, beta 1, no decay): the inverse is 1 on the
    diagonal and -1 beneath it, which substitution over blocks of 16 gives
    to float32's last bits; its gradient is -T^T g T^T."""
    a = jnp.tril(jnp.ones((2, 64, 64)), -1)
    t = la.unit_lower_inverse(a)
    want = np.eye(64) - np.eye(64, k=-1)
    np.testing.assert_allclose(np.asarray(t[0]), want, atol=1e-3)
    rng = np.random.default_rng(0)
    b = jnp.asarray(np.tril(rng.normal(size=(3, 32, 32)) * 0.3, -1),
                    jnp.float32)
    np.testing.assert_allclose(
        la.unit_lower_inverse(b), np.linalg.inv(np.eye(32) + np.asarray(b)),
        rtol=2e-4, atol=2e-5)
    g = jnp.asarray(rng.normal(size=b.shape), jnp.float32)
    got = jax.grad(lambda x: jnp.sum(la.unit_lower_inverse(x) * g))(b)
    want = jax.grad(lambda x: jnp.sum(jnp.linalg.inv(
        jnp.eye(32) + x) * g))(b)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)
    with pytest.raises(ValueError):
        la.unit_lower_inverse(jnp.zeros((24, 24)))


def test_a_scan_held_in_bfloat16_is_another_scan():
    """`scan_dtype` is what the recurrence's gates, decays and state are
    held in: bfloat16 there moves the outputs by per cents where float32
    operands move them by nothing."""
    args, _ = _scan_inputs(64)
    with jax.default_matmul_precision("highest"):
        o, _, lowest = la.gated_delta_chunked(*args, chunk=16)
        o_low, _, _ = la.gated_delta_chunked(*args, chunk=16,
                                             scan_dtype=jnp.bfloat16)
        o_ref, _ = jax.vmap(qwen3next_reference.delta_rule)(*args)
    assert _rel(o, o_ref) < 1e-5 < 3e-3 < _rel(o_low, o_ref)
    assert float(lowest) < 0


def test_the_convolution_is_causal_and_tap_three_is_the_token():
    x = jax.random.normal(jax.random.key(1), (2, 9, 5))
    w = jnp.zeros((4, 5)).at[3].set(1.0)
    np.testing.assert_allclose(la.causal_conv_silu(x, w), jax.nn.silu(x),
                               rtol=1e-6)
    w = jnp.zeros((4, 5)).at[0].set(1.0)
    y = la.causal_conv_silu(x, w)
    assert not np.any(np.asarray(y[:, :3]))
    np.testing.assert_allclose(y[:, 3:], jax.nn.silu(x[:, :-3]), rtol=1e-6)


# -- the layers against the reference's ----------------------------------------------

def _layer_params(cfg, unit, seed=5):
    params = qwen3next_seeded.make_params(cfg, jax.random.key(seed))
    return {k: jnp.asarray(v) for k, v in params[unit].items()}


def test_the_linear_layer_is_the_references():
    cfg = tiny(batch_per_chip=1, init_std=0.3)
    p = _layer_params(cfg, 1)
    x = 0.7 * jax.random.normal(jax.random.key(6), (cfg["seq_len"], 64))
    prec = qwen3next_reference.Precision("float32")
    with jax.default_matmul_precision("highest"):
        want, state = qwen3next_reference.gated_delta_net(cfg, p, x, prec)
        h = ol.rms_norm(x, p["attn_norm"], 1e-6, offset=1.0)
        got, seen = la.gated_delta_net(
            {k[len("attn_"):]: v for k, v in p.items()
             if k.startswith("attn_")}, h[None], key_heads=2, value_heads=4,
            key_dim=16, value_dim=16, chunk=8)
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(seen["gdn_state_rms"],
                               jnp.sqrt(jnp.mean(state * state)), rtol=1e-4)


@pytest.mark.parametrize("flash", [False, True], ids=["xla", "flash"])
def test_the_full_layer_is_the_references(flash):
    """Gated attention, 4 query heads on 2 key-value heads, a quarter of
    the head turned: through the blocked XLA core, and through the
    `flash_attn` kernels (interpreted here) at a head the kernels take,
    each query head handed its own copy of its key-value head."""
    from veles_tpu.ops import variants
    over = {"head_dim": 128, "seq_len": 128} if flash else {}
    cfg = tiny(batch_per_chip=1, init_std=0.3, **over)
    d = qwen3next_ops_count.dims(cfg)
    p = _layer_params(cfg, 4)
    x = 0.7 * jax.random.normal(jax.random.key(7), (cfg["seq_len"], 64))
    prec = qwen3next_reference.Precision("float32")
    own = {k[len("attn_"):]: v for k, v in p.items() if k.startswith("attn_")}
    kw = dict(n_heads=4, kv_heads=2, head_dim=d["d"], rotary_dim=d["rotary"],
              rope_theta=cfg["rope_theta"], norm_offset=1.0)

    def program(own, x, apply):
        h = ol.rms_norm(x, own["norm"], 1e-6, offset=1.0)
        return oa.gated_attention(own, h[None], flash=apply, **kw)[0]

    def loss_of(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)))

    with jax.default_matmul_precision("highest"), \
            variants.pallas_interpret():
        apply = variants.get("flash_attn", "pallas").apply if flash else None
        want, g_want = jax.value_and_grad(loss_of(
            lambda p_, x_: qwen3next_reference.gated_attention(
                cfg, p_, x_, prec)), argnums=(0, 1))(p, x)
        got, g_got = jax.value_and_grad(loss_of(
            lambda o_, x_: program(o_, x_, apply)), argnums=(0, 1))(own, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert _rel(g_got[1], g_want[1]) < 1e-4
    for name, g in g_got[0].items():
        assert _rel(g, g_want[0]["attn_" + name]) < 1e-4, name


def test_the_spec_takes_the_flash_lowering_where_the_kernels_take_the_head():
    from veles_tpu.ops import variants
    from veles_tpu.znicz.lm import BlockSpec
    base = dict(features=64, ffn="experts", width=32, n_experts=8,
                held=(0, 8), top_k=2, residual="plain", scoring="softmax")
    full = BlockSpec(attention="gated", n_heads=4, kv_heads=2, head_dim=256,
                     rotary_dim=64, **base)
    lin = BlockSpec(attention="gated_delta", n_heads=4, key_heads=2,
                    value_heads=4, key_dim=16, value_dim=16, **base)
    assert full.lowerings(1, 8192) == {"flash_attn": "xla_blocked"}
    with variants.pallas_interpret():               # (above: off a TPU)
        assert full.lowerings(1, 8192) == {"flash_attn": "pallas"}
        assert full.lowerings(1, 100) == {"flash_attn": "xla_blocked"}
        # a Gated DeltaNet on a plain residual path resolves no registry op
        assert lin.lowerings(1, 8192) == {}


# -- the share of a deployment ------------------------------------------------------

def test_the_sixteen_shares_of_a_layer_add_up_to_the_uncut_layer():
    """512 -> 64 experts over 16 chips, 4 a chip: the parts the shares
    give, each through the PROGRAM's expert layer (experts 4 s to 4 s +
    3), with what every chip computes alike counted once (the gated
    shared expert; the mixer and the router are whole on every chip), are
    the uncut reference's whole layer, mixer and residuals and all."""
    from veles_tpu.znicz.lm import BlockSpec
    cfg = tiny(batch_per_chip=1, scan_groups=1, init_std=0.3, num_experts=64,
               published={"num_experts": 64}, num_experts_per_tok=6)
    p = _layer_params(cfg, 1)
    x = 0.7 * jax.random.normal(jax.random.key(8), (1, cfg["seq_len"], 64))
    prec = qwen3next_reference.Precision("float32")
    table = layer_table(cfg)[1]
    kw = {k: v for k, v in table.items() if k not in ("type", "held")}

    def cut(first, count):
        return {k: (v[first:first + count] if k.startswith("moe_experts")
                    else v) for k, v in p.items()}

    with jax.default_matmul_precision("highest"):
        a = x[0] + qwen3next_reference.mixer(cfg, p, x[0], prec)[0]
        moe, balance, idx = qwen3next_reference.expert_layer(
            cfg, p, a, 0, prec)
        whole = a + moe
        shared = qwen3next_reference.expert_layer(
            cfg, cut(0, 0), a, 0, prec)[0]
        routed = jnp.zeros_like(whole)
        for s in range(16):
            spec = BlockSpec(features=64, held=(4 * s, 4), **kw)
            y, out = spec.apply(cut(4 * s, 4), x)
            # a share's block: its mixer and residuals, the shared expert
            # and its own four experts' part
            assert int(out["dropped"]) == 0
            np.testing.assert_allclose(out["balance_loss"], balance,
                                       rtol=1e-5)
            routed = routed + (y[0] - a - shared)
        assert np.array_equal(np.sort(out["picked"], 1), np.sort(idx, 1))
    # (sixteen differences of numbers of the layer's size, each rounded
    # at float32's last bit of THAT size)
    scale = float(jnp.abs(whole).max())
    assert float(jnp.abs(routed).max()) > 1e-2 * scale
    np.testing.assert_allclose(a + shared + routed, whole,
                               atol=2e-5 * scale, rtol=1e-4)


@pytest.mark.parametrize("skew", [0.0, 3.0], ids=["balanced", "skewed"])
def test_a_whole_buffer_too_large_is_walked_in_windows(skew, monkeypatch):
    """Past the fast rows the held pairs are computed on the whole sorted
    buffer, or, where that buffer passes `_WHOLE_BUFFER_MAX` bytes, a
    window of the fast rows at a time: the same layer and the same
    gradients, whichever held experts a window's rows are of (50 rows a
    window against 190 to 290 held pairs of 4 experts)."""
    ks = jax.random.split(jax.random.key(0), 5)
    t, c, w, k, count, experts = 96, 16, 8, 3, 4, 6
    h = jax.random.normal(ks[0], (t, c))
    weights = [0.3 * jax.random.normal(ks[i], shape) for i, shape in (
        (1, (count, c, w)), (2, (count, c, w)), (3, (count, w, c)))]
    logits = jax.random.normal(ks[4], (t, experts)) \
        + skew * jnp.asarray([1., 1., 1., 1., 0., 0.])
    _r, idx, gates = om.softmax_topk_gates(logits, k)
    assert int((idx < count).sum()) > 150

    def layer(fast_rows):
        def loss(h, gates, *ws):
            y, dropped = om.held_experts_swiglu(h, idx, gates, *ws,
                                                (0, count), fast_rows)
            return jnp.sum(jnp.sin(y)), (y, dropped)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)(h, gates, *weights)

    (_, (y_all, _)), g_all = layer(None)
    (_, (y_whole, _)), g_whole = layer(50)
    monkeypatch.setattr(om, "_WHOLE_BUFFER_MAX", 0)
    assert om._windows((50, t * k), h) == 6
    (_, (y_walk, dropped)), g_walk = layer(50)
    assert int(dropped) == 0
    np.testing.assert_allclose(y_whole, y_all, atol=1e-6)
    np.testing.assert_allclose(y_walk, y_all, atol=2e-6)
    for got, whole, want in zip(g_walk, g_whole, g_all):
        np.testing.assert_allclose(whole, want, atol=1e-5)
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_cells_whole_buffer_is_walked_and_the_others_are_not():
    """By the bytes of the buffer alone: 327,680 rows of 2,048 bfloat16
    (this cell) in six windows of 61,440; keye2_ep8's 131,072 rows whole,
    as they were."""
    like = jax.ShapeDtypeStruct((8, 2048), jnp.bfloat16)
    assert om._windows((61440, 327680), like) == 6
    assert om._windows((49152, 131072), like) == 0


# -- what a spec says -----------------------------------------------------------------

def test_a_third_and_fourth_kind_of_block_are_one_spec():
    from veles_tpu.znicz.lm import BlockSpec
    base = dict(features=64, ffn="experts", width=32, n_experts=16,
                held=(4, 4), top_k=2, residual="plain", scoring="softmax")
    lin = BlockSpec(attention="gated_delta", n_heads=4, key_heads=2,
                    value_heads=4, key_dim=16, value_dim=8,
                    norm="zero_centred", shared_gate=True, **base)
    full = BlockSpec(attention="gated", n_heads=4, kv_heads=2, head_dim=16,
                     rotary_dim=4, shared=False, **base)
    assert lin.shapes()["attn_w_qkvz"] == (64, 2 * 32 + 2 * 32)
    assert lin.shapes()["attn_conv"] == (4, 2 * 32 + 32)
    assert lin.shapes()["moe_shared_mix"] == (64, 1)
    assert full.shapes()["attn_w_q"] == (64, 4 * 2 * 16)
    assert "moe_shared_mix" not in full.shapes()
    assert {"gdn_tokens", "gdn_chunks", "gdn_state_rms", "gdn_decay_min",
            "load", "steps"} <= set(lin.aux_shapes())
    assert "gdn_tokens" not in full.aux_shapes()
    zeros = lambda shape, std, filling="gaussian": np.zeros(  # noqa: E731
        shape, np.float32)
    # zero-centred norms start from 0, the gated norm from 1, dt_bias 1
    assert not lin.initial("attn_norm", (64,), zeros).any()
    assert lin.initial("attn_o_norm", (8,), zeros).all()
    assert full.initial("attn_q_norm", (16,), zeros).all()     # plain
    assert lin.initial("attn_dt_bias", (4,), zeros).all()
    drawn = lin.initial("attn_a_log", (512,), lambda shape, std, filling:
                        np.random.default_rng(0).uniform(
                            -std * 3 ** .5, std * 3 ** .5, shape))
    assert 0 < np.exp(drawn).min() and np.exp(drawn).max() < 16
    with pytest.raises(ValueError):
        BlockSpec(attention="gated", n_heads=4, shared=False,
                  shared_gate=True, **base)
    with pytest.raises(ValueError):
        BlockSpec(attention="gated", n_heads=4, norm="centred", **base)
    x = jnp.asarray([[3.0, 4.0]])
    np.testing.assert_allclose(
        ol.rms_norm(x, jnp.zeros(2), 0.0, offset=1.0),
        ol.rms_norm(x, jnp.ones(2), 0.0), rtol=1e-7)


def test_the_decay_reaches_the_step_in_float32():
    """`A_log` and `dt_bias` are named by their unit and keep their master
    dtype under a bfloat16 step."""
    cfg = tiny(compute_dtype="bfloat16")
    _mod, ses = session_of(cfg)
    seen = {}
    unit = ses.step.forwards[1]
    inner = unit.fused_apply

    def spy(params, x, **kw):
        seen.update({k: v.dtype for k, v in params.items()})
        return inner(params, x, **kw)

    unit.fused_apply = spy
    try:
        ses.dispatch()
    finally:
        unit.fused_apply = inner
    assert seen["attn_a_log"] == seen["attn_dt_bias"] == jnp.float32
    assert seen["attn_w_qkvz"] == seen["attn_norm"] == jnp.bfloat16


def test_the_published_layer_table_is_the_counted_model():
    """The real configuration's layer table has the counted shapes, leaf
    for leaf, without a unit being built; the counts are the issue's."""
    from veles_tpu.znicz.lm import BlockSpec
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3next_ep16.json")) as f:
        cfg = json.load(f)
    table = layer_table(cfg)
    assert [s["type"] for s in table] == ["token_embedding"] \
        + ["hc_block"] * 4 + ["lm_head"]
    assert [s["attention"] for s in table[1:-1]] == ["gated_delta"] * 3 \
        + ["gated"]
    counted = qwen3next_ops_count.shapes_of(cfg)
    for spec, want in zip(table[1:-1], counted[1:-1]):
        got = BlockSpec(features=cfg["hidden_size"],
                        **{k: v for k, v in spec.items() if k != "type"})
        assert got.shapes() == want
    assert table[1]["held"] == (0, 32) and table[1]["n_experts"] == 512
    assert table[4]["rotary_dim"] == 64
    size = lambda shapes, pre: sum(  # noqa: E731
        int(np.prod(s)) for k, s in shapes.items() if k.startswith(pre))
    assert size(counted[1], "attn_") - 2048 == 33718464
    assert size(counted[4], "attn_") - 2048 == 27263488
    assert size(counted[1], "moe_") - size(counted[1], "moe_experts") \
        + 2048 == 4200448
    assert qwen3next_ops_count.n_params(cfg) == cfg["n_params"] == 625667136
    assert BlockSpec(features=2048, **{
        k: v for k, v in table[1].items() if k != "type"}).fast_rows(
        32768) == 61440
    forward = qwen3next_ops_count.forward_flops(cfg, 4)
    for part, tera in (("gdn_proj", 6.6), ("attn_proj", 1.8),
                       ("attn_pairs", 2.2), ("moe", 1.6), ("head", 2.5)):
        assert forward[part] == pytest.approx(tera * 1e12, rel=0.03), part
    assert qwen3next_ops_count.train_flops_per_step(cfg, 4) \
        == pytest.approx(45.2e12, rel=0.01)


def test_the_sample_trains_through_the_normal_entry(tmp_path):
    """`python -m veles_tpu veles_tpu/samples/qwen3next.py --fused`, tiny
    preset, CPU: three epochs, and fewer tokens wrong at the end."""
    import re
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu",
         os.path.join(ROOT, "veles_tpu", "samples", "qwen3next.py"),
         "--fused", "-v"], cwd=str(tmp_path), env=env, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    wrong = [int(n) for n in re.findall(r"epoch \d+: train_err=(\d+)",
                                        out.stderr + out.stdout)]
    assert len(wrong) == 3 and wrong[-1] < wrong[0], wrong
