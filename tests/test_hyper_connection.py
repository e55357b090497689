"""The op `hc` (ISSUE 34): its `pallas_one_pass` lowering (two
`jax.custom_vjp` functions over four kernels, each behind one module-level
`jax.jit`, Sinkhorn inside; interpret mode here) against the `xla`
lowering and against the plain reference's equations
(`benchmark/xing4_reference.py`, which imports nothing of the program),
outputs and every gradient; that every site of a program shares one trace
of a kernel; and the rule that chooses between the two lowerings from the
platform and the shape."""

import contextlib
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import xing4_reference  # noqa: E402
from veles_tpu.ops import lm as ol  # noqa: E402
from veles_tpu.ops import pallas_kernels as pk  # noqa: E402
from veles_tpu.ops import variants  # noqa: E402
from veles_tpu.samples.xing4 import TINY, layer_table  # noqa: E402

KW = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
REF_CFG = {"rms_norm_eps": 1e-6, "mhc_h_res_clamp_min": -30.0,
           "mhc_h_res_clamp_max": 30.0, "hc_sinkhorn_iters": 20,
           "hc_eps": 1e-6}
TOKENS = 256


def operands(n, c, dtype, seed=0, tokens=TOKENS):
    """A connection's leaves at the scales the model has them (biases
    around `hc_init_biases`, scalars away from 0 so that no gradient is
    a rounding), the streams, the sub-layer's weight and bias, and the
    random projection whose inner product with the result is the loss."""
    ks = jax.random.split(jax.random.key(seed), 10)
    normal = jax.random.normal
    p = {"p_pre": 0.05 * normal(ks[0], (n * c, n)),
         "p_post": 0.05 * normal(ks[1], (n * c, n)),
         "p_res": 0.05 * normal(ks[2], (n * c, n * n)),
         "a_pre": jnp.array([0.7]), "a_post": jnp.array([0.5]),
         "a_res": jnp.array([0.9]),
         "b_pre": 0.3 * normal(ks[3], (n,)),
         "b_post": 0.3 * normal(ks[4], (n,)),
         "b_res": 2.0 * jnp.eye(n) + 0.3 * normal(ks[5], (n, n))}
    args = (p, normal(ks[6], (tokens, n * c)),
            normal(ks[7], (c, c)) / np.sqrt(c), 0.1 * normal(ks[8], (c,)))
    args = jax.tree.map(lambda a: a.astype(dtype), args)
    return args, normal(ks[9], (tokens, n * c), jnp.float32)


def loss_of(apply, n, proj):
    """(p, x, w, y0) -> (<connection around h -> tanh(h w) + y0, proj>,
    the streams). `y0`'s gradient is the sub-layer output's."""
    def loss(p, x, w, y0):
        out, _ = apply(p, x, lambda h: (jnp.tanh(ol.mm(h, w)) + y0, None),
                       n, **KW)
        return (out.astype(jnp.float32) * proj).sum(), out
    return loss


def reference_apply(p, x, f, n, **_kw):
    """The reference's equations, float32, in the op's signature."""
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t = x.shape[0]
    out, extra = xing4_reference.hyper_connection(
        REF_CFG, f32, "", x.astype(jnp.float32).reshape(t, n, -1), f,
        xing4_reference.Precision("float32"))
    return out.reshape(t, -1), extra


def value_and_grads(apply, n, proj, args):
    (_, out), grads = jax.value_and_grad(
        loss_of(apply, n, proj), argnums=(0, 1, 2, 3), has_aux=True)(*args)
    p, x, w, y0 = grads
    return out, {**p, "x": x, "w": w, "y": y0}


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n,c,tokens", [(2, 128, 256), (2, 256, 256),
                                        (4, 128, 256), (4, 256, 256),
                                        (4, 128, 384), (2, 128, 640)])
def test_one_pass_a_side_gives_what_the_xla_form_and_the_reference_give(
        n, c, tokens, dtype):
    """Outputs and the gradient of every leaf (`p_pre`, `p_post`,
    `p_res`, the three scalars, the three biases), of x, of the
    sub-layer's weight and of its output. float32: the two lowerings and
    the reference agree to rounding. bfloat16: both lowerings are held to
    the float32 reference, and the kernels may not stand further from it
    than the XLA form does (they round once less). 256 tokens are one
    tile, 384 one of 384, 640 five of 128: the view function takes the
    largest multiple of 128 (up to 512) that divides the tokens and fits,
    and refuses what no multiple of 128 divides (below)."""
    args, proj = operands(n, c, jnp.dtype(dtype), seed=n * c, tokens=tokens)
    tile = pk.hc_view(tokens, c, n)
    assert tokens % tile == 0 and tile % 128 == 0
    with variants.pallas_interpret():
        out_p, g_p = value_and_grads(
            variants.get("hc", "pallas_one_pass").apply, n,
            proj, args)
    out_x, g_x = value_and_grads(
        variants.get("hc", "xla").apply, n, proj, args)
    out_r, g_r = value_and_grads(
        reference_apply, n, proj,
        jax.tree.map(lambda a: a.astype(jnp.float32), args))
    assert set(g_p) == {"p_pre", "p_post", "p_res", "a_pre", "a_post",
                        "a_res", "b_pre", "b_post", "b_res", "x", "w", "y"}
    if dtype == "float32":
        assert rel(out_p, out_x) < 1e-5 and rel(out_p, out_r) < 1e-5
        for k in g_p:
            assert rel(g_p[k], g_x[k]) < 1e-4, k
            assert rel(g_p[k], g_r[k]) < 1e-4, k
        return
    assert out_p.dtype == jnp.bfloat16 and g_p["x"].dtype == jnp.bfloat16
    assert rel(out_p, out_r) < 1e-2 and rel(out_x, out_r) < 1e-2
    for k in g_p:
        # a leaf of a few numbers is a sum over 256 tokens of terms of
        # either sign, in bfloat16 both ways: it may come out small
        # against its own error, so it is held to the XLA form's distance
        floor = 2e-2 if g_r[k].size > 16 else 5e-2
        assert rel(g_p[k], g_r[k]) < max(floor,
                                         2.0 * rel(g_x[k], g_r[k])), k


def test_the_kernels_under_checkpoint_give_the_same_gradients():
    """`jax.checkpoint` around a block recomputes the forward kernels in
    the backward pass, as the fused step does."""
    n, c = 4, 128
    args, proj = operands(n, c, jnp.float32, seed=3)
    apply = variants.get("hc", "pallas_one_pass").apply
    loss = lambda *a: loss_of(apply, n, proj)(*a)[0]  # noqa: E731
    with variants.pallas_interpret():
        plain = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
        remat = jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2, 3))(*args)
        kernels = str(jax.make_jaxpr(jax.grad(jax.checkpoint(loss)))(*args))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    for name in ("veles_hc_pre_fwd", "veles_hc_pre_bwd",
                 "veles_hc_post_fwd", "veles_hc_post_bwd"):
        assert name in kernels, name


def _arrays_outside_kernels(jaxpr, found):
    """(shape, dtype) of every value of a jaxpr and of the jaxprs its
    equations hold, a `pallas_call`'s own body aside."""
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        found.add((tuple(v.aval.shape), str(v.aval.dtype)))
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            found.add((tuple(v.aval.shape), str(v.aval.dtype)))
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _arrays_outside_kernels(sub, found)
    return found


def test_no_float32_copy_of_the_streams_outside_a_kernel():
    """Forward and backward of the passes in bfloat16 hold no (T, n*C)
    float32 array outside a kernel; the XLA form, differentiated by
    autodiff, does (which is what this looks for)."""
    n, c = 4, 128
    args, proj = operands(n, c, jnp.bfloat16)
    streams = (TOKENS, n * c)

    def arrays(name):
        apply = variants.get("hc", name).apply

        def fwd_bwd(g, *a):
            out, vjp = jax.vjp(lambda *b: apply(
                b[0], b[1], lambda h: (jnp.tanh(ol.mm(h, b[2])) + b[3],
                                       None), n, **KW)[0], *a)
            return out, vjp(g)
        return _arrays_outside_kernels(jax.make_jaxpr(fwd_bwd)(
            proj.astype(jnp.bfloat16), *args).jaxpr, set())

    with variants.pallas_interpret():
        passes = arrays("pallas_one_pass")
    assert (streams, "float32") in arrays("xla")
    assert (streams, "bfloat16") in passes
    assert (streams, "float32") not in passes


# -- the rule ----------------------------------------------------------------

RULE_CASES = {
    # case: (tokens, C, n, interpret mode, allow_pallas, what is traced)
    "takes_128_lanes": (256, 128, 4, True, True, "pallas_one_pass"),
    "sample_width_64": (256, 64, 2, True, True, "xla"),
    "tokens_the_tile_does_not_divide": (192, 128, 4, True, True, "xla"),
    "off_a_tpu": (256, 128, 4, False, True, "xla"),
    "allow_pallas_cleared": (256, 128, 4, True, False, "xla"),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_lowering_follows_the_platform_and_the_shape(case, caplog):
    """No selection, no option: the default `pallas_one_pass` is traced on
    a TPU (here: interpret mode) where a stream is whole lanes and the
    token tile divides the tokens; `xla` for the sample's width, for any
    other token count, off a TPU (quietly: it is the default that
    resolved) and under GSPMD."""
    from veles_tpu.znicz.lm import BlockSpec
    tokens, c, n, interpret, allow, want = RULE_CASES[case]
    assert variants.selected("hc") is None
    assert variants.effective("hc") == "pallas_one_pass"
    spec = BlockSpec(features=c, streams=n, n_heads=1, q_rank=8, kv_rank=8,
                     nope=8, rope=8, v_dim=8, ffn="dense", width=8)
    spec.allow_pallas = allow
    p = operands(n, c, jnp.float32)[0][0]
    x = jnp.ones((tokens, n * c), jnp.float32)

    def loss(xx):
        return variants.get("hc", spec.lowerings(1, tokens)["hc"]).apply(
            p, xx, lambda h: (h, None), n, **KW)[0].sum()

    with caplog.at_level(logging.WARNING, logger="veles.variants"), \
            (variants.pallas_interpret() if interpret
             else contextlib.nullcontext()):
        assert spec.lowerings(1, tokens)["hc"] == want
        text = str(jax.make_jaxpr(jax.grad(loss))(x))
    assert not caplog.records
    assert text.count("veles_hc_") == (4 if want == "pallas_one_pass" else 0)


@pytest.mark.parametrize("hidden,want", [(64, "xla"),
                                         (128, "pallas_one_pass")])
def test_the_step_names_what_its_connections_traced(hidden, want):
    """`FusedTrainStep.variant_table()` of the tiny workflow: the sample
    (hidden 64, 2 streams, 4 x 16 tokens) keeps the XLA form even where
    the kernels could run; the same model at hidden 128 and 128 tokens
    traces the kernels, and the table says so for blocks and head."""
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    cfg = dict(TINY, hidden_size=hidden, init_std=0.02)
    batch, seq = 8, cfg["seq_len"]

    class ShapeOnlyLoader(FullBatchLoader):
        def load_data(self):
            self.bind_arrays(np.zeros((batch, seq), np.int32),
                             np.zeros((batch, seq, 2), np.int32), 0, 0,
                             batch)

    wf = StandardWorkflow(
        layers=layer_table(cfg),
        loader=ShapeOnlyLoader(minibatch_size=batch, on_device=False),
        loss="softmax", n_classes=cfg["vocab_size"],
        decision_config={"max_epochs": 1, "fail_iterations": 1},
        gd_config={"learning_rate": 0.01}, name=f"xing4_rule_{hidden}")
    wf.initialize(device=None)
    step = wf.build_fused_step()
    assert step.variant_table()["hc"] == "xla"   # off a TPU
    with variants.pallas_interpret():
        assert step.variant_table()["hc"] == want
        units = [u for u in step.forwards
                 if getattr(u, "variant_op", None) == "hc"]
        assert len(units) == cfg["num_hidden_layers"] + 1
        assert not any(hasattr(u, "variant_signature") for u in units)
        block = units[0]
        x = {"x": jnp.zeros(block.input.shape, jnp.float32),
             "ids": jnp.zeros((batch, seq), jnp.int32), "table": None}
        params = {k: jnp.asarray(a.mem) for k, a in
                  block.param_arrays().items()}
        text = str(jax.make_jaxpr(
            lambda pp, xx: block.fused_apply(pp, xx)["x"])(params, x))
    assert ("veles_hc_" in text) == (want == "pallas_one_pass")


def test_the_kernels_refuse_a_shape_they_have_no_view_of():
    x = jnp.zeros((192, 4 * 128), jnp.float32)
    assert pk.hc_view(192, 128, 4) is None and pk.hc_view(256, 128, 4)
    with pytest.raises(ValueError, match="hc_view"):
        pk.hc_post_forward_pallas(x, x[:, :128], jnp.zeros((192, 32)), n=4,
                                  interpret=True)


def test_the_tile_follows_the_rows_bytes(monkeypatch):
    """The tile is what the widest kernel's float32 rows leave of the
    block budget: 128 at the published widths, and with a budget that
    holds 128 tokens of a small shape the kernels walk several tiles (the
    dP^T accumulator over the grid among them) to the same numbers."""
    assert pk.hc_view(8192, 3584, 4) == 128
    assert pk.hc_view(8192, 3584 * 4, 4) is None      # no 128 rows fit
    n, c, tokens = 4, 128, 512
    monkeypatch.setattr(pk, "_HC_BLOCK_BUDGET",
                        2 * 4 * (3 * n + 2) * c * 128)
    assert pk.hc_view(tokens, c, n) == 128
    args, proj = operands(n, c, jnp.float32, seed=11, tokens=tokens)
    for fn in (pk.hc_pre_forward_pallas, pk.hc_pre_backward_pallas,
               pk.hc_post_forward_pallas, pk.hc_post_backward_pallas):
        fn.clear_cache()
    try:
        with variants.pallas_interpret():
            out_p, g_p = value_and_grads(
                variants.get("hc", "pallas_one_pass").apply, n, proj, args)
    finally:
        for fn in (pk.hc_pre_forward_pallas, pk.hc_pre_backward_pallas,
                   pk.hc_post_forward_pallas, pk.hc_post_backward_pallas):
            fn.clear_cache()
    out_x, g_x = value_and_grads(variants.get("hc", "xla").apply, n, proj,
                                 args)
    assert rel(out_p, out_x) < 1e-5
    for k in g_p:
        assert rel(g_p[k], g_x[k]) < 1e-4, k


def _calls_of(jaxpr, found):
    """{jitted function's name: [its jaxpr at every call]} through a jaxpr
    and the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit" and eqn.params["name"].startswith(
                "hc_"):
            found.setdefault(eqn.params["name"], []).append(
                eqn.params["jaxpr"])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls_of(sub, found)
    return found


def test_every_site_of_a_program_shares_one_trace_of_a_kernel():
    """Three checkpointed blocks of two connections each, differentiated:
    every kernel is reached through its module-level `jax.jit` at all six
    sites (the pre side's forward at twelve, recomputed), and the sites
    hold the SAME jaxpr: one for a backward kernel, at most two for a
    forward one (the plain one of the first forward, and the one
    `jax.checkpoint`'s partial evaluation stages for the recomputed
    forward, derived once and cached). Inlined, as PR 33 had them, a
    `pallas_call` a site is traced and lowered by Python a site."""
    n, c = 2, 128
    (p, x, w, y0), proj = operands(n, c, jnp.float32)
    apply = variants.get("hc", "pallas_one_pass").apply

    @jax.checkpoint
    def block(xx, pp, ww):
        for _ in range(2):
            xx, _ = apply(pp, xx, lambda h: (jnp.tanh(ol.mm(h, ww)), None),
                          n, **KW)
        return xx

    def loss(pp, xx, ww):
        for _ in range(3):
            xx = block(xx, pp, ww)
        return (xx * proj).sum()

    with variants.pallas_interpret():
        calls = _calls_of(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
            p, x, w).jaxpr, {})
    sites = {k: len(v) for k, v in calls.items()}
    bodies = {k: len({id(j) for j in v}) for k, v in calls.items()}
    # the last post side of a block feeds nothing its backward reads
    assert sites == {"hc_pre_forward_pallas": 12, "hc_post_forward_pallas": 9,
                     "hc_post_backward_pallas": 6,
                     "hc_pre_backward_pallas": 6}, sites
    assert bodies == {"hc_pre_forward_pallas": 2, "hc_post_forward_pallas": 2,
                      "hc_post_backward_pallas": 1,
                      "hc_pre_backward_pallas": 1}, bodies
