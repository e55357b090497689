"""Pallas kernels vs golden models (interpreter mode on CPU — SURVEY.md §4
cross-backend strategy applied to hand-written kernels): fused SGD update,
LRN fwd/bwd, blocked flash attention."""

import numpy as np
import pytest

import veles_tpu.ops.pallas_kernels as pk
from veles_tpu.ops import attention as oa
from veles_tpu.ops import reference as ref


@pytest.fixture(autouse=True)
def _interpret_mode():
    pk._FORCE_INTERPRET = True
    yield
    pk._FORCE_INTERPRET = False


def test_sgd_update_matches_host_math():
    rng = np.random.RandomState(0)
    p = rng.randn(33, 17).astype(np.float32)   # deliberately unaligned
    g = rng.randn(33, 17).astype(np.float32)
    v = rng.randn(33, 17).astype(np.float32)
    lr, mom, wd = 0.05, 0.9, 1e-3
    g_eff = g + wd * p
    v_gold = mom * v - lr * g_eff
    p_gold = p + v_gold
    p_new, v_new = pk.sgd_update_pallas(p, g, v, lr, mom, wd)
    np.testing.assert_allclose(np.asarray(p_new), p_gold, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_new), v_gold, rtol=1e-5,
                               atol=1e-6)


def test_lrn_forward_matches_golden():
    rng = np.random.RandomState(1)
    x = rng.randn(16, 2, 3, 256).astype(np.float32)
    gold = ref.lrn_forward(x, 2.0, 1e-4, 0.75, 5)
    got = np.asarray(pk.lrn_forward_pallas(x, 2.0, 1e-4, 0.75, 5))
    np.testing.assert_allclose(got, gold, rtol=1e-4, atol=1e-5)
    # the kernels take a lane-dense view or nothing: the fallback by
    # shape is lrn_pallas's, not theirs
    with pytest.raises(ValueError, match="lrn_view"):
        pk.lrn_forward_pallas(x[:2, :, :, :16])


def test_lrn_backward_matches_golden():
    rng = np.random.RandomState(2)
    x = rng.randn(16, 2, 3, 256).astype(np.float32)
    err = rng.randn(16, 2, 3, 256).astype(np.float32)
    gold = ref.lrn_backward(x, err, 2.0, 1e-4, 0.75, 5)
    got = np.asarray(pk.lrn_backward_pallas(x, err, 2.0, 1e-4, 0.75, 5))
    np.testing.assert_allclose(got, gold, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="lrn_view"):
        pk.lrn_backward_pallas(x[:2, :, :, :16], err[:2, :, :, :16])


# -- LRN in the layout the convs emit (ISSUE 27): the view follows the shape ---

#: case -> (NHWC shape, the walk `lrn_view` must pick, None = no view: the
#: XLA closed form is traced and no kernel)
LRN_VIEW_CASES = {
    "batch_in_lanes": ((128, 2, 3, 96), "_walk_batch_lanes"),
    "channels_in_lanes": ((16, 2, 3, 256), "_walk_channel_lanes"),
    "batch_256_rows_per_iteration": ((256, 5, 5, 32), "_walk_batch_lanes"),
    # C a multiple of 128 that is no power of two (AlexNet conv3's width)
    "channels_384": ((8, 3, 3, 384), "_walk_channel_lanes"),
    # nb is capped at _LRN_LANE_MAX: the grid's second axis is 2
    "batch_2048_two_lane_blocks": ((2048, 1, 2, 32), "_walk_batch_lanes"),
    "falls_back_batch_100": ((100, 2, 3, 96), None),
    "falls_back_channels_40": ((128, 2, 3, 40), None),
    "falls_back_rows_not_tiles": ((3, 1, 1, 256), None),
}
#: a window that matters (alpha 0.3) beside AlexNet's constants, and a
#: beta that is no multiple of a quarter (the divide form of d/s)
LRN_SCALARS = {"alexnet": (2.0, 1e-4, 0.75, 5), "heavy": (1.0, 0.3, 0.75, 5),
               "generic_beta": (1.0, 0.3, 0.6, 3)}


def _lrn_kernels_traced(fn, *args):
    import jax
    txt = str(jax.make_jaxpr(fn)(*args))
    return [n for n in ("veles_lrn_fwd", "veles_lrn_bwd") if n in txt]


@pytest.mark.parametrize("scalars", sorted(LRN_SCALARS))
@pytest.mark.parametrize("case", sorted(LRN_VIEW_CASES))
def test_lrn_view_forward_matches_golden(case, scalars):
    shape, walk = LRN_VIEW_CASES[case]
    k, alpha, beta, n = LRN_SCALARS[scalars]
    view = pk.lrn_view(shape, 4)
    assert (view and view[0].__name__) == walk
    x = np.random.RandomState(5).randn(*shape).astype(np.float32)
    fn = lambda a: pk.lrn_pallas(a, k, alpha, beta, n)  # noqa: E731
    assert _lrn_kernels_traced(fn, x) == (["veles_lrn_fwd"] if walk else [])
    np.testing.assert_allclose(np.asarray(fn(x)),
                               ref.lrn_forward(x, k, alpha, beta, n),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("scalars", sorted(LRN_SCALARS))
@pytest.mark.parametrize("case", sorted(LRN_VIEW_CASES))
def test_lrn_view_backward_matches_golden(case, scalars):
    import jax
    shape, walk = LRN_VIEW_CASES[case]
    k, alpha, beta, n = LRN_SCALARS[scalars]
    rs = np.random.RandomState(6)
    x = rs.randn(*shape).astype(np.float32)
    g = rs.randn(*shape).astype(np.float32)
    fn = lambda a, e: jax.vjp(  # noqa: E731
        lambda v: pk.lrn_pallas(v, k, alpha, beta, n), a)[1](e)[0]
    assert _lrn_kernels_traced(fn, x, g) == (
        ["veles_lrn_fwd", "veles_lrn_bwd"] if walk else [])
    np.testing.assert_allclose(np.asarray(fn(x, g)),
                               ref.lrn_backward(x, g, k, alpha, beta, n),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("case", ["batch_in_lanes", "channels_in_lanes"])
def test_lrn_view_bfloat16_activation(case):
    """What the fused step hands the kernels: a bfloat16 activation goes
    to the MXU rounded once, the arithmetic around it is float32, the
    result is rounded to bfloat16 once."""
    import jax
    import jax.numpy as jnp
    shape, _ = LRN_VIEW_CASES[case]
    k, alpha, beta, n = LRN_SCALARS["heavy"]
    rs = np.random.RandomState(7)
    x = jnp.asarray(rs.randn(*shape), jnp.bfloat16)
    g = jnp.asarray(rs.randn(*shape), jnp.bfloat16)
    y, vjp = jax.vjp(lambda v: pk.lrn_pallas(v, k, alpha, beta, n), x)
    (dx,) = vjp(g)
    assert y.dtype == dx.dtype == jnp.bfloat16
    xf, gf = (np.asarray(a, np.float32) for a in (x, g))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               ref.lrn_forward(xf, k, alpha, beta, n),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               ref.lrn_backward(xf, gf, k, alpha, beta, n),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("shape,itemsize,block", [
    ((1024, 55, 55, 96), 2, (5, 96, 1024)),     # AlexNet LRN1, one chip
    ((256, 55, 55, 96), 2, (25, 96, 256)),      # ... at 256 a chip (dp)
    ((1024, 27, 27, 256), 2, (2592, 256)),      # LRN2
    ((256, 27, 27, 256), 2, (2592, 256)),
    ((1024, 55, 55, 96), 4, (1, 96, 1024)),     # float32: half the rows
])
def test_lrn_view_blocks_divide_exactly_and_fit(shape, itemsize, block):
    """No pad and no slice: a block divides its view in every dimension,
    and its double-buffered operands stay inside half the scoped VMEM."""
    from veles_tpu.analysis.resources import SCOPED_VMEM_LIMIT
    _, vshape, got = pk.lrn_view(shape, itemsize)
    assert got == block
    assert int(np.prod(vshape)) == int(np.prod(shape))
    assert all(v % b == 0 for v, b in zip(vshape, got))
    assert pk.lrn_view_vmem_bytes(got, itemsize) <= SCOPED_VMEM_LIMIT // 2


def test_lrn_view_vmem_rule_prices_whole_tiles():
    """A bfloat16 tile of the batch-in-lanes view is 16 channels x 128
    samples: 24 channels occupy 32, 130 lanes occupy 256."""
    assert pk.lrn_view_vmem_bytes((1, 16, 128), 2) == 6 * 16 * 128 * 2
    assert pk.lrn_view_vmem_bytes((3, 24, 130), 2) == 6 * 3 * 32 * 256 * 2
    assert pk.lrn_view_vmem_bytes((8, 128), 4) == 6 * 8 * 128 * 4


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_golden(causal):
    rng = np.random.RandomState(3)
    b, s, h, d = 2, 32, 2, 8
    q, k, v = (rng.randn(b, s, h, d).astype(np.float32) for _ in range(3))
    gold = np.asarray(oa.mha_forward(q, k, v, causal=causal))
    got = np.asarray(pk.flash_attention_pallas(q, k, v, causal=causal,
                                               blk_q=16, blk_k=16))
    np.testing.assert_allclose(got, gold, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_backward_matches_einsum_grad(causal):
    """The custom-VJP kernel pair vs jax.grad of the einsum golden model:
    dQ, dK, dV must agree on a multi-block grid (so the online-softmax
    recompute, the causal tile skip and BOTH streaming orders are
    exercised, not just the single-tile degenerate case)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    b, s, h, d = 2, 64, 2, 8
    q, k, v = (rng.randn(b, s, h, d).astype(np.float32) for _ in range(3))
    # a fixed random cotangent-shaping loss so all rows/heads contribute
    w = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))

    def loss_flash(q, k, v):
        o = pk.flash_attention_pallas(q, k, v, causal=causal,
                                      blk_q=16, blk_k=16)
        return jnp.sum(o * w)

    def loss_gold(q, k, v):
        return jnp.sum(oa.mha_forward(q, k, v, causal=causal) * w)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gold = jax.grad(loss_gold, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", got, gold):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


def test_attention_unit_trains_with_flash():
    """MultiHeadAttention.fused_apply differentiates THROUGH the Pallas
    kernel (use_flash='on', interpreter mode): parameter grads match the
    einsum path, so long-S local training really uses the kernel."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.znicz.attention import MultiHeadAttention

    rng = np.random.RandomState(5)
    n, s, e = 2, 32, 16
    x = jnp.asarray(rng.randn(n, s, e).astype(np.float32))
    grads = {}
    for mode in ("on", "off"):
        unit = MultiHeadAttention(None, n_heads=2, causal=True,
                                  use_flash=mode, name="mha")
        params = {k2: jnp.asarray(0.2 * rng2)
                  for k2, rng2 in zip(
                      ("wq", "wk", "wv", "wo"),
                      np.random.RandomState(6).randn(4, e, e)
                      .astype(np.float32))}
        unit.head_dim = e // 2
        loss = lambda p: jnp.sum(unit._apply(p, x) ** 2)  # noqa: E731
        grads[mode] = jax.grad(loss)(params)
    for k2 in grads["on"]:
        np.testing.assert_allclose(
            np.asarray(grads["on"][k2]), np.asarray(grads["off"][k2]),
            rtol=5e-3, atol=1e-4, err_msg=k2)
