"""Cross-backend equivalence: ops.xla (jit) vs ops.reference (numpy golden).

This replicates the reference's central testing idea (SURVEY.md §4): the
NumPy backend is the golden model; the accelerated backend must agree within
dtype tolerance. Backwards are checked as jax.vjp(xla forward) vs the
hand-derived numpy backward.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.ops import reference as ref
from veles_tpu.ops import xla as ox

# float32 cross-backend tolerance: XLA's exp/log approximations differ from
# numpy's libm by up to ~1e-4 absolute (measured on this CPU backend).
RTOL, ATOL = 5e-4, 2e-4
rng = np.random.RandomState(42)


@pytest.fixture(autouse=True)
def _fresh_rng():
    # identical draws regardless of which subset/order of tests runs
    global rng
    rng = np.random.RandomState(42)


def assert_close(a, b, rtol=RTOL, atol=ATOL):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol)


ACTS = ["linear", "tanh", "relu", "strictrelu", "sigmoid", "log"]


@pytest.mark.parametrize("act", ACTS)
def test_activation_forward_and_grad(act):
    x = rng.randn(4, 7).astype(np.float32)
    assert_close(jax.jit(lambda v: ox.act_forward(act, v))(x),
                 ref.act_forward(act, x))
    # grad: vjp of xla forward vs numpy act_backward
    err = rng.randn(4, 7).astype(np.float32)
    y, vjp = jax.vjp(lambda v: ox.act_forward(act, v), x)
    (gx,) = vjp(jnp.asarray(err))
    gx_ref = ref.act_backward(act, np.asarray(y), err, x=x)
    assert_close(gx, gx_ref)


@pytest.mark.parametrize("act", ["linear", "tanh", "strictrelu"])
def test_all2all_forward_backward(act):
    x = rng.randn(8, 12).astype(np.float32)
    w = rng.randn(12, 5).astype(np.float32) * 0.1
    b = rng.randn(5).astype(np.float32) * 0.1
    y_ref = ref.all2all_forward(x, w, b, act)
    y_xla = jax.jit(lambda *a: ox.all2all_forward(*a, activation=act))(x, w, b)
    assert_close(y_xla, y_ref)

    err_y = rng.randn(8, 5).astype(np.float32)
    err_x_ref, dw_ref, db_ref = ref.all2all_backward(x, w, y_ref, err_y, act)
    f = lambda xx, ww, bb: ox.all2all_forward(xx, ww, bb, activation=act)
    _, vjp = jax.vjp(f, x, w, b)
    err_x, dw, db = vjp(jnp.asarray(err_y))
    assert_close(err_x, err_x_ref)
    assert_close(dw, dw_ref)
    assert_close(db, db_ref)


def test_all2all_softmax():
    x = rng.randn(6, 10).astype(np.float32)
    w = rng.randn(10, 4).astype(np.float32)
    b = rng.randn(4).astype(np.float32)
    y_ref = ref.softmax(x @ w + b)
    assert_close(jax.jit(ox.all2all_softmax_forward)(x, w, b), y_ref)


@pytest.mark.parametrize("stride,padding", [((1, 1), (0, 0)), ((2, 2), (1, 1)),
                                            ((1, 2), (2, 1))])
def test_conv2d_forward_backward(stride, padding):
    x = rng.randn(2, 9, 8, 3).astype(np.float32)
    w = rng.randn(3, 3, 3, 5).astype(np.float32) * 0.2
    b = rng.randn(5).astype(np.float32) * 0.1
    y_ref = ref.conv2d_forward(x, w, b, stride, padding, "tanh")
    f = lambda xx, ww, bb: ox.conv2d_forward(xx, ww, bb, stride, padding,
                                             "tanh")
    y_xla = jax.jit(f)(x, w, b)
    assert_close(y_xla, y_ref)

    err_y = rng.randn(*y_ref.shape).astype(np.float32)
    ex_ref, dw_ref, db_ref = ref.conv2d_backward(x, w, y_ref, err_y, stride,
                                                 padding, "tanh")
    _, vjp = jax.vjp(f, x, w, b)
    ex, dw, db = vjp(jnp.asarray(err_y))
    assert_close(ex, ex_ref, rtol=5e-4, atol=5e-5)
    assert_close(dw, dw_ref, rtol=5e-4, atol=5e-5)
    assert_close(db, db_ref, rtol=5e-4, atol=5e-5)


def test_deconv2d_is_conv_adjoint():
    x = rng.randn(2, 4, 4, 6).astype(np.float32)   # conv output grad shape
    w = rng.randn(3, 3, 3, 6).astype(np.float32)
    y_ref = ref.deconv2d_forward(x, w, (2, 2), (1, 1), out_hw=(8, 8))
    y_xla = jax.jit(lambda a, b: ox.deconv2d_forward(a, b, (2, 2), (1, 1),
                                                     out_hw=(8, 8)))(x, w)
    assert_close(y_xla, y_ref, rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("shape,ksize,stride", [
    ((2, 8, 8, 3), (2, 2), (2, 2)),
    ((2, 7, 9, 4), (3, 3), (2, 2)),   # truncated edge windows (ceil mode)
    ((1, 5, 5, 2), (2, 2), (1, 1)),
])
def test_maxpool_forward_backward(shape, ksize, stride):
    x = rng.randn(*shape).astype(np.float32)
    y_ref, idx = ref.maxpool_forward(x, ksize, stride)
    f = lambda v: ox.maxpool_forward(v, ksize, stride)
    y_xla = jax.jit(f)(x)
    assert_close(y_xla, y_ref)

    err_y = rng.randn(*y_ref.shape).astype(np.float32)
    ex_ref = ref.maxpool_backward(err_y, idx, x.shape)
    _, vjp = jax.vjp(f, x)
    (ex,) = vjp(jnp.asarray(err_y))
    assert_close(ex, ex_ref)


def test_maxabs_pooling():
    x = rng.randn(2, 6, 6, 3).astype(np.float32)
    y_ref, _ = ref.maxpool_forward(x, (2, 2), (2, 2), use_abs=True)
    y_xla = jax.jit(lambda v: ox.maxpool_forward(v, (2, 2), (2, 2),
                                                 use_abs=True))(x)
    assert_close(y_xla, y_ref)


@pytest.mark.parametrize("shape,ksize,stride,use_abs", [
    ((2, 8, 8, 3), (2, 2), (2, 2), False),
    ((2, 7, 9, 4), (3, 3), (2, 2), False),   # truncated edges (ceil mode)
    ((1, 5, 5, 2), (2, 2), (1, 1), False),   # overlapping windows
    ((2, 7, 7, 3), (3, 3), (2, 2), True),    # maxabs flavor
    ((1, 8, 8, 1), (3, 3), (2, 2), True),    # maxabs WITH edge padding:
    # the fill must be 0, not -inf (|−inf| would win every edge window)
])
def test_maxpool_slices_lowering_matches_golden(shape, ksize, stride,
                                                use_abs):
    """The shifted-strided-slices lowering (backward = selects + pads,
    the select_and_scatter-free candidate) matches the golden model in
    BOTH passes on tie-free random floats."""
    x = rng.randn(*shape).astype(np.float32)
    y_ref, idx = ref.maxpool_forward(x, ksize, stride, use_abs)
    f = lambda v: ox.maxpool_forward_slices(v, ksize, stride, use_abs)
    assert_close(jax.jit(f)(x), y_ref)
    err_y = rng.randn(*y_ref.shape).astype(np.float32)
    ex_ref = ref.maxpool_backward(err_y, idx, x.shape)
    _, vjp = jax.vjp(f, x)
    (ex,) = vjp(jnp.asarray(err_y))
    assert_close(ex, ex_ref)


@pytest.mark.parametrize("shape,ksize,stride", [
    ((2, 8, 8, 3), (2, 2), (2, 2)),
    ((2, 7, 7, 2), (3, 3), (2, 2)),
])
def test_avgpool_forward_backward(shape, ksize, stride):
    x = rng.randn(*shape).astype(np.float32)
    y_ref = ref.avgpool_forward(x, ksize, stride)
    f = lambda v: ox.avgpool_forward(v, ksize, stride)
    assert_close(jax.jit(f)(x), y_ref)
    err_y = rng.randn(*y_ref.shape).astype(np.float32)
    ex_ref = ref.avgpool_backward(err_y, x.shape, ksize, stride)
    _, vjp = jax.vjp(f, x)
    (ex,) = vjp(jnp.asarray(err_y))
    assert_close(ex, ex_ref)


def test_lrn_forward_backward():
    x = rng.randn(2, 4, 4, 8).astype(np.float32)
    y_ref = ref.lrn_forward(x)
    f = ox.lrn_forward
    assert_close(jax.jit(f)(x), y_ref)
    err_y = rng.randn(*x.shape).astype(np.float32)
    ex_ref = ref.lrn_backward(x, err_y)
    _, vjp = jax.vjp(f, x)
    (ex,) = vjp(jnp.asarray(err_y))
    assert_close(ex, ex_ref)


def test_dropout_equivalence():
    x = rng.randn(4, 10).astype(np.float32)
    mask = ref.make_dropout_mask(rng, x.shape, 0.3)
    assert_close(ox.dropout_forward(jnp.asarray(x), jnp.asarray(mask)),
                 ref.dropout_forward(x, mask))
    key = jax.random.key(0)
    m = ox.make_dropout_mask(key, (1000,), 0.5)
    keep_frac = float(np.asarray((m > 0).mean()))
    assert 0.4 < keep_frac < 0.6
    assert_close(float(np.asarray(m).max()), 2.0)


def test_softmax_ce_evaluator():
    logits = rng.randn(16, 5).astype(np.float32)
    probs = ref.softmax(logits)
    labels = rng.randint(0, 5, 16)
    loss_r, err_r, nerr_r, conf_r = ref.softmax_ce(probs, labels, 5)
    loss_x, err_x, nerr_x, conf_x = jax.jit(
        lambda p, l: ox.softmax_ce(p, l, 5))(probs, labels)
    assert_close(loss_x, loss_r)
    assert_close(err_x, err_r)
    assert int(nerr_x) == nerr_r
    np.testing.assert_array_equal(np.asarray(conf_x), conf_r)
    # err convention: (probs - onehot)/N is exactly grad of mean-CE wrt logits
    g = jax.grad(lambda lg: ox.ce_loss_from_logits(lg, jnp.asarray(labels), 5)
                 )(jnp.asarray(logits))
    assert_close(g, err_r)


def test_mse_evaluator():
    y = rng.randn(8, 3).astype(np.float32)
    t = rng.randn(8, 3).astype(np.float32)
    loss_r, err_r = ref.mse(y, t)
    loss_x, err_x = jax.jit(ox.mse)(y, t)
    assert_close(loss_x, loss_r)
    assert_close(err_x, err_r)


def test_kohonen_forward_and_update():
    x = rng.randn(10, 6).astype(np.float32)
    w = rng.randn(9, 6).astype(np.float32)
    grid = np.stack(np.meshgrid(np.arange(3), np.arange(3)),
                    -1).reshape(9, 2).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(ox.kohonen_forward(
        jnp.asarray(x), jnp.asarray(w))), ref.kohonen_forward(x, w))
    w_ref = ref.kohonen_update(x, w, grid, lr=0.1, sigma=1.0)
    w_xla = jax.jit(lambda *a: ox.kohonen_update(*a, lr=0.1, sigma=1.0))(
        x, w, grid)
    assert_close(w_xla, w_ref, rtol=1e-3, atol=1e-4)


def test_lstm_step_and_scan():
    n, d, hsz, t = 3, 4, 5, 7
    x = rng.randn(t, n, d).astype(np.float32)
    wx = rng.randn(d, 4 * hsz).astype(np.float32) * 0.3
    wh = rng.randn(hsz, 4 * hsz).astype(np.float32) * 0.3
    b = rng.randn(4 * hsz).astype(np.float32) * 0.1
    h = np.zeros((n, hsz), np.float32)
    c = np.zeros((n, hsz), np.float32)
    # scan vs step-by-step numpy
    hs_ref = []
    hr, cr = h, c
    for step in range(t):
        hr, cr = ref.lstm_step(x[step], hr, cr, wx, wh, b)
        hs_ref.append(hr)
    hs, hT, cT = ox.lstm_scan(x, h, c, wx, wh, b)
    assert_close(hs, np.stack(hs_ref))
    assert_close(hT, hr)
    assert_close(cT, cr)


def test_rbm_cd1_statistical():
    """RBM uses sampling: compare deterministic parts + gradient statistics
    over a shared probability path (h0 sampled differently per backend, so
    compare expectations loosely on a large batch)."""
    v = (rng.random_sample((512, 20)) < 0.5).astype(np.float32)
    w = rng.randn(20, 12).astype(np.float32) * 0.1
    bv = np.zeros(20, np.float32)
    bh = np.zeros(12, np.float32)
    dw_r, dbv_r, dbh_r = ref.rbm_cd1(v, w, bv, bh, np.random.RandomState(1))
    dw_x, dbv_x, dbh_x = jax.jit(ox.rbm_cd1)(v, w, bv, bh, jax.random.key(1))
    assert_close(dw_x, dw_r, rtol=1.0, atol=0.05)
    assert_close(dbv_x, dbv_r, rtol=1.0, atol=0.05)
    assert_close(dbh_x, dbh_r, rtol=1.0, atol=0.05)


def test_stochastic_pooling_shape_matches_maxpool():
    """Regression: stochastic pooling must use the same ceil-mode window
    geometry as max/avg pooling so the flavors are interchangeable."""
    x = rng.randn(2, 7, 9, 4).astype(np.float32)
    y_max = ox.maxpool_forward(jnp.asarray(x), (3, 3), (2, 2))
    y_sto = ox.stochastic_pool_forward(jnp.asarray(x), jax.random.key(0),
                                       (3, 3), (2, 2))
    assert y_sto.shape == y_max.shape


def test_stochastic_pooling_properties():
    x = np.abs(rng.randn(2, 4, 4, 3)).astype(np.float32)
    y = ox.stochastic_pool_forward(jnp.asarray(x), jax.random.key(0),
                                   (2, 2), (2, 2))
    y = np.asarray(y)
    assert y.shape == (2, 2, 2, 3)
    # each output must be one of its window's elements
    for n in range(2):
        for i in range(2):
            for j in range(2):
                for ch in range(3):
                    win = x[n, 2 * i:2 * i + 2, 2 * j:2 * j + 2, ch].ravel()
                    assert np.any(np.isclose(win, y[n, i, j, ch]))


def test_sgd_momentum_weight_decay():
    from veles_tpu.ops.optim import SGDConfig, sgd_init, sgd_update
    params = {"layer0": {"w": jnp.ones((3, 3)), "b": jnp.zeros(3)}}
    grads = {"layer0": {"w": jnp.full((3, 3), 0.5), "b": jnp.full(3, 0.5)}}
    vel = sgd_init(params)
    cfg = SGDConfig(lr=0.1, momentum=0.9, weight_decay=0.01, lr_bias_mult=2.0)
    p1, v1 = jax.jit(lambda p, g, v: sgd_update(p, g, v, cfg))(params, grads,
                                                               vel)
    # w: v = -0.1*(0.5 + 0.01*1) = -0.0510 ; b gets 2x lr, no decay on 0-val b
    assert_close(p1["layer0"]["w"], np.full((3, 3), 1 - 0.0510))
    assert_close(p1["layer0"]["b"], np.full(3, -0.1 * 2 * 0.5))
    p2, v2 = sgd_update(p1, grads, v1, cfg)
    # momentum carries: v2_w = 0.9*(-0.051) - 0.1*(0.5 + 0.01*p1_w)
    expect = 0.9 * -0.0510 - 0.1 * (0.5 + 0.01 * (1 - 0.0510))
    assert_close(p2["layer0"]["w"], np.asarray(p1["layer0"]["w"]) + expect)


def test_adam_decreases_quadratic():
    from veles_tpu.ops.optim import AdamConfig, adam_init, adam_update
    params = {"w": jnp.array([3.0, -2.0])}
    state = adam_init(params)
    cfg = AdamConfig(lr=0.1)
    loss = lambda p: (p["w"] ** 2).sum()
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, state = adam_update(params, g, state, cfg)
    assert float(loss(params)) < 0.5


def test_conv_space_to_depth_exact():
    """The s2d rewrite of a strided conv equals the direct lowering for
    the AlexNet stem geometry (227x227x3, 11x11/4) and assorted others."""
    import jax.numpy as jnp

    from veles_tpu.ops import xla as ox
    rng = np.random.RandomState(0)
    cases = [
        ((2, 227, 227, 3), (11, 11, 3, 8), 4, (0, 0)),   # AlexNet stem
        ((2, 32, 32, 3), (7, 7, 3, 4), 2, (0, 0)),
        ((1, 29, 29, 2), (5, 5, 2, 6), 3, (2, 2)),       # with padding
        ((2, 16, 16, 4), (4, 4, 4, 8), 4, (0, 0)),       # kernel == b
    ]
    for xshape, wshape, s, pad in cases:
        x = rng.randn(*xshape).astype(np.float32)
        w = rng.randn(*wshape).astype(np.float32) * 0.1
        b = rng.randn(wshape[-1]).astype(np.float32)
        gold = np.asarray(ox.conv2d_forward(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
            stride=(s, s), padding=pad))
        got = np.asarray(ox.conv2d_forward(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
            stride=(s, s), padding=pad, s2d=True))
        assert got.shape == gold.shape, (xshape, got.shape, gold.shape)
        np.testing.assert_allclose(got, gold, rtol=1e-5, atol=1e-5,
                                   err_msg=str((xshape, wshape, s, pad)))


def test_composed_golden_lrn_maxpool_is_bitwise_composition():
    """The composed fusion goldens (ISSUE 13) must be EXACTLY the
    sequential application of the member goldens — bitwise, numpy-only:
    a fused kernel gated on the composed golden is then transitively
    gated on every member's golden."""
    x = rng.randn(2, 8, 8, 16).astype(np.float32)
    k, alpha, beta, n = 2.0, 1e-3, 0.75, 5
    ksize, stride = (3, 3), (2, 2)
    y_lrn = ref.lrn_forward(x, k, alpha, beta, n)
    y_seq, idx = ref.maxpool_forward(y_lrn, ksize, stride, False)
    y_cmp = ref.lrn_maxpool_forward(x, k, alpha, beta, n, ksize, stride)
    np.testing.assert_array_equal(y_cmp, y_seq)
    g = rng.randn(*y_seq.shape).astype(np.float32)
    dx_seq = ref.lrn_backward(
        x, ref.maxpool_backward(g, idx, y_lrn.shape), k, alpha, beta, n)
    dx_cmp = ref.lrn_maxpool_backward(x, g, k, alpha, beta, n, ksize,
                                      stride)
    np.testing.assert_array_equal(dx_cmp, dx_seq)


def test_composed_golden_conv_lrn_is_bitwise_composition():
    x = rng.randn(2, 19, 19, 3).astype(np.float32)
    w = (rng.randn(5, 5, 3, 8) * 0.1).astype(np.float32)
    b = rng.randn(8).astype(np.float32)
    stride, padding, act = (4, 4), (0, 0), "strictrelu"
    k, alpha, beta, n = 2.0, 1e-3, 0.75, 5
    y_conv = ref.conv2d_forward(x, w, b, stride, padding, act)
    y_seq = ref.lrn_forward(y_conv, k, alpha, beta, n)
    y_cmp = ref.conv_lrn_forward(x, w, b, stride, padding, act,
                                 k, alpha, beta, n)
    np.testing.assert_array_equal(y_cmp, y_seq)
    g = rng.randn(*y_seq.shape).astype(np.float32)
    g_conv = ref.lrn_backward(y_conv, g, k, alpha, beta, n)
    seq = ref.conv2d_backward(x, w, y_conv, g_conv, stride, padding, act)
    cmp_ = ref.conv_lrn_backward(x, w, b, g, stride, padding, act,
                                 k, alpha, beta, n)
    for a, b_ in zip(cmp_, seq):
        np.testing.assert_array_equal(a, b_)


def test_composed_golden_attn_dropout_is_bitwise_composition():
    q, k, v = (rng.randn(1, 16, 2, 4).astype(np.float32)
               for _ in range(3))
    mask = ref.make_dropout_mask(np.random.RandomState(3),
                                 (1, 16, 2, 4), 0.4)
    y_seq = ref.dropout_forward(
        ref.mha_forward(q, k, v, causal=True), mask)
    y_cmp = ref.attn_dropout_forward(q, k, v, mask, causal=True)
    np.testing.assert_array_equal(y_cmp, y_seq)
    # the backward leg of the composition IS the member golden: dropout
    # backward routes the pooled error through the same mask
    g = rng.randn(1, 16, 2, 4).astype(np.float32)
    np.testing.assert_array_equal(ref.dropout_backward(g, mask),
                                  g * mask)


def test_finite_difference_gradcheck_composite_stack():
    """Independent-of-autodiff validation: central finite differences on
    a conv+LRN+pool+FC+softmax-CE stack match jax.grad to float64
    precision. Every other gradient test compares implementations
    against each other; this one compares against the definition."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops import xla as ox

    from jax import enable_x64

    with enable_x64(True):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(3, 10, 10, 2), jnp.float64)
        y = jnp.asarray(rng.randint(0, 4, 3))
        params = {
            "cw": jnp.asarray(rng.randn(3, 3, 2, 4) * 0.3, jnp.float64),
            "cb": jnp.asarray(rng.randn(4) * 0.1, jnp.float64),
            "fw": jnp.asarray(rng.randn(4 * 4 * 4, 4) * 0.2, jnp.float64),
            "fb": jnp.asarray(rng.randn(4) * 0.1, jnp.float64),
        }

        def loss(p):
            h = ox.conv2d_forward(x, p["cw"], p["cb"],
                                  stride=(1, 1), padding=(0, 0),
                                  activation="strictrelu")
            h = ox.lrn_forward(h, k=2.0, alpha=1e-3, beta=0.75, n=3)
            h = ox.maxpool_forward(h, (2, 2), (2, 2))
            logits = h.reshape(3, -1) @ p["fw"] + p["fb"]
            return ox.ce_loss_from_logits(logits, y, 4)

        grads = jax.grad(loss)(params)
        eps = 1e-6
        for name in params:
            flat = np.asarray(params[name]).ravel()
            # probe a handful of coordinates per tensor
            idxs = rng.choice(flat.size, size=min(6, flat.size),
                              replace=False)
            for i in idxs:
                d = np.zeros_like(flat)
                d[i] = eps
                bump = d.reshape(params[name].shape)
                pp = dict(params); pp[name] = params[name] + bump
                pm = dict(params); pm[name] = params[name] - bump
                fd = (float(loss(pp)) - float(loss(pm))) / (2 * eps)
                ad = float(np.asarray(grads[name]).ravel()[i])
                assert fd == pytest.approx(ad, rel=2e-4, abs=1e-7), \
                    (name, int(i), fd, ad)
