"""The held experts' combine as a sorted segment sum (ISSUE 43):
`ops.moe._sum_rows` through `veles_seg_sum`, interpreted, against the form
that gathers a row a (token, slot) pair, as the forward's combine and as
`_take_rows`' transpose; what a dead row holds never reaches a sum; the
whole expert layer and its five gradients with the kernel engaged against
the layer without it, through both branches of `_held_swiglu` and through
the walk in windows. Small shapes: seconds, not minutes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.ops import moe as om
from veles_tpu.ops import pallas_kernels as pk
from veles_tpu.ops import variants


@pytest.fixture(autouse=True)
def _interpreted():
    """Every kernel of this file runs in interpret mode, read where
    `pallas_kernels` is called."""
    with variants.pallas_interpret():
        yield


def _routing(t: int, k: int, rows: int, share: float, seed: int,
             idle=(0, 0)):
    """A sorted buffer's bookkeeping for `t` tokens under top-`k` of which
    about `share` of the pairs are held, by 5 experts; the tokens
    [idle[0], idle[1]) hold nothing. (order (T k,), the live rows)."""
    rng = np.random.default_rng(seed)
    held = rng.random((t, k)) < share
    held[idle[0]:idle[1]] = False
    group = np.where(held, rng.integers(0, 5, (t, k)), 5).reshape(-1)
    order = np.argsort(group, kind="stable").astype(np.int32)
    return jnp.asarray(order), min(int(held.sum()), rows)


def _both(y, order, n_live: int, t: int, k: int):
    """(`_sum_rows` by the gather, by the kernel) of the buffer y."""
    rows = y.shape[0]
    slot = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    tile = pk.seg_sum_view(rows, t, y.shape[1], y.dtype.itemsize)
    assert tile
    plan = pk.seg_sum_plan(order[:rows], n_live, k, t, tile)
    return (om._sum_rows(y, None, slot, n_live),
            om._sum_rows(y, None, plan, n_live, tile))


def _ulps(got, want) -> float:
    """The widest difference in units of `want`'s last place."""
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    unit = eps * 2.0 ** np.floor(np.log2(np.maximum(np.abs(want), 1e-30)))
    return float((np.abs(got - want) / unit).max())


@pytest.mark.parametrize("t,k,rows,c,share", [
    (64, 4, 128, 128, 0.2), (512, 10, 1024, 256, 1 / 16),
    (512, 8, 1536, 128, 1 / 8), (96, 3, 256, 128, 0.6),
], ids=lambda v: str(v))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_segment_sum_is_the_gathers_sum(t, k, rows, c, share, dtype):
    """Every held pair summed, none dropped, in float32 and rounded once:
    the gather form's numbers but for the order of at most k additions.
    In bfloat16 within one unit in the last place, and to the bit where a
    token holds one row; in float32 to the rounding of a sum."""
    order, n_live = _routing(t, k, rows, share, seed=t + k)
    y = jnp.asarray(np.random.default_rng(1).normal(size=(rows, c)), dtype)
    want, got = _both(y, order, n_live, t, k)
    assert got.shape == want.shape == (t, c) and got.dtype == want.dtype
    held = np.bincount(np.asarray(order[:n_live]) // k, minlength=t)
    assert held.max() > 1 and (held == 1).any() and (held == 0).any()
    if dtype == "bfloat16":
        assert _ulps(got, want) <= 1.0
        one = held <= 1
        assert np.array_equal(np.asarray(got, np.float32)[one],
                              np.asarray(want, np.float32)[one])
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not np.asarray(got, np.float32)[held == 0].any()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_segment_sum_is_the_transpose_of_the_rows_gather(dtype):
    """`_take_rows`' cotangent through the kernel is its cotangent through
    the gather, and `_sum_rows`' cotangent is `_take_rows` either way."""
    t, k, rows, c = 256, 6, 512, 128
    order, n_live = _routing(t, k, rows, 0.25, seed=5)
    token_of = (order[:rows] // k).astype(jnp.int32)
    slot = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    tile = pk.seg_sum_view(rows, t, c, jnp.dtype(dtype).itemsize)
    plan = pk.seg_sum_plan(order[:rows], n_live, k, t, tile)
    rng = np.random.default_rng(2)
    h, g = (jnp.asarray(rng.normal(size=s), dtype)
            for s in ((t, c), (rows, c)))
    for fn, x, ct in ((om._take_rows, h, g), (om._sum_rows, g, h)):
        (y0, vjp0), (y1, vjp1) = (
            jax.vjp(lambda x: fn(x, token_of, pairs, n_live, seg), x)
            for pairs, seg in ((slot, None), (plan, tile)))
        np.testing.assert_allclose(np.asarray(y1, np.float32),
                                   np.asarray(y0, np.float32),
                                   rtol=1e-6, atol=1e-2 * (
                                       dtype == "bfloat16"))
        if dtype == "bfloat16":
            assert _ulps(vjp1(ct)[0], vjp0(ct)[0]) <= 1.0
        else:
            np.testing.assert_allclose(vjp1(ct)[0], vjp0(ct)[0],
                                       rtol=1e-6, atol=1e-6)


def test_a_tile_of_tokens_that_owns_no_row_gives_zeros():
    """Two whole token tiles hold nothing: their group of the work list is
    one item that keeps no row, which writes the zeros."""
    t, k, rows, c = 1024, 4, 1024, 128
    order, n_live = _routing(t, k, rows, 0.2, seed=7, idle=(256, 768))
    assert pk.seg_sum_view(rows, t, c, 2) == 256
    y = jnp.asarray(np.random.default_rng(3).normal(size=(rows, c)),
                    jnp.bfloat16)
    want, got = _both(y, order, n_live, t, k)
    assert not np.asarray(got, np.float32)[256:768].any()
    assert np.asarray(got, np.float32)[:256].any()
    assert _ulps(got, want) <= 1.0


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
def test_what_a_dead_row_holds_never_reaches_a_sum(poison):
    """The rows past `n_live` are whatever a grouped product left there,
    and in the transposed use a cotangent nobody masked: selected away
    before the one-hot product (0 x NaN is NaN), as the gather form never
    read them."""
    t, k, rows, c = 256, 4, 512, 128
    order, n_live = _routing(t, k, rows, 0.15, seed=9)
    assert 0 < n_live < rows - 128
    y = np.random.default_rng(4).normal(size=(rows, c)).astype(np.float32)
    y[n_live:] = poison
    want, got = _both(jnp.asarray(y, jnp.bfloat16), order, n_live, t, k)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert _ulps(got, want) <= 1.0


@pytest.mark.parametrize("live", ["none", "all"])
def test_a_buffer_with_no_live_row_and_one_with_no_dead_row(live):
    t, k, rows, c = 128, 4, 256, 128
    order, _ = _routing(t, k, rows, 0.6, seed=11)
    n_live = 0 if live == "none" else rows
    assert int((np.asarray(order) < 0).sum()) == 0
    y = jnp.asarray(np.random.default_rng(5).normal(size=(rows, c)),
                    jnp.float32)
    want, got = _both(y, order, n_live, t, k)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert bool(np.asarray(got).any()) == (live == "all")


@pytest.mark.parametrize("n_live", [0, 1, 128, 129, 300, 512])
def test_the_live_rows_alone_are_put_in_token_order(n_live, monkeypatch):
    """The walk gathers whole chunks, as many as hold a live row (here of
    128 rows, so that a buffer of 512 is several): the live rows are
    `jnp.take`'s, what lies past the last chunk walked was never read, and
    the sums are the sums."""
    monkeypatch.setattr(pk, "_SEG_SUM_TAKE_ROWS", 128)
    t, k, rows, c = 256, 4, 512, 128
    order, _ = _routing(t, k, rows, 0.7, seed=13)
    y = jnp.asarray(np.random.default_rng(6).normal(size=(rows, c)),
                    jnp.float32)
    perm = pk.seg_sum_plan(order[:rows], n_live, k, t, 128)[0]
    got = np.asarray(pk._rows_in_token_order(y, perm, n_live))
    walked = -(-n_live // 128) * 128
    assert np.array_equal(got[:walked], np.asarray(y)[np.asarray(
        perm)[:walked]])
    assert not got[walked:].any()
    want, summed = _both(y, order, n_live, t, k)
    np.testing.assert_allclose(summed, want, rtol=1e-6, atol=1e-6)


def test_the_view_takes_the_cells_buffers_and_no_width_off_the_lanes():
    """(rows, tokens, width, itemsize) -> the token tile: the three
    language-model cells' fast buffers and whole ones; nothing for a width
    or a row count off the lanes, which keep the gather."""
    for rows, tokens, width in ((61440, 32768, 2048), (49152, 16384, 2048),
                                (131072, 16384, 2048), (6144, 8192, 3584),
                                (32768, 8192, 3584)):
        assert pk.seg_sum_view(rows, tokens, width, 2) \
            == pk._SEG_SUM_TOKEN_TILE, (rows, tokens, width)
    assert pk.seg_sum_view(6144, 8192, 3584 + 64, 2) is None
    assert pk.seg_sum_view(6144 + 8, 8192, 3584, 2) is None
    assert pk.seg_sum_view(128, 60, 128, 2) is None
    assert pk.seg_sum_view(128, 48, 128, 2) == 48


# -- the whole expert layer -------------------------------------------------------

def _layer(dtype, fast_rows, seg_sum: bool, skew: float = 0.0):
    """value, (y, dropped) and the five gradients of the held experts'
    part over 128 tokens of 128 features, 4 of 6 experts held, top-3."""
    ks = jax.random.split(jax.random.key(0), 5)
    t, c, w, k, count, experts = 128, 128, 128, 3, 4, 6
    h = jax.random.normal(ks[0], (t, c)).astype(dtype)
    weights = [(0.3 * jax.random.normal(ks[i], shape)).astype(dtype)
               for i, shape in ((1, (count, c, w)), (2, (count, c, w)),
                                (3, (count, w, c)))]
    logits = jax.random.normal(ks[4], (t, experts)) \
        + skew * jnp.asarray([1., 1., 1., 1., 0., 0.])
    _r, idx, gates = om.softmax_topk_gates(logits, k)

    def loss(h, gates, *ws):
        y, dropped = om.held_experts_swiglu(
            h, idx, gates.astype(dtype), *ws, (0, count), fast_rows,
            kernels=seg_sum)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), (y, dropped)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        h, gates, *weights), (idx < count).sum()


def _traced(fn, primitive: str):
    """Every equation of `primitive` in the traced function, bodies of
    calls, branches and loops included."""
    out = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == primitive:
                out.append(e)
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jax.make_jaxpr(fn)().jaxpr)
    return out


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("branch", ["one_buffer", "fast", "whole", "walk"])
def test_the_layer_and_its_five_gradients_with_the_kernel_engaged(
        branch, dtype, monkeypatch):
    """`held_experts_swiglu` with `seg_sum` against without: one buffer of
    every pair there can be; the fast branch of `_held_swiglu` (the held
    pairs fit 256 rows); its whole-buffer branch (they do not fit 128);
    the walk in windows of 128 rows."""
    fast_rows = {"one_buffer": None, "fast": 256, "whole": 128,
                 "walk": 128}[branch]
    if branch == "walk":
        monkeypatch.setattr(om, "_WHOLE_BUFFER_MAX", 0)
        assert om._windows((128, 384), jnp.zeros((128, 128), dtype)) == 3
    skew = -1.0 if branch == "fast" else 2.0
    ((v0, (y0, d0)), g0), held = _layer(dtype, fast_rows, False, skew)
    ((v1, (y1, d1)), g1), _ = _layer(dtype, fast_rows, True, skew)
    assert (int(held) <= 256) if branch == "fast" else (int(held) > 128)
    assert int(d0) == int(d1) == 0
    tol = 1e-5 if dtype == "float32" else 2e-2
    for got, want in ((y1, y0), *zip(g1, g0)):
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        np.testing.assert_allclose(got, want, atol=tol * (
            np.abs(want).max() + 1e-3))


@pytest.mark.parametrize("fast_rows,calls", [(None, 2), (256, 6)])
def test_the_kernel_is_what_the_engaged_layer_traces(fast_rows, calls):
    """One buffer: the combine and, in the backward, the transpose of the
    rows' gather. Under `_held_swiglu` both branches of the forward's
    `cond`, and in each branch of the backward's the combine it forms
    again beside that transpose."""
    names = [e.params["name"] for e in _traced(
        lambda: _layer(jnp.float32, fast_rows, True)[0], "pallas_call")]
    assert names == ["veles_seg_sum"] * calls
    assert not _traced(lambda: _layer(jnp.float32, fast_rows, False)[0],
                       "pallas_call")


def test_no_sort_of_every_pair_is_left_on_the_engaged_path():
    """2 of 6 experts held under top-3: a buffer of 256 rows for 384
    (token, slot) pairs. The gather form sorts the pairs twice (by expert;
    every pair's sorted row), the engaged one once, and then the buffer's
    rows."""
    ks = jax.random.split(jax.random.key(1), 3)
    h = jax.random.normal(ks[0], (128, 128))
    w = 0.3 * jax.random.normal(ks[1], (2, 128, 128))
    _r, idx, gates = om.softmax_topk_gates(
        jax.random.normal(ks[2], (128, 6)), 3)

    def sorted_lengths(seg_sum: bool):
        return sorted(e.invars[0].aval.shape[0] for e in _traced(
            lambda: om.held_experts_swiglu(
                h, idx, gates, w, w, w.swapaxes(1, 2), (0, 2),
                kernels=seg_sum)[0], "sort"))
    assert sorted_lengths(False) == [384, 384]
    assert sorted_lengths(True) == [256, 384]
