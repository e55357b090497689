"""Latent attention's core as the flash kernels (ISSUE 38): the three
`veles_flash_*` kernels in interpret mode against the blocked XLA form at
the published widths (keys of 128 + 64 shared rotary dimensions, values of
128), the rule that chooses between the two, and what a block's
`jax.checkpoint` keeps of them."""

import contextlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.ops import attention as oa
from veles_tpu.ops import pallas_kernels as pk
from veles_tpu.ops import variants

NOPE, ROPE, VDIM = 128, 64, 128
SCALE = (NOPE + ROPE) ** -0.5
KERNELS = ("veles_flash_fwd", "veles_flash_dq", "veles_flash_dkv")


def operands(seq, dtype, n=1, heads=2, seed=0):
    """(q_nope, q_rope, k_nope, k_rope shared by the heads, v) and a
    cotangent of the output."""
    rng = np.random.default_rng(seed)
    g = lambda *sh: jnp.asarray(rng.normal(size=sh), dtype)  # noqa: E731
    return (g(n, seq, heads, NOPE), g(n, seq, heads, ROPE),
            g(n, seq, heads, NOPE), g(n, seq, ROPE),
            g(n, seq, heads, VDIM)), g(n, seq, heads, VDIM)


def flash_core(*args):
    with variants.pallas_interpret():
        return oa._latent_core_flash(
            variants.resolve("flash_attn").apply, *args, SCALE)


# several tiles (4 of 512 queries x 2 of 1,024 keys, three of the eight
# above the diagonal and passed over) and a single tile
@pytest.mark.parametrize("seq", [2048, 256])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_the_kernels_follow_the_blocked_xla_form(seq, dtype, tol):
    """Outputs and the gradients by the queries, the keys (a head's own
    part and the rotary part the heads share) and the values; float32
    differs by the order of its sums, bfloat16 by roundings of 2^-9 at the
    same points (probabilities before they meet the values, the score
    cotangent before its two products)."""
    args, w = operands(seq, jnp.dtype(dtype))
    assert variants.resolve("flash_attn").name == "xla_mha"     # off a TPU

    def value_and_grads(core):
        out, vjp = jax.vjp(core, *args)
        return (out,) + vjp(w)

    got = value_and_grads(flash_core)
    want = value_and_grads(lambda *a: oa._latent_core_xla(*a, SCALE))
    for name, a, b in zip(("out", "dq_nope", "dq_rope", "dk_nope",
                           "dk_rope", "dv"), got, want):
        assert a.dtype == b.dtype == jnp.dtype(dtype), name
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), name


@pytest.mark.parametrize("seq", [2048, 256])
def test_the_logsumexps_are_the_causal_scores(seq):
    (q_nope, q_rope, k_nope, k_rope, v), _ = operands(seq, jnp.float32,
                                                      heads=1, seed=1)
    q = jnp.concatenate([q_nope, q_rope], -1)[0].transpose(1, 0, 2)
    k = jnp.concatenate([k_nope, k_rope[:, :, None]], -1)[0].transpose(1, 0, 2)
    out, lse = pk.flash_forward_pallas(
        q, k, v[0].transpose(1, 0, 2), scale=SCALE, causal=True,
        blk_q=pk.flash_fit_block(seq, pk._FLASH_BLK_Q),
        blk_k=pk.flash_fit_block(seq, pk._FLASH_BLK_K), interpret=True)
    scores = jnp.einsum("hqd,hkd->hqk", q, k) * SCALE
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -jnp.inf)
    assert out.shape == (1, seq, VDIM) and lse.shape == (1, 1, seq)
    np.testing.assert_allclose(lse[:, 0], jax.nn.logsumexp(scores, axis=-1),
                               rtol=1e-5, atol=1e-5)


def test_a_checkpoint_that_saves_the_names_attends_once():
    """Under the blocks' policy the backward pass holds the two backward
    kernels and no second forward; under a `jax.checkpoint` that saves
    nothing the forward is recomputed."""
    from veles_tpu.znicz.lm import _SAVED_POLICY
    args, _ = operands(256, jnp.float32)

    def kernels(policy):
        f = jax.checkpoint(lambda *a: flash_core(*a).sum(), policy=policy)
        text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(
            *args))
        return [text.count("name=" + k) for k in KERNELS]

    assert kernels(_SAVED_POLICY) == [1, 1, 1]
    assert kernels(None) == [2, 1, 1]


def test_the_backward_stands_under_the_scope_it_is_given():
    """A custom VJP's backward is traced outside the forward's scope:
    `step_attn_ms` reads `mla`, so the backward opens it itself."""
    args, _ = operands(256, jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: flash_core(*a).sum()))(*args)
    stacks = {e.params["name"]: str(e.source_info.name_stack)
              for e in jaxpr.jaxpr.eqns if "jaxpr" in e.params
              and "name" in e.params}
    assert stacks["flash_dq_pallas"] == "transpose(jvp(mla))", stacks
    assert stacks["flash_dkv_pallas"] == "transpose(jvp(mla))", stacks


VIEW_CASES = {
    # (sequence, key width, value width): admitted
    "published_widths": ((4096, 192, 128), True),
    "one_tile": ((128, 192, 128), True),
    "equal_widths": ((1024, 128, 128), True),
    "tiles_do_not_divide": ((4000, 192, 128), False),
    "short_sequence": ((16, 192, 128), False),
    "sample_widths": ((128, 16, 8), False),
    "values_not_whole_lanes": ((128, 192, 64), False),
}


@pytest.mark.parametrize("case", sorted(VIEW_CASES))
def test_flash_view_admits_whole_tiles_only(case):
    shape, admitted = VIEW_CASES[case]
    assert pk.flash_view(*shape) is admitted


RULE_CASES = {
    # case: (seq, nope, rope, values, interpret mode, allow_pallas, traced)
    "published_widths": (128, NOPE, ROPE, VDIM, True, True, "pallas"),
    "sample_widths": (128, 8, 8, 8, True, True, "xla_blocked"),
    "tiles_do_not_divide": (192, NOPE, ROPE, VDIM, True, True, "xla_blocked"),
    "off_a_tpu": (128, NOPE, ROPE, VDIM, False, True, "xla_blocked"),
    "allow_pallas_cleared": (128, NOPE, ROPE, VDIM, True, False,
                             "xla_blocked"),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_lowering_follows_the_platform_and_the_shape(case, caplog):
    """No selection, no option: a block's value and gradient trace the
    three kernels on a TPU (here: interpret mode) where `flash_view`
    admits the shape, and the blocked XLA form off a TPU (quietly), for a
    shape the tiles do not divide and in a step that cleared
    `allow_pallas` (GSPMD)."""
    from veles_tpu.znicz.lm import BlockSpec
    seq, nope, rope, v_dim, interpret, allow, want = RULE_CASES[case]
    assert variants.selected("flash_attn") is None
    spec = BlockSpec(features=32, streams=1, residual="plain", n_heads=2,
                     q_rank=16, kv_rank=16, nope=nope, rope=rope,
                     v_dim=v_dim, ffn="dense", width=32)
    spec.allow_pallas = allow
    rng = np.random.default_rng(3)
    p = {k: jnp.asarray(rng.normal(size=sh) * 0.1, jnp.float32)
         for k, sh in spec.shapes().items()}
    x = jnp.asarray(rng.normal(size=(2, seq, 32)), jnp.float32)
    with caplog.at_level(logging.WARNING, logger="veles.variants"), \
            (variants.pallas_interpret() if interpret
             else contextlib.nullcontext()):
        assert spec.lowerings(2, seq) == {"flash_attn": want}
        text = str(jax.make_jaxpr(jax.grad(
            lambda pp: spec.apply(pp, x)[0].sum()))(p))
    assert not caplog.records
    assert [text.count("name=" + k) for k in KERNELS] == (
        [1, 1, 1] if want == "pallas" else [0, 0, 0])


def test_another_attention_resolves_nothing():
    from veles_tpu.znicz.lm import BlockSpec
    spec = BlockSpec(features=32, n_heads=2, attention="indexed",
                     residual="plain", kv_heads=1, head_dim=128,
                     index_heads=2, index_dim=8, index_topk=4, ffn="dense",
                     width=32)
    assert "flash_attn" not in spec.lowerings(1, 128)
