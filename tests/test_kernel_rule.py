"""The one rule that says which kernel a block traces (ISSUE 44): a Pallas
kernel where the step allows it (`BlockSpec.allow_pallas`, the fused
step's word), the platform runs it (a TPU, or interpret mode asked for:
`variants.kernels_ok` says both) and the kernel's `*_view` takes the shape;
the XLA form otherwise. One table: every kernel family of a block against
the four states. Tiny shapes, jaxprs only: nothing runs."""

import contextlib

import jax
import jax.numpy as jnp
import pytest

from veles_tpu.ops import variants
from veles_tpu.znicz.lm import BlockSpec

_DENSE = dict(ffn="dense", width=32)
_LATENT = dict(n_heads=2, q_rank=16, kv_rank=16)
_INDEXED = dict(attention="indexed", n_heads=2, kv_heads=1, index_heads=2,
                index_dim=8, index_topk=4, query_block=64, key_bands=1,
                residual="plain", features=32)
_EXPERTS = dict(n_heads=2, ffn="experts", n_experts=8, held=(0, 4), top_k=2,
                residual="plain", scoring="softmax", shared=False,
                attention="gated", kv_heads=1, head_dim=64, rotary_dim=16)
_DELTA = dict(attention="gated_delta", n_heads=2, key_heads=1, value_heads=2,
              residual="plain", features=64)

#: family: (the prefix of its kernels' names, its registry op or None for
#: a family outside the registry, the spec where its view takes the shape,
#: what changes for a shape off the view, tokens of the one sequence, the
#: dtype of the activations)
FAMILIES = {
    "hc": ("veles_hc_", "hc",
           dict(features=128, streams=2, nope=8, rope=8, v_dim=8,
                **_LATENT, **_DENSE),
           dict(features=64), 256, jnp.float32),
    "flash_attn_latent": (
        "veles_flash_", "flash_attn",
        dict(features=32, residual="plain", nope=128, rope=64, v_dim=128,
             **_LATENT, **_DENSE),
        dict(nope=8, rope=8, v_dim=8), 128, jnp.float32),
    "flash_attn_gated": (
        "veles_flash_", "flash_attn",
        dict(features=32, residual="plain", attention="gated", n_heads=2,
             kv_heads=1, head_dim=128, rotary_dim=16, **_DENSE),
        dict(head_dim=64), 128, jnp.float32),
    "dsa": ("veles_dsa_", "dsa", dict(head_dim=128, **_INDEXED, **_DENSE),
            dict(head_dim=64), 128, jnp.float32),
    "grouped": ("veles_gmm", None,
                dict(features=128, width=128, grouped="pallas", **_EXPERTS),
                dict(width=64), 128, jnp.float32),
    "seg_sum": ("veles_seg_sum", None,
                dict(features=128, width=128, **_EXPERTS),
                dict(features=64), 128, jnp.float32),
    "gdn_chunk": ("veles_gdn_chunk_", None,
                  dict(key_dim=128, value_dim=128, **_DELTA, **_DENSE),
                  dict(key_dim=64, value_dim=64), 128, jnp.bfloat16),
}

#: state: (interpret mode asked for, the step's word, the shape on the view)
STATES = {"all_three_hold": (True, True, True),
          "off_a_tpu": (False, True, True),
          "the_steps_word_cleared": (True, False, True),
          "a_shape_off_the_view": (True, True, False)}


@pytest.mark.parametrize("state", sorted(STATES))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_traces_its_kernels_where_all_three_hold(family, state):
    """The family's kernel is in the block's traced value and gradient
    where the step allows kernels, the platform runs them and the view
    takes the shape; with the platform or the step's word against it the
    block traces no `pallas_call` at all, and with the shape off the view
    none of this family's. What the spec reports for a registry op is what
    it traces."""
    prefix, op, kw, off_view, seq, dtype = FAMILIES[family]
    interpret, allow, on_view = STATES[state]
    spec = BlockSpec(**{**kw, **({} if on_view else off_view)})
    spec.allow_pallas = allow
    # (a linear layer's decay stays float32: `HCBlock.fused_float32_params`)
    p = {k: jnp.full(sh, 0.01, jnp.float32 if k in (
        "attn_a_log", "attn_dt_bias") else dtype)
        for k, sh in spec.shapes().items()}
    x = jnp.ones((1, seq, spec.n * spec.c), dtype)
    with (variants.pallas_interpret() if interpret
          else contextlib.nullcontext()):
        assert variants.kernels_ok(spec) == (interpret and allow)
        text = str(jax.make_jaxpr(jax.grad(
            lambda pp: spec.apply(pp, x)[0].astype(jnp.float32).sum()))(p))
        reported = spec.lowerings(1, seq)
    if not (interpret and allow):
        assert "pallas_call" not in text
        assert set(reported.values()) <= {"xla", "xla_blocked"}
    traced = "name=" + prefix in text
    assert traced == (interpret and allow and on_view), text.count(prefix)
    if op is not None:
        assert (reported[op] not in ("xla", "xla_blocked")) == traced
