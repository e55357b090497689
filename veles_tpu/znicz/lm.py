"""Units of a sparse-expert language model with hyper-connected residual
streams: token embedding, the block, and the head with its losses.

Not in the reference (SURVEY.md §5.7). House pattern: Forward unit with
`fused_apply`, the vjp-driven GD twin, parameters as named Arrays. These
units train through `FusedTrainStep` (the `--fused` path); the granular
unit-by-unit path has no evaluator for a head that owns two losses, and
`numpy_run`/`xla_run` raise.

What flows between the units is a dict, not an array: `x`, the streams
(N, S, n*C) with a token's `n` residual streams side by side in one row
(`ops/lm.py` says why); `ids`, the tokens; `table`, the embedding in the
compute dtype, which the head's multi-token-prediction module reads a
second time (the SHARED embedding: one leaf, two uses, one gradient).

`HCBlock` is one transformer block: a hyper-connection around latent
attention, another around a dense SwiGLU MLP or an expert layer. It is
what `jax.checkpoint` wraps (`fused_remat`): the step keeps a block's
input and recomputes its inside in the backward pass. An expert layer
routes over ALL `n_experts` and computes the `held = (first, count)` it
holds (`ops/moe.py`); its selection bias and its load counters are step
state that no gradient touches (`aux_arrays`, `fused_aux_update`), moved
after every step under the scope `update/balance`.

A block's token mixer is one of four (`BlockSpec(attention=...)`): latent
attention; grouped-query attention over the keys an indexer selects
(`indexed`); grouped-query attention behind an output gate (`gated`); a
Gated DeltaNet, linear attention whose state is a matrix a head
(`gated_delta`, `ops/linear_attention.py`). The blocks of one layer table
need not be alike (`samples/qwen3next.py`: three `gated_delta` to one
`gated`); one `jax.checkpoint` policy serves them all
(`HCBlock.fused_remat_policy`). Their scopes: `dsa`, `attn`, `gdn` (with
`proj`, `conv`, `scan`, `out` beneath it).

Scopes inside a block, for a profile: `hc_pre` (the three maps, Sinkhorn,
reading the sub-layer's input from the streams), `mla`, `hc_post` (writing
the streams), `mlp`, or `moe/router`, `moe/dispatch`, `moe/experts`,
`moe/combine`, `moe/shared`; in the head `head` and `mtp/...`. The two
`hc_*` scopes are opened by `ops.lm.hyper_connection`.

Which kernel a block traces is ONE rule (`variants.kernels_ok`, then the
kernel's view of the shape): a Pallas kernel where the step allows it
(not under GSPMD), the platform runs it (a TPU, or interpret mode asked
for) and the kernel's `*_view` takes the shape; the XLA form otherwise.
`BlockSpec.lowerings` says it for the registry ops (`hc`, `dsa`,
`flash_attn`: what `apply` traces and `variant_table()` reports), the
held experts' products and combine (`ops/moe.py`) and the Gated DeltaNet's
operand stage (`ops/linear_attention.py`) get the first two thirds as one
boolean and ask their own view.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from veles_tpu.memory import Array
from veles_tpu.ops import attention as oa
from veles_tpu.ops import linear_attention as la
from veles_tpu.ops import lm as ol
from veles_tpu.ops import moe as om
from veles_tpu.ops import variants
from veles_tpu.znicz.nn_units import (Forward, GradientDescentVJP,
                                      register_gd)

#: rows of an expert layer's sorted (token, slot) buffer over the rows a
#: perfectly balanced router fills (`ops.moe.held_experts_swiglu`'s
#: `fast_rows`), by what balances the router: held loads within it take
#: the fast path, anything beyond is computed on the whole buffer. Under
#: the selection-bias rule the held experts of one layer were given at
#: most 1.150 times the even load in one step, from the first step on
#: (ten seeds x 83 steps x 5 layers on a v5e, PERF.md section 6, PR 32),
#: and a step's load stands 4 % around the even one: 1.5 is twelve of
#: those away. Softmax scores under a balance loss alone are held to
#: nothing: from a random start a handful of the experts take most of
#: the slots, the held sixteen's share of a layer is a draw of the seed,
#: and four-step means of it reached 2.03 times the even one (16 seeds x
#: 6 layers, PR 35): past 1.5 were a tenth of the layers' steps, each
#: 28 ms slower on the whole buffer (a layer's 13 ms at 24,576 rows, 45
#: at 131,072), which was most of what the seed did to a step's time. A
#: buffer row costs 0.18 us a layer and step beside the products
FAST_ROWS_HEADROOM = {"sigmoid_bias": 1.5, "softmax": 3.0}


class BlockSpec:
    """The static description of one block and its pure forward. Shared
    by `HCBlock` and by the head's multi-token-prediction module."""

    def __init__(self, *, features: int, streams: int = 1, n_heads: int,
                 ffn: str, width: int, residual: str = "hc",
                 attention: str = "latent", q_rank: int = 0,
                 kv_rank: int = 0, nope: int = 0, rope: int = 0,
                 v_dim: int = 0, kv_heads: int = 0, head_dim: int = 0,
                 index_heads: int = 0, index_dim: int = 0,
                 index_topk: int = 0, query_block: int = 256,
                 key_bands: int = 4, rotary_dim: int = 0,
                 key_heads: int = 0, value_heads: int = 0, key_dim: int = 0,
                 value_dim: int = 0, conv_kernel: int = 4, chunk: int = 64,
                 scan_groups: int = 1,
                 norm: str = "plain", n_experts: int = 0,
                 held: Sequence[int] = (0, 0), top_k: int = 0,
                 scoring: str = "sigmoid_bias", shared: bool = True,
                 shared_gate: bool = False,
                 grouped: str = "ragged_dot", routed_scaling: float = 1.0,
                 bias_update_speed: float = 0.001,
                 rope_theta: float = 10000.0,
                 rope_scaling: Optional[Dict[str, Any]] = None,
                 sinkhorn_iters: int = 20, hc_eps: float = 1e-6,
                 hc_clamp: Sequence[float] = (-30.0, 30.0),
                 norm_eps: float = 1e-6, init_std: float = 0.02) -> None:
        for what, value, known in (
                ("ffn", ffn, ("dense", "experts")),
                ("residual", residual, ("hc", "plain")),
                ("attention", attention, ("latent", "indexed", "gated",
                                          "gated_delta")),
                ("norm", norm, ("plain", "zero_centred")),
                ("scoring", scoring, ("sigmoid_bias", "softmax")),
                ("grouped", grouped, ("ragged_dot", "pallas"))):
            if value not in known:
                raise ValueError(f"{what} must be one of {known}, "
                                 f"not {value!r}")
        if residual == "plain" and streams != 1:
            raise ValueError("a plain residual path has one stream")
        self.c, self.n = features, streams
        self.residual, self.attention = residual, attention
        self.scoring, self.shared = scoring, bool(shared)
        if shared_gate and not shared:
            raise ValueError("a gate with no shared expert behind it")
        if norm != "plain" and attention in ("latent", "indexed"):
            raise ValueError(f"{attention} attention's inner norms are "
                             f"plain ones: norm {norm!r} is not implemented")
        self.shared_gate = bool(shared_gate)
        #: 1 where a norm's scale is stored less 1 (`ops.lm.rms_norm`)
        self.norm, self.norm_offset = norm, float(norm == "zero_centred")
        self.rotary_dim = rotary_dim or head_dim
        self.key_heads, self.value_heads = key_heads, value_heads
        self.key_dim, self.value_dim = key_dim, value_dim
        self.conv_kernel, self.chunk = conv_kernel, chunk
        self.scan_groups = int(scan_groups)
        self.grouped = grouped
        self.n_heads, self.q_rank, self.kv_rank = n_heads, q_rank, kv_rank
        self.nope, self.rope, self.v_dim = nope, rope, v_dim
        self.kv_heads, self.head_dim = kv_heads, head_dim
        self.index_heads, self.index_dim = index_heads, index_dim
        self.index_topk = index_topk
        self.query_block, self.key_bands = query_block, key_bands
        self.ffn, self.width = ffn, width
        self.n_experts, self.top_k = n_experts, top_k
        self.held = (int(held[0]), int(held[1]))
        self.routed_scaling = routed_scaling
        self.bias_update_speed = bias_update_speed
        self.rope_theta = rope_theta
        self.rope_scaling = dict(rope_scaling or {})
        self.sinkhorn_iters, self.hc_eps = sinkhorn_iters, hc_eps
        self.hc_clamp = (float(hc_clamp[0]), float(hc_clamp[1]))
        self.norm_eps, self.init_std = norm_eps, init_std

    #: what a block hands on to the head that owns the loss, by the key
    #: its `apply` counts it under: differentiable terms of the loss
    LOSS_TERMS = {"balance_loss": "balance", "index_loss": "index"}

    #: the step's word on Pallas kernels (`variants.kernels_ok` reads it):
    #: the unit that owns the spec hands it on before every trace
    allow_pallas = True

    #: the registry ops a block resolves at trace time: (the view of
    #: `pallas_kernels` that says whether the op's kernels take a shape,
    #: the op's XLA form; `xla_blocked`: latent and gated attention's
    #: blocked form in the place of the op's `xla_mha`). A kernel family
    #: that is a registry op is one row here and one shape below
    KERNEL_OPS = {"hc": ("hc_view", "xla"), "dsa": ("dsa_view", "xla"),
                  "flash_attn": ("flash_view", "xla_blocked")}

    def lowerings(self, batch: int, seq: int) -> Dict[str, str]:
        """{registry op: the lowering it traces} for the ops this block
        resolves over `batch` sequences of `seq` tokens: `hc` on
        hyper-connected streams, `dsa` for indexed, `flash_attn` for
        latent and gated attention. The rule, for each: the registry's
        kernel where `variants.resolve` hands one out (the step allows
        kernels and the platform runs them) and the kernels' view takes
        the shape, else the op's XLA form. What `apply` traces and what
        `variant_table()` reports."""
        shapes: Dict[str, Tuple[int, ...]] = {}
        if self.residual == "hc":
            shapes["hc"] = (batch * seq, self.c, self.n)
        if self.attention == "indexed":
            shapes["dsa"] = (seq, self.head_dim)
        elif self.attention == "latent":
            shapes["flash_attn"] = (seq, self.nope + self.rope, self.v_dim)
        elif self.attention == "gated":
            shapes["flash_attn"] = (seq, self.head_dim, self.head_dim)
        from veles_tpu.ops import pallas_kernels as pk
        out = {}
        for op, shape in shapes.items():
            view, xla = self.KERNEL_OPS[op]
            v = variants.resolve(op, unit=self)
            out[op] = v.name if v.pallas and getattr(pk, view)(*shape) \
                else xla
        return out

    # -- parameters ----------------------------------------------------------

    def shapes(self) -> Dict[str, Tuple[int, ...]]:
        c, n, h = self.c, self.n, self.n_heads
        out: Dict[str, Tuple[int, ...]] = {}
        for hc in ("hca_", "hcm_") if self.residual == "hc" else ():
            out.update({hc + "p_pre": (n * c, n), hc + "p_post": (n * c, n),
                        hc + "p_res": (n * c, n * n), hc + "a_pre": (1,),
                        hc + "a_post": (1,), hc + "a_res": (1,),
                        hc + "b_pre": (n,), hc + "b_post": (n,),
                        hc + "b_res": (n, n)})
        if self.attention == "latent":
            out.update({
                "attn_norm": (c,), "attn_w_dq": (c, self.q_rank),
                "attn_q_norm": (self.q_rank,),
                "attn_w_uq": (self.q_rank, h * (self.nope + self.rope)),
                "attn_w_dkv": (c, self.kv_rank + self.rope),
                "attn_kv_norm": (self.kv_rank,),
                "attn_w_ukv": (self.kv_rank, h * (self.nope + self.v_dim)),
                "attn_w_o": (h * self.v_dim, c)})
        elif self.attention == "gated":
            d, kv = self.head_dim, self.kv_heads
            out.update({
                "attn_norm": (c,), "attn_w_q": (c, h * 2 * d),
                "attn_w_k": (c, kv * d), "attn_w_v": (c, kv * d),
                "attn_q_norm": (d,), "attn_k_norm": (d,),
                "attn_w_o": (h * d, c)})
        elif self.attention == "gated_delta":
            kw = self.key_heads * self.key_dim
            vw = self.value_heads * self.value_dim
            out.update({
                "attn_norm": (c,), "attn_w_qkvz": (c, 2 * kw + 2 * vw),
                "attn_w_ba": (c, 2 * self.value_heads),
                "attn_conv": (self.conv_kernel, 2 * kw + vw),
                "attn_a_log": (self.value_heads,),
                "attn_dt_bias": (self.value_heads,),
                "attn_o_norm": (self.value_dim,), "attn_w_o": (vw, c)})
        else:
            d, kv = self.head_dim, self.kv_heads
            hi, di = self.index_heads, self.index_dim
            out.update({
                "attn_norm": (c,), "attn_w_q": (c, h * d),
                "attn_w_k": (c, kv * d), "attn_w_v": (c, kv * d),
                "attn_q_norm": (d,), "attn_k_norm": (d,),
                "attn_w_o": (h * d, c), "attn_idx_w_q": (c, hi * di),
                "attn_idx_w_k": (c, di), "attn_idx_k_norm": (di,),
                "attn_idx_k_bias": (di,), "attn_idx_w_w": (c, hi)})
        w = self.width
        if self.ffn == "dense":
            out.update({"mlp_norm": (c,), "mlp_w_gate": (c, w),
                        "mlp_w_up": (c, w), "mlp_w_down": (w, c)})
        else:
            e = self.held[1]
            out.update({
                "moe_norm": (c,), "moe_w_router": (c, self.n_experts),
                "moe_experts_gate": (e, c, w),
                "moe_experts_up": (e, c, w), "moe_experts_down": (e, w, c)})
            if self.shared:
                out.update({"moe_shared_gate": (c, w),
                            "moe_shared_up": (c, w),
                            "moe_shared_down": (w, c)})
            if self.shared_gate:
                # (a matrix of one column: a leaf of one dimension is
                # a bias to the optimizer)
                out["moe_shared_mix"] = (c, 1)
        return out

    def initial(self, name: str, shape: Tuple[int, ...], fill) -> np.ndarray:
        """A leaf's initial value; `fill(shape, std)` draws the normal
        ones from the unit's generator."""
        if name == "attn_a_log":
            # Qwen3-Next's: the decay rate uniform on (0, 16)
            if not self.init_std:       # (a caller that brings its own)
                return np.zeros(shape, np.float32)
            rate = 8.0 + fill(shape, 8.0 / np.sqrt(3.0), "uniform")
            return np.log(np.maximum(rate, 1e-3)).astype(np.float32)
        if name == "attn_dt_bias":
            return np.ones(shape, np.float32)
        if name.endswith("norm"):
            # (the gated norm of a linear layer's output is a plain one)
            zero = self.norm_offset and name != "attn_o_norm"
            return np.full(shape, 0.0 if zero else 1.0, np.float32)
        if name.endswith("_bias"):
            return np.zeros(shape, np.float32)
        if name[-5:] in ("a_pre", "a_res") or name.endswith("a_post"):
            return np.full(shape, 0.01, np.float32)
        for b, value in ol.hc_init_biases(self.n).items():
            if name.endswith(b):
                return value
        return fill(shape, self.init_std)

    def aux_shapes(self, batch: int = 1, seq: int = 8
                   ) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """Step state beside the parameters, for `batch` sequences of
        `seq` tokens. An expert layer: the selection bias (under that
        rule), the slots every expert was given so far, the sum over
        steps of the fullest held expert's slots, slots not computed,
        steps counted, the last step's selected experts. Indexed
        attention: the (query, key) pairs that were causal, selected and
        scored so far, each as (2^20s, rest) since int32 holds 16 steps
        of them, and the last step's selection, 8 keys a byte."""
        out: Dict[str, Tuple[Tuple[int, ...], Any]] = {}
        if self.attention == "indexed":
            out.update({"pairs_" + k: ((2,), np.int32)
                        for k in ("causal", "selected", "scored")})
            out["selected"] = ((batch * seq, seq // 8), np.uint8)
            if self.ffn != "experts":
                out["steps"] = ((1,), np.int32)
        if self.attention == "gated_delta":
            # tokens and chunks walked so far; of the last step the final
            # state's root mean square and the lowest cumulative
            # log-decay a chunk reached
            out.update({"gdn_tokens": ((1,), np.int32),
                        "gdn_chunks": ((1,), np.int32),
                        "gdn_state_rms": ((1,), np.float32),
                        "gdn_decay_min": ((1,), np.float32),
                        # the last step's final state itself, which a
                        # reference's recurrence can be held against
                        "gdn_state": ((batch, self.value_heads,
                                       self.key_dim, self.value_dim),
                                      np.float32)})
            if self.ffn != "experts":
                out["steps"] = ((1,), np.int32)
        if self.ffn == "experts":
            e = self.n_experts
            if self.scoring == "sigmoid_bias":
                out["bias"] = ((e,), np.float32)
            out.update({"load": ((e,), np.int32),
                        "fullest": ((1,), np.int32),
                        "dropped": ((1,), np.int32),
                        "steps": ((1,), np.int32),
                        "picked": ((batch * seq, self.top_k), np.int32)})
        return out

    # -- forward -----------------------------------------------------------------

    def _hc(self, p: Dict[str, Any], prefix: str, x, f, lowering):
        """The residual path around the sub-layer `f`: x (T, n*C) ->
        (x, f's extra). One hyper-connection under `lowering`
        (`lowerings`' `hc`), or x + f(x)."""
        if self.residual == "plain":
            y, extra = f(x)
            return x + y, extra
        return variants.get("hc", lowering).apply(
            {k[len(prefix):]: v for k, v in p.items()
             if k.startswith(prefix)}, x, f, self.n,
            iters=self.sinkhorn_iters, eps=self.hc_eps,
            clamp=self.hc_clamp, norm_eps=self.norm_eps)

    def _attention(self, p: Dict[str, Any], h, batch: int,
                   low: Dict[str, str]):
        """The token mixer, its registry op under `low`'s lowering
        (`lowerings`; a Gated DeltaNet resolves none)."""
        if self.attention == "indexed":
            return self._indexed_attention(p, h, batch, low["dsa"])
        if self.attention == "gated":
            return self._gated_attention(p, h, batch, low["flash_attn"])
        if self.attention == "gated_delta":
            return self._gated_delta(p, h, batch)
        lowering = low["flash_attn"]
        with jax.named_scope("mla"):
            seq = h.shape[0] // batch
            rs = self.rope_scaling
            factor = rs.get("factor", 1.0)
            inv_freq = ol.yarn_inv_freq(
                self.rope, self.rope_theta, factor,
                rs.get("original_max_position_embeddings", seq),
                rs.get("beta_fast", 32), rs.get("beta_slow", 1))
            all_dim = ol.yarn_mscale(factor, rs.get("mscale_all_dim", 0))
            cos, sin = ol.rope_tables(
                seq, inv_freq,
                ol.yarn_mscale(factor, rs.get("mscale", 1)) / all_dim)
            hn = ol.rms_norm(h, p["attn_norm"], self.norm_eps)
            y = oa.latent_attention(
                _under(p, "attn_"),
                hn.reshape(batch, seq, self.c), n_heads=self.n_heads,
                nope=self.nope, rope=self.rope, v_dim=self.v_dim, cos=cos,
                sin=sin, scale=(self.nope + self.rope) ** -0.5
                * all_dim * all_dim, norm_eps=self.norm_eps,
                flash=None if lowering == "xla_blocked"
                else variants.get("flash_attn", lowering).apply)
            return y.reshape(h.shape), None

    def _norm(self, x, scale):
        return ol.rms_norm(x, scale, self.norm_eps, offset=self.norm_offset)

    def _gated_attention(self, p: Dict[str, Any], h, batch: int, lowering):
        with jax.named_scope("attn"):
            seq = h.shape[0] // batch
            y = oa.gated_attention(
                _under(p, "attn_"),
                self._norm(h, p["attn_norm"]).reshape(batch, seq, self.c),
                n_heads=self.n_heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, rotary_dim=self.rotary_dim,
                rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                norm_offset=self.norm_offset,
                flash=None if lowering == "xla_blocked"
                else variants.get("flash_attn", lowering).apply)
            return y.reshape(h.shape), None

    def _gated_delta(self, p: Dict[str, Any], h, batch: int):
        """The Gated DeltaNet on `scan_groups` groups of the sequences, one
        after another (a `lax.map`; 1: all at once), each under a
        `jax.checkpoint` of its own: the layer's insides exist for one
        group at a time, forward and backward, its chain along the sequence
        is that many times as long, and the block's checkpoint keeps the
        layer's output by name (`ops.linear_attention.GDN_OUT`), so that
        the layer is formed again once, by its own checkpoint, not twice."""
        with jax.named_scope("gdn"):
            seq = h.shape[0] // batch
            own = _under(p, "attn_")
            hn = self._norm(h, p["attn_norm"]).reshape(batch, seq, self.c)

            groups = self.scan_groups
            if batch % groups:
                raise ValueError(f"{batch} sequences do not divide into "
                                 f"{groups} groups")
            y, seen = jax.lax.map(
                jax.checkpoint(lambda x: la.gated_delta_net(
                    own, x, key_heads=self.key_heads,
                    value_heads=self.value_heads, key_dim=self.key_dim,
                    value_dim=self.value_dim, chunk=self.chunk,
                    norm_eps=self.norm_eps,
                    kernels=variants.kernels_ok(self))),
                hn.reshape(groups, batch // groups, seq, self.c))
            y = checkpoint_name(y.reshape(batch, seq, self.c), la.GDN_OUT)
            return y.reshape(h.shape), {
                "gdn_state_rms": jnp.sqrt(jnp.mean(jnp.square(
                    seen["gdn_state_rms"]))),
                "gdn_decay_min": seen["gdn_decay_min"].min(),
                "gdn_state": seen["gdn_state"].reshape(
                    (batch,) + seen["gdn_state"].shape[2:]),
                "gdn_tokens": jnp.asarray(batch * seq, jnp.int32),
                "gdn_chunks": jnp.asarray(
                    batch * la.chunks_of(seq, self.chunk)[1], jnp.int32)}

    def _indexed_attention(self, p: Dict[str, Any], h, batch: int,
                           lowering):
        with jax.named_scope("dsa"):
            seq = h.shape[0] // batch
            hn = ol.rms_norm(h, p["attn_norm"], self.norm_eps)
            y, extra = variants.get("dsa", lowering).apply(
                _under(p, "attn_"),
                hn.reshape(batch, seq, self.c), n_heads=self.n_heads,
                kv_heads=self.kv_heads, head_dim=self.head_dim,
                index_heads=self.index_heads, index_dim=self.index_dim,
                topk=self.index_topk, rope_theta=self.rope_theta,
                query_block=self.query_block, key_bands=self.key_bands,
                norm_eps=self.norm_eps)
            return y.reshape(h.shape), extra

    def fast_rows(self, tokens: int) -> int:
        even = tokens * self.top_k * self.held[1] / max(self.n_experts, 1)
        return int(np.ceil(FAST_ROWS_HEADROOM[self.scoring] * even))

    def _experts(self, p: Dict[str, Any], h, bias):
        with jax.named_scope("moe"):
            hn = self._norm(h, p["moe_norm"])
            out = {}
            with jax.named_scope("router"):
                logits = jnp.matmul(hn, p["moe_w_router"],
                                    preferred_element_type=jnp.float32)
                if self.scoring == "softmax":
                    probs, idx, gates = om.softmax_topk_gates(
                        logits, self.top_k)
                    gates = self.routed_scaling * gates
                else:
                    idx, picked = om.route_topk(
                        jax.nn.sigmoid(logits),
                        0.0 if bias is None else bias, self.top_k)
                    gates = self.routed_scaling * picked \
                        / (picked.sum(axis=-1, keepdims=True) + 1e-20)
                load = om.expert_loads(idx, self.n_experts)
            if self.scoring == "softmax":
                out["router_probs"] = probs     # for `apply`'s balance loss
            with jax.named_scope("experts"):
                y, dropped = om.held_experts_swiglu(
                    hn, idx, gates, p["moe_experts_gate"],
                    p["moe_experts_up"], p["moe_experts_down"], self.held,
                    self.fast_rows(h.shape[0]), self.grouped,
                    variants.kernels_ok(self))
            if self.shared:
                with jax.named_scope("shared"):
                    ys = ol.swiglu(hn, p["moe_shared_gate"],
                                   p["moe_shared_up"], p["moe_shared_down"])
                    if self.shared_gate:
                        # a sigmoid gate of its own a token (Qwen2-MoE's)
                        mix = jax.nn.sigmoid(jnp.matmul(
                            hn, p["moe_shared_mix"],
                            preferred_element_type=jnp.float32))
                        ys = (ys.astype(jnp.float32) * mix).astype(ys.dtype)
                    y = y + ys
        return y, {**out, "load": load, "dropped": dropped,
                   "picked": idx.astype(jnp.int32)}

    def _mlp(self, p: Dict[str, Any], h):
        with jax.named_scope("mlp"):
            hn = self._norm(h, p["mlp_norm"])
            return ol.swiglu(hn, p["mlp_w_gate"], p["mlp_w_up"],
                             p["mlp_w_down"]), None

    def apply(self, p: Dict[str, Any], x, bias=None):
        """x (N, S, n*C) -> (x, what the block counted or None: an expert
        layer's loads and selected experts, indexed attention's selection;
        with them the block's terms of the loss, `LOSS_TERMS`)."""
        batch = x.shape[0]
        low = self.lowerings(*x.shape[:2])
        hc = low.get("hc")
        flat = x.reshape(-1, x.shape[-1])
        flat, seen = self._hc(
            p, "hca_", flat, lambda h: self._attention(p, h, batch, low), hc)
        if self.ffn == "dense":
            flat, out = self._hc(p, "hcm_", flat, lambda h: self._mlp(p, h),
                                 hc)
        else:
            flat, out = self._hc(p, "hcm_", flat,
                                 lambda h: self._experts(p, h, bias), hc)
        if out and "router_probs" in out:
            # of each sequence, which the expert layer does not know
            with jax.named_scope("moe"), jax.named_scope("balance_loss"):
                out["balance_loss"] = om.balance_loss(
                    out.pop("router_probs"), out["picked"], batch)
        if seen is not None:
            out = {**seen, **(out or {})}
        return flat.reshape(x.shape), out

    def aux_update(self, aux: Dict[str, Any], out: Dict[str, Any]
                   ) -> Dict[str, Any]:
        """After a step: b_e <- b_e + u sign(mean load - load_e) from the
        step's loads over all experts, and the counters."""
        new = {"steps": aux["steps"] + 1}
        if "selected" in aux:
            s = aux["selected"].shape[1] * 8
            causal = aux["selected"].shape[0] // s * (s * (s + 1) // 2)
            # (every causal pair of a tile it visits is scored: the masked
            # dense form visits them all)
            new.update({
                "pairs_causal": _add_wide(aux["pairs_causal"], causal),
                "pairs_scored": _add_wide(aux["pairs_scored"], causal),
                "pairs_selected": _add_wide(aux["pairs_selected"],
                                            out["pairs_selected"]),
                "selected": out["selected"]})
        if "gdn_tokens" in aux:
            new.update({
                "gdn_tokens": aux["gdn_tokens"] + out["gdn_tokens"],
                "gdn_chunks": aux["gdn_chunks"] + out["gdn_chunks"],
                "gdn_state": out["gdn_state"],
                **{k: out[k].reshape(1).astype(jnp.float32)
                   for k in ("gdn_state_rms", "gdn_decay_min")}})
        if "load" in aux:
            load = out["load"]
            lf = load.astype(jnp.float32)
            first, count = self.held
            if "bias" in aux:
                new["bias"] = aux["bias"] + self.bias_update_speed \
                    * jnp.sign(lf.mean() - lf)
            new.update({
                "load": aux["load"] + load,
                "fullest": aux["fullest"] + load[first:first + count].max(),
                "dropped": aux["dropped"]
                + out["dropped"].astype(jnp.int32),
                "picked": out["picked"]})
        return new


def _under(p: Dict[str, Any], prefix: str) -> Dict[str, Any]:
    """The leaves of `p` whose names start with `prefix`, without it."""
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


WIDE = 20


def _add_wide(acc, n):
    """acc (2,) int32 = (2^20s, rest below 2^20) plus `n`, a count below
    2^31 (a Python int or an int32)."""
    rest = acc[1] + (n & ((1 << WIDE) - 1))
    return jnp.stack([acc[0] + (n >> WIDE) + (rest >> WIDE),
                      rest & ((1 << WIDE) - 1)]).astype(jnp.int32)


def _wide(acc) -> int:
    return (int(acc[0]) << WIDE) + int(acc[1])


#: what a block's `jax.checkpoint` saves: the names of either kind of
#: attention, so that the backward pass does not attend a second time (a
#: lowering that names nothing saves nothing), and the held experts'
#: output, so that a hyper-connection's backward, which asks for its
#: sub-layer's output, does not run their products a third time (a plain
#: residual path asks for nothing, and nothing is kept for it), and a
#: Gated DeltaNet layer's output, which the layer's own checkpoints (a
#: group of sequences each) would otherwise form a second time. ONE object
#: for every block and for the head's MTP block: `jax.checkpoint` splits a
#: jitted kernel's jaxpr by its policy and caches the split by the policy's
#: identity, so a policy made anew a block leaves the step with a copy of
#: every kernel's body a block (28 of `veles_dsa_pmean` in the six-block
#: step, lowered here for a described v5e)
_SAVED_POLICY = jax.checkpoint_policies.save_only_these_names(
    *oa.DSA_SAVED, *oa.FLASH_SAVED, *om.MOE_SAVED, la.GDN_OUT)


def _gaussian(unit):
    """Normal at `std` from the unit's generator; zeros at `std` 0 (a
    caller that brings its own weights pays for no draw)."""
    return lambda shape, std, filling="gaussian": (
        unit._fill(shape, filling, std) if std
        else np.zeros(shape, np.float32))


class _LMUnit(Forward):
    """Parameters by name from a shape table; the fused step only."""

    def _block(self) -> Optional[BlockSpec]:
        """The unit's block with the fused step's word on Pallas kernels
        handed on to it (the step sets the unit's `allow_pallas` before
        every trace and report); None for a head without its MTP
        block."""
        if self.spec is not None:
            self.spec.allow_pallas = getattr(self, "allow_pallas", True)
        return self.spec

    def variant_more(self) -> Dict[str, str]:
        """What the registry ops the unit's block resolves trace, for
        `variant_table()` (`BlockSpec.lowerings`)."""
        spec = self._block()
        if spec is None or not self.input:
            return {}
        return spec.lowerings(*self.input.shape[:2])

    def variant_effective(self) -> Optional[str]:
        """The same for the unit's `variant_op` alone; None where the
        block does not resolve it."""
        return self.variant_more().get(self.variant_op)

    def _make_arrays(self, names: Sequence[str], aux: Sequence[str] = ()
                     ) -> None:
        self._pnames, self._anames = tuple(names), tuple(aux)
        for name in self._pnames + tuple("aux_" + a for a in self._anames):
            setattr(self, name, Array())

    def param_arrays(self) -> Dict[str, Array]:
        return {name: getattr(self, name) for name in self._pnames}

    def _apply(self, params, x):
        raise NotImplementedError(
            f"{type(self).__name__} trains through the fused step only")

    def numpy_run(self) -> None:
        self._apply(None, None)

    xla_run = numpy_run

    def xla_init(self):
        return None


class TokenEmbedding(_LMUnit):
    """ids (N, S) int32 -> the dict the blocks pass on: `x`, every
    token's row of `weights` (vocab, features) copied to the `streams`
    residual streams, (N, S, streams * features); `ids`; `table`."""

    #: the fused step hands this unit its input as it came: no input
    #: normalisation, no cast to the compute dtype
    fused_integer_input = True

    def __init__(self, workflow=None, vocab: int = 256, features: int = 64,
                 streams: int = 1, init_std: float = 0.02,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.vocab, self.features, self.streams = vocab, features, streams
        self.init_std = init_std
        self._make_arrays(("weights",))

    def initialize(self, device=None, **kwargs: Any):
        if not self.input:
            return False
        n, s = self.input.shape[:2]
        if not self.weights:
            self.weights.reset(_gaussian(self)(
                (self.vocab, self.features), self.init_std))
        shape = (n, s, self.streams * self.features)
        if not self.output or self.output.shape != shape:
            self.output.reset(np.zeros(shape, np.float32))
        return super().initialize(device=device, **kwargs)

    def fused_apply(self, params, x, *, key=None, train=True):
        table = params["weights"]
        rows = jnp.take(table, x.astype(jnp.int32), axis=0)
        return {"x": jnp.tile(rows, (1, 1, self.streams)),
                "ids": x.astype(jnp.int32), "table": table}


class HCBlock(_LMUnit):
    """One block (module docstring). Layer-table keys are `BlockSpec`'s,
    `features` aside, which the input gives."""

    #: the fused step wraps this unit's forward in `jax.checkpoint`
    fused_remat = True
    #: the registry op its two hyper-connections resolve at trace time
    #: (xla | pallas_one_pass); no `variant_signature`: the autotuner times
    #: nothing here, the platform and the shape decide
    variant_op = "hc"

    def __init__(self, workflow=None, streams: int = 1, **kwargs: Any
                 ) -> None:
        spec_keys = ("n_heads", "q_rank", "kv_rank", "nope", "rope",
                     "v_dim", "ffn", "width", "n_experts", "held", "top_k",
                     "routed_scaling", "bias_update_speed", "rope_theta",
                     "rope_scaling", "sinkhorn_iters", "hc_eps", "hc_clamp",
                     "norm_eps", "init_std", "residual", "attention",
                     "scoring", "shared", "kv_heads", "head_dim",
                     "index_heads", "index_dim", "index_topk",
                     "query_block", "key_bands", "grouped", "rotary_dim",
                     "key_heads", "value_heads", "key_dim", "value_dim",
                     "conv_kernel", "chunk", "scan_groups",
                     "norm", "shared_gate")
        self._spec_kw = {k: kwargs.pop(k) for k in spec_keys if k in kwargs}
        super().__init__(workflow, **kwargs)
        self.streams = streams
        self.spec: Optional[BlockSpec] = None
        if self._spec_kw.get("attention") == "indexed":
            self.variant_op = "dsa"     # (one op a unit: the costlier one)
        probe = BlockSpec(features=1, streams=streams, **self._spec_kw)
        self._make_arrays(tuple(probe.shapes()), tuple(probe.aux_shapes()))

    def aux_arrays(self) -> Dict[str, Array]:
        return {a: getattr(self, "aux_" + a) for a in self._anames}

    def initialize(self, device=None, **kwargs: Any):
        if not self.input:
            return False
        n, s, width = self.input.shape
        self.spec = BlockSpec(features=width // self.streams,
                              streams=self.streams, **self._spec_kw)
        _init_leaves(self, self.spec, "", n, s)
        if not self.output or self.output.shape != (n, s, width):
            self.output.reset(np.zeros((n, s, width), np.float32))
        return super().initialize(device=device, **kwargs)

    #: what the step's `jax.checkpoint` around this unit saves: indexed
    #: attention's thresholds and outputs, the flash kernels' outputs and
    #: logsumexps, so that the backward pass neither selects nor attends a
    #: second time, the held experts' output where the residual path's
    #: backward reads it, and a Gated DeltaNet layer's output
    fused_remat_policy = staticmethod(_SAVED_POLICY)

    #: leaves the fused step hands on in their master dtype whatever the
    #: compute dtype: a linear layer's decay is float32
    fused_float32_params = ("attn_a_log", "attn_dt_bias")

    def fused_apply(self, params, x, *, key=None, train=True, aux=None):
        y, out = self._block().apply(
            params, x["x"], None if aux is None else aux.get("bias"))
        y = {**x, "x": y}
        terms = {name: out.pop(key) for key, name in
                 BlockSpec.LOSS_TERMS.items() if out and key in out}
        if terms:
            # differentiable, so they ride with the activations to the
            # head that owns the loss (`counted` is state no gradient
            # touches)
            y["terms"] = x.get("terms", ()) + (terms,)
        return y if aux is None else (y, out)

    def fused_aux_update(self, aux, out):
        return self.spec.aux_update(aux, out)


def _init_leaves(unit: _LMUnit, spec: BlockSpec, prefix: str,
                 batch: int, seq: int) -> None:
    """Fill the unit's still empty Arrays of one block."""
    for name, shape in spec.shapes().items():
        arr = getattr(unit, prefix + name)
        if not arr:
            arr.reset(spec.initial(name, shape, _gaussian(unit)))
    for name, (shape, dtype) in spec.aux_shapes(batch, seq).items():
        arr = getattr(unit, "aux_" + prefix + name)
        if not arr:
            arr.reset(np.zeros(shape, dtype))


class LMHead(_LMUnit):
    """The read-out and the losses. The streams are summed, normed and
    multiplied by the untied head `weights` (features, vocab); the loss is
    the mean cross-entropy of the next token, a `loss_chunk` of tokens at a
    time (`ops.lm.chunked_ce`). With `mtp` (a dict of `BlockSpec`'s keys,
    `ffn` "experts") one multi-token-prediction module (DeepSeek-V3,
    arXiv:2412.19437, section 2.2) adds `mtp_weight` times the mean
    cross-entropy of the next-next token: h' = W [RMSNorm(h);
    RMSNorm(Emb(next token))] through one block of its own and a final
    norm of its own, then the SAME head. Targets are (N, S, 2): the next
    and the next-next token (`(N, S)` without `mtp`).

    It owns its loss (`fused_emits_loss`): the fused step hands it the
    targets, the per-sample weights and the global weight sum, and gets
    (loss, tokens wrong) back. Both cross-entropies of the last step are
    kept as step state (`ce_main`, `ce_mtp`)."""

    fused_emits_loss = True
    fused_emits_logits = True       # n_classes is the vocabulary
    fused_remat = False
    variant_op = "hc"               # the MTP block's two (`HCBlock`)

    def __init__(self, workflow=None, vocab: int = 256, streams: int = 1,
                 loss_chunk: int = 1024, mtp: Optional[Dict[str, Any]] = None,
                 mtp_weight: float = 0.3,
                 term_weights: Optional[Dict[str, float]] = None,
                 norm_eps: float = 1e-6, init_std: float = 0.02,
                 norm: str = "plain", **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        if norm not in ("plain", "zero_centred"):
            raise ValueError(f"norm must be plain or zero_centred, "
                             f"not {norm!r}")
        #: 1 where the final norm's scale is stored less 1
        self.norm_offset = float(norm == "zero_centred")
        self.vocab, self.streams = vocab, streams
        self.loss_chunk, self.mtp_weight = loss_chunk, mtp_weight
        self.norm_eps, self.init_std = norm_eps, init_std
        #: {term: weight} of the blocks' terms of the loss
        #: (`BlockSpec.LOSS_TERMS`): `balance` is weighted as the MEAN
        #: over the blocks that hand one on, any other as their SUM
        self.term_weights = dict(term_weights or {})
        self.mtp_kw = dict(mtp) if mtp else None
        self.spec: Optional[BlockSpec] = None
        names, aux = ["final_norm", "weights"], ["ce_main", "ce_mtp"]
        aux += ["term_" + t for t in sorted(self.term_weights)]
        if self.mtp_kw:
            probe = BlockSpec(features=1, streams=streams, **self.mtp_kw)
            names += ["mtp_norm_h", "mtp_norm_e", "mtp_w_proj",
                      "mtp_final_norm"]
            names += ["mtp_" + k for k in probe.shapes()]
            aux += ["mtp_" + k for k in probe.aux_shapes()]
        self._make_arrays(names, aux)

    def aux_arrays(self) -> Dict[str, Array]:
        return {a: getattr(self, "aux_" + a) for a in self._anames}

    def initialize(self, device=None, **kwargs: Any):
        if not self.input:
            return False
        n, s, width = self.input.shape
        c = width // self.streams
        fill = _gaussian(self)
        own = {"final_norm": (c,), "weights": (c, self.vocab)}
        if self.mtp_kw:
            own.update({"mtp_norm_h": (c,), "mtp_norm_e": (c,),
                        "mtp_w_proj": (2 * c, c), "mtp_final_norm": (c,)})
            self.spec = BlockSpec(features=c, streams=self.streams,
                                  **self.mtp_kw)
            _init_leaves(self, self.spec, "mtp_", n, s)
        for name, shape in own.items():
            arr = getattr(self, name)
            if not arr:
                arr.reset(np.full(shape, 1.0 - self.norm_offset
                                  if name == "final_norm" else 1.0,
                                  np.float32) if len(shape) == 1
                          else fill(shape, self.init_std))
        for name in self._anames:
            if not name.startswith("mtp_") and not getattr(self,
                                                           "aux_" + name):
                getattr(self, "aux_" + name).reset(np.zeros((1,),
                                                            np.float32))
        if not self.output or self.output.shape != (n, self.vocab):
            # the evaluator's view; the fused step never fills it
            self.output.reset(np.zeros((n, self.vocab), np.float32))
        return super().initialize(device=device, **kwargs)

    def _read_out(self, x):
        c = x.shape[-1] // self.streams
        return sum(x[..., i * c:(i + 1) * c].astype(jnp.float32)
                   for i in range(self.streams)).astype(x.dtype)

    def fused_apply(self, params, x, *, key=None, train=True, aux=None,
                    targets=None, weights=None, denom=None):
        p = params
        n, s = x["ids"].shape
        trunk = self._read_out(x["x"]).reshape(n * s, -1)
        targets = targets.reshape(n, s, -1).astype(jnp.int32)
        wt = jnp.broadcast_to(weights.astype(jnp.float32)[:, None],
                              (n, s)).reshape(-1)
        denom = jnp.maximum(denom * s, 1e-9)
        chunk = min(self.loss_chunk, n * s)
        with jax.named_scope("head"):
            ce, n_err = ol.chunked_ce(
                ol.rms_norm(trunk, p["final_norm"], self.norm_eps,
                            offset=self.norm_offset),
                p["weights"], targets[..., 0].reshape(-1), wt, chunk)
            ce = ce / denom
        out = {"ce_main": ce, "ce_mtp": jnp.zeros_like(ce)}
        extra = 0.0
        for name, weight in sorted(self.term_weights.items()):
            with jax.named_scope("terms"):
                parts = [t[name] for t in x.get("terms", ()) if name in t]
                term = jnp.asarray(sum(parts), jnp.float32) \
                    / (len(parts) if name == "balance" and parts else 1)
                out["term_" + name] = term
                extra = extra + weight * term
        if self.spec is None:
            loss = ce + extra
            return (loss, n_err) if aux is None else ((loss, n_err), out)
        with jax.named_scope("mtp"):
            with jax.named_scope("proj"):
                nxt = jnp.take(x["table"], targets[..., 0].reshape(-1),
                               axis=0)
                joined = jnp.concatenate(
                    [ol.rms_norm(trunk, p["mtp_norm_h"], self.norm_eps),
                     ol.rms_norm(nxt, p["mtp_norm_e"], self.norm_eps)],
                    axis=-1)
                h = ol.mm(joined, p["mtp_w_proj"])
                x2 = jnp.tile(h, (1, self.streams)).reshape(n, s, -1)
            block = jax.checkpoint(self._block().apply,
                                   policy=_SAVED_POLICY)
            x2, counted = block(
                {k[len("mtp_"):]: v for k, v in p.items()
                 if k.startswith("mtp_")}, x2,
                None if aux is None else aux["mtp_bias"])
            with jax.named_scope("head"):
                ce2, _ = ol.chunked_ce(
                    ol.rms_norm(self._read_out(x2).reshape(n * s, -1),
                                p["mtp_final_norm"], self.norm_eps),
                    p["weights"], targets[..., 1].reshape(-1), wt, chunk)
                ce2 = ce2 / denom
        out["ce_mtp"] = ce2
        out.update({"mtp_" + k: v for k, v in counted.items()})
        loss = ce + self.mtp_weight * ce2 + extra
        return (loss, n_err) if aux is None else ((loss, n_err), out)

    def fused_aux_update(self, aux, out):
        new = {k: v.reshape(1).astype(jnp.float32) for k, v in out.items()
               if not k.startswith("mtp_")}
        if self.spec is not None:
            cut = len("mtp_")
            moved = self.spec.aux_update(
                {k[cut:]: v for k, v in aux.items() if k.startswith("mtp_")},
                {k[cut:]: v for k, v in out.items() if k.startswith("mtp_")})
            new.update({"mtp_" + k: v for k, v in moved.items()})
        return new


def moe_counts(step, aux) -> Dict[str, Dict[str, int]]:
    """{layer: {steps, slots, held, fullest, dropped}} so far, from the
    host copy `aux` of a fused step's `state["aux"]`: one entry per expert
    layer, named by its unit's scope (`L02`), the head's module `mtp`."""
    out = {}
    for scope, u, a in zip(step.scopes, step.forwards, aux):
        spec = getattr(u, "spec", None)
        if spec is None or spec.ffn != "experts":
            continue
        name, pre = (("mtp", "mtp_") if isinstance(u, LMHead)
                     else (scope.split(".")[0], ""))
        load = np.asarray(a[pre + "load"], np.int64)
        first, count = spec.held
        out[name] = {"steps": int(a[pre + "steps"][0]),
                     "slots": int(load.sum()),
                     "held": int(load[first:first + count].sum()),
                     "fullest": int(a[pre + "fullest"][0]),
                     "dropped": int(a[pre + "dropped"][0])}
    return out


def publish_moe_counters(now: Dict[str, Dict[str, int]],
                         base: Optional[Dict[str, Dict[str, int]]] = None,
                         balance_reached: Optional[bool] = None) -> None:
    """Set the `veles_moe_*` counters (`telemetry/metrics.py`) to what
    `moe_counts` read `now`, less what it read at `base`."""
    from veles_tpu.telemetry import metrics
    h = metrics.moe_handles()
    dropped = 0
    for layer, c in now.items():
        b = (base or {}).get(layer, dict.fromkeys(c, 0))
        for key, fam in (("steps", h.steps), ("slots", h.slots),
                         ("held", h.held), ("fullest", h.fullest)):
            fam.labels(layer=layer).set_total(c[key] - b[key])
        dropped += c["dropped"] - b["dropped"]
    h.dropped.set_total(dropped)
    if balance_reached is not None:
        h.reached.set(1.0 if balance_reached else 0.0)


def gdn_counts(step, aux) -> Dict[str, Dict[str, float]]:
    """{layer: {steps, tokens, chunks, state_rms, decay_min}} from the
    host copy `aux` of a fused step's `state["aux"]`: one entry per Gated
    DeltaNet block, named by its unit's scope (`L01`). Tokens and chunks
    are counted so far; the final state's root mean square and the lowest
    cumulative log-decay of a chunk are the last step's."""
    out = {}
    for scope, u, a in zip(step.scopes, step.forwards, aux):
        spec = getattr(u, "spec", None)
        if spec is None or spec.attention != "gated_delta" \
                or isinstance(u, LMHead):
            continue
        out[scope.split(".")[0]] = {
            "steps": int(a["steps"][0]), "tokens": int(a["gdn_tokens"][0]),
            "chunks": int(a["gdn_chunks"][0]),
            "state_rms": float(a["gdn_state_rms"][0]),
            "decay_min": float(a["gdn_decay_min"][0])}
    return out


def publish_gdn_counters(now: Dict[str, Dict[str, float]],
                         base: Optional[Dict[str, Dict[str, float]]] = None
                         ) -> None:
    """Set the `veles_gdn_*` families (`telemetry/metrics.py`) to what
    `gdn_counts` read `now`, the counters less what it read at `base`."""
    from veles_tpu.telemetry import metrics
    h = metrics.gdn_handles()
    for layer, c in now.items():
        b = (base or {}).get(layer, dict.fromkeys(c, 0))
        for key, fam in (("steps", h.steps), ("tokens", h.tokens),
                         ("chunks", h.chunks)):
            fam.labels(layer=layer).set_total(c[key] - b[key])
        h.state_rms.labels(layer=layer).set(c["state_rms"])
        h.decay_min.labels(layer=layer).set(c["decay_min"])


def dsa_counts(step, aux) -> Dict[str, Dict[str, int]]:
    """{layer: {steps, causal, selected, scored}} so far, from the host
    copy `aux` of a fused step's `state["aux"]`: one entry per block of
    indexed attention, named by its unit's scope (`L02`); the counts are
    (query, key) pairs."""
    out = {}
    for scope, u, a in zip(step.scopes, step.forwards, aux):
        spec = getattr(u, "spec", None)
        if spec is None or spec.attention != "indexed" \
                or isinstance(u, LMHead):
            continue
        out[scope.split(".")[0]] = {
            "steps": int(a["steps"][0]),
            **{k: _wide(a["pairs_" + k])
               for k in ("causal", "selected", "scored")}}
    return out


def publish_dsa_counters(now: Dict[str, Dict[str, int]],
                         base: Optional[Dict[str, Dict[str, int]]] = None
                         ) -> None:
    """Set the `veles_dsa_*` counters (`telemetry/metrics.py`) to what
    `dsa_counts` read `now`, less what it read at `base`."""
    from veles_tpu.telemetry import metrics
    h = metrics.dsa_handles()
    for layer, c in now.items():
        b = (base or {}).get(layer, dict.fromkeys(c, 0))
        for key, fam in (("steps", h.steps), ("causal", h.causal),
                         ("selected", h.selected), ("scored", h.scored)):
            fam.labels(layer=layer).set_total(c[key] - b[key])


@register_gd(TokenEmbedding)
class GDTokenEmbedding(GradientDescentVJP):
    pass


@register_gd(HCBlock)
class GDHCBlock(GradientDescentVJP):
    pass


@register_gd(LMHead)
class GDLMHead(GradientDescentVJP):
    pass


from veles_tpu.znicz import standard_workflow as _sw  # noqa: E402

_sw.LAYER_TYPES.update({"token_embedding": TokenEmbedding,
                        "hc_block": HCBlock, "lm_head": LMHead})
