"""FusedTrainStep: the whole fwd+bwd+update chain as ONE XLA computation.

Parity note: in the reference, one minibatch = dozens of kernel enqueues
(§3.1 hot loop) and distributed training = pickled weight deltas over
ZeroMQ (§3.2). Here the entire StandardWorkflow hot loop compiles into a
single donated jit step; on a device mesh the batch is sharded over the
"data" axis and gradient averaging is a `lax.pmean` all-reduce over ICI —
the north-star replacement (BASELINE.json:5). Tensor parallelism (absent
in the reference) shards layer output dims over "model" via GSPMD named
shardings.

Two execution modes:
- "dp"    — explicit `shard_map` over the data axis with hand-placed
            pmean/psum collectives (the guaranteed-collectives path used
            by the scaling harness);
- "gspmd" — `jax.jit` with NamedSharding annotations on params (model
            axis) and batch (data axis); XLA's SPMD partitioner inserts
            the collectives. Composes DP×TP.
- "seq"   — `shard_map` over ("data", "seq"): the batch dim rides the
            data axis and the SEQUENCE dim rides the seq axis; attention
            units run their ring/Ulysses kernels (via `seq_axis_name`),
            per-token CE averages globally through the same
            grad-transpose psum. The long-context training path.
Expert parallelism (`ep=True`, "dp" mode only) shards MoE expert tensors
over the data axis via per-param shard_map specs: each shard owns
E/n_data experts, MoE units run the all_to_all token exchange
(ops.moe.moe_forward_ep via `ep_axis_name`), and expert grads arrive
through the all_to_all transpose while replicated params keep the
broadcast-psum. The EP group IS the DP group (DeepSpeed-MoE layout).
A mesh of one device degrades to plain jit (same code path, collectives
are no-ops) — SURVEY.md §7: build size-agnostically.

ZeRO weight-update sharding ("dp" mode; arxiv 2004.13336 — the
decomposition that became XLA's weight-update sharding): instead of every
replica applying the full update after the grad all-reduce, the gradient
is reduce-SCATTERED (via the `grad_reduce` registry op), each replica
updates only its 1/N slice of params + momentum/Adam state under the
per-leaf plan in `parallel.mesh.zero_plan`, and the fresh params are
all-gathered for the next forward. Optimizer-state memory ÷N, paid for
with the gather and a relayout of every leaf: on a v5e 2x2 that is 6.3 ms
of a 75.4 ms VGG-16 step (PERF.md, PR 26), so `zero_sharding="auto"`
shards only where memory asks for it (`ZERO_AUTO_STATE_SHARE`) and traces
the replicated update otherwise. Degrades (with a logged reason, see
`zero_reason`) for local/gspmd/seq modes, EP, single-shard data axes and
multi-host meshes. The replicated dp update exchanges what the sharded one
does: each leaf's per-shard partial gradient, summed in float32 by an
explicit all-reduce under `update/<unit>/grad_exchange` — except for a
dense layer whose weights outweigh its batch of activations
(`dense_grad_form`): there every chip all-gathers the layer's operands and
forms the whole weight gradient itself (Krizhevsky 2014,
arXiv:1404.5997), and the update has nothing left to exchange.

Names in a profile: every operation of the compiled step carries a
`jax.named_scope` path that depends on the layer table only, never on a
compile — `input_normalize`, `cast_params`, one `L<index>.<layer type>`
per forward unit (`L01.norm`; a searched fused pair is one scope,
`L01.norm+L02.max_pooling`), `loss`, and `update` with the unit's scope
beneath it (on a dp mesh `grad_exchange` beneath that, and under ZeRO
`param_gather` too).
Autodiff writes the backward's `transpose(jvp(<scope>))` itself. Scopes
are metadata: the compiled program is the same (docs/OBSERVABILITY.md).
`train()` records the `train.dispatch` span with the step's number.

Numerics match the granular unit-by-unit path (tested): grads come from
`jax.grad` over the same `fused_apply` forward math, and the update is the
same `ops.optim.sgd_update` the GD units use, with each layer keeping its
own hyperparameters from its GD twin.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from jax import shard_map
from veles_tpu import prng
from veles_tpu.ops import optim
from veles_tpu.ops import xla as ox
# the Varying -> Invariant all-gather (private on the installed jax,
# imported once, in ops/xla.py). The ZeRO update needs exactly that
# type — fresh params every replica provably agrees on — so the dp step
# passes shard_map's varying-axes check instead of disabling it.
from veles_tpu.ops.xla import all_gather_invariant
from veles_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS, SEQ_AXIS,
                                     zero_flatten, zero_plan,
                                     zero_unflatten)
from veles_tpu.telemetry import tracer as _tracer


#: `zero_sharding="auto"` shards the weight update where the replicated
#: update's state (parameters, one gradient, optimizer state: 12 B a
#: parameter under SGD with momentum, 16 under Adam) passes this share of
#: the device's memory limit. The other half is what a step holds beside
#: that state: activations and the compiler's temporaries (3.7 to 4.1 GB
#: of a v5e's 16.9 GB in the benchmark's cells, a quarter of the limit:
#: `hbm_peak_gb` less state, ledger PR 25), the compute-dtype copy of the
#: parameters (2 B beside each 12 to 16 B of state, 6 to 8 % of the limit
#: at this share), and the 20 % `analysis.resources.NEAR_LIMIT_FRAC` keeps
#: free. Below it sharding buys memory nobody needs at the price of a
#: parameter all-gather and a relayout of every leaf each step (6.3 ms of
#: VGG-16's 75.4 on a v5e 2x2, 831 against 905 samples/s a chip; PERF.md,
#: PR 26).
ZERO_AUTO_STATE_SHARE = 0.5

#: What one FLOP of the gathered form's redundant matmul costs, in bytes
#: a chip sends over the mesh: the rate a v5e 2x2 sustains on the wire
#: over the rate its MXU sustains on a training step's matmuls. Wire:
#: VGG-16 FC1's float32 gradient, 411 MB all-reduced in 7.22 ms, of which
#: a ring sends 2 x 3/4 a chip: 85 GB/s (PERF.md, PR 26). MXU: 52 % of
#: 197 TFLOP/s over the whole of `vgg16.step` (ledger, PR 28): 100
#: TFLOP/s. (Where the matmul's time is the float32 weights and velocity
#: it reads and writes with its fused update, the redundant FLOP cost
#: nothing: FC1's 52.6 GFLOP at 256 rows run 2.45 ms for 1.64 GB, my chip
#: run, PR 29. The ratio prices them where nothing hides them.)
GRAD_GATHER_WIRE_BYTES_PER_FLOP = 85e9 / 100e12

#: The gathered form has to be this many times cheaper than the
#: all-reduce before a layer takes it. The chip has confirmed ratios of 6
#: and more (VGG-16's head, 16.4 MB of gradient against 2.6 MB of
#: operands, is the nearest: PERF.md, PR 29); nothing nearer to break-even
#: has been measured, and there the all-reduce has an edge the byte count
#: does not show: a summed leaf rides in the compiler's one combined
#: all-reduce, a gathered one adds two collectives of its own.
GRAD_GATHER_MIN_GAIN = 2.0


def dense_grad_wire(n: int, rows: int, fan_in: int, fan_out: int,
                    itemsize: int) -> Tuple[int, int, int]:
    """What the two ways of forming a dense layer's weight gradient over
    `n` chips of `rows` rows each put on the wire and on the MXU a step:
    (bytes all-reduced: the float32 gradient; bytes all-gathered: the
    operands `X` and `dY` of all `n` chips in the compute dtype; FLOP of
    the gathered form's matmul beyond the local one)."""
    return (4 * fan_in * fan_out,
            n * rows * (fan_in + fan_out) * itemsize,
            2 * (n - 1) * rows * fan_in * fan_out)


def dense_grad_form(n: int, rows: int, fan_in: int, fan_out: int,
                    itemsize: int) -> str:
    """"gather" or "psum" for one dense layer, from shapes and the mesh
    alone. A ring all-reduce sends 2 (n-1)/n of its bytes a chip, a ring
    all-gather (n-1)/n; the redundant FLOP are priced in wire bytes. At
    n == 1 nothing is sent either way, and the form is "psum"."""
    reduced, gathered, flop = dense_grad_wire(n, rows, fan_in, fan_out,
                                              itemsize)
    psum_cost = 2 * (n - 1) / n * reduced
    gather_cost = ((n - 1) / n * gathered
                   + GRAD_GATHER_WIRE_BYTES_PER_FLOP * flop)
    return ("gather" if GRAD_GATHER_MIN_GAIN * gather_cost < psum_cost
            else "psum")


def _unit_param_bytes(u) -> int:
    """Bytes of a forward unit's parameters, from its HOST-side arrays."""
    return sum(int(np.asarray(a.mem).nbytes)
               for a in u.param_arrays().values() if a)


def _tree_cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


def apply_input_normalize(spec, x):
    """The uint8-wire prologue affine, shared by FusedTrainStep (traced
    into the step) and PipelineTrainStep (eager device ops before
    microbatching): float conversion + scale/offset + mean subtraction
    in f32 — exactly the loaders' host `_normalize` math (loader
    wire_format contract). One implementation so the fused and pipeline
    paths can never diverge numerically. No-op when spec is None."""
    if spec is None:
        return x
    x = x.astype(jnp.float32) * spec.get("scale", 1.0) \
        + spec.get("offset", 0.0)
    mean = spec.get("mean")
    if mean is not None:
        x = x - jnp.asarray(mean, jnp.float32)
    return x


#: the base GD units keep velocities as vel_w/vel_b for the params named
#: weights/bias; every other GD twin names them vel_<param_name>
#: (vel_wq, vel_wx, vel_wr, ...). _vel_attr resolves the attribute for a
#: param name so ALL layer families round-trip momentum through fused
#: snapshots, not just {weights, bias}.
_VEL_ALIASES = {"weights": "vel_w", "bias": "vel_b"}


def _vel_attr(gd_unit, param_name: str) -> Optional[str]:
    for cand in (f"vel_{param_name}", _VEL_ALIASES.get(param_name)):
        if cand is not None and getattr(gd_unit, cand, None) is not None:
            return cand
    return None


def pair_gd_configs(workflow):
    """(gd_units, optimizer configs) aligned with workflow.forwards — each
    forward keeps its GD twin's hyperparameters (gds is built in reverse
    order by StandardWorkflow). Shared by the fused and pipeline steps.
    gd_config={"optimizer": "adam"} selects AdamConfig for a layer; the
    default is the reference SGD+momentum rule."""
    gds = list(workflow.gds)
    n = len(list(workflow.forwards))
    gd_units = [gds[n - 1 - i] for i in range(n)]
    cfgs = []
    for g in gd_units:
        if getattr(g, "optimizer", "sgd") == "adam":
            cfgs.append(optim.AdamConfig(
                lr=getattr(g, "learning_rate", 0.0),
                b1=getattr(g, "adam_beta1", 0.9),
                b2=getattr(g, "adam_beta2", 0.999),
                eps=getattr(g, "adam_eps", 1e-8),
                weight_decay=getattr(g, "weights_decay", 0.0)))
        else:
            cfgs.append(optim.SGDConfig(
                lr=getattr(g, "learning_rate", 0.0),
                momentum=getattr(g, "gradient_moment", 0.0),
                weight_decay=getattr(g, "weights_decay", 0.0),
                l1_decay=getattr(g, "l1_decay", 0.0),
                lr_bias_mult=getattr(g, "learning_rate_bias", 1.0)))
    return gd_units, cfgs


class FusedTrainStep:
    """Compile a StandardWorkflow's training chain into one sharded step.

    state = {"params": tuple-of-dicts (one per forward layer),
             "vel":    matching velocity pytree,
             "key":    jax PRNG key,
             "lr_scale": traced scalar (lr_adjust drives it, no retrace)}
    """

    def __init__(self, workflow, mesh=None, mode: str = "auto",
                 donate: bool = True,
                 compute_dtype: Optional[Any] = None,
                 ep: bool = False,
                 input_normalize: Optional[Dict[str, Any]] = None,
                 zero_sharding: Any = "auto") -> None:
        self.mesh = mesh
        #: on-device input prologue {"scale", "offset", "mean"} (the
        #: uint8-wire contract, loader wire_format/device_feed): raw
        #: integer batches are converted + affinely normalized as the
        #: first traced op, where XLA fuses it into the first layer's
        #: HBM read — the bench-e2e trick promoted into the step proper.
        #: None = inputs arrive host-normalized (the float32 wire).
        self.input_normalize = (dict(input_normalize)
                                if input_normalize else None)
        self.forwards = list(workflow.forwards)
        kinds = [spec.get("type")
                 for spec in getattr(workflow, "layers_config", ())]
        if len(kinds) != len(self.forwards) or not all(kinds):
            kinds = [type(u).__name__.lower() for u in self.forwards]
        #: each forward unit's named scope: its index and its layer-table
        #: type — the names a profile of the compiled step carries
        self.scopes = tuple(f"L{i:02d}.{k}" for i, k in enumerate(kinds))
        #: train steps dispatched: the number a train.dispatch span carries
        self.n_dispatched = 0
        self.loss_kind = workflow.loss
        self.n_classes = getattr(workflow, "n_classes", None)
        if compute_dtype is None:
            # root.common.precision_type is the reference's global
            # precision knob (SURVEY.md §2.2 dtype mapping row); it sets
            # the default compute dtype for fused steps. "float32" means
            # no cast (params are already f32 master weights).
            from veles_tpu.config import root
            pt = getattr(root.common, "precision_type", None)
            if pt and pt != "float32":
                compute_dtype = pt
        self.compute_dtype = compute_dtype
        #: the last unit computes the loss itself from the targets (a
        #: head whose logits exist a chunk of tokens at a time, a loss
        #: with a second term): `_loss_metrics` hands it targets, weights
        #: and the global weight sum and gets (loss, n_err) back
        self.unit_loss = bool(getattr(self.forwards[-1],
                                      "fused_emits_loss", False))
        #: units that carry step state beside parameters and velocity,
        #: which no gradient touches (an expert layer's selection bias
        #: and load counters): state["aux"], one dict per forward unit
        self.has_aux = any(hasattr(u, "aux_arrays") for u in self.forwards)
        if self.loss_kind == "softmax" and not getattr(
                self.forwards[-1], "fused_emits_logits", False):
            raise ValueError(
                "fused softmax loss needs an All2AllSoftmax final layer "
                "(it emits logits for log-softmax CE)")
        self.gd_units, self.cfgs = pair_gd_configs(workflow)
        if mode == "auto":
            if mesh is None:
                mode = "local"
            elif SEQ_AXIS in mesh.axis_names and mesh.shape[SEQ_AXIS] > 1:
                mode = "seq"
            elif MODEL_AXIS in mesh.axis_names \
                    and mesh.shape[MODEL_AXIS] > 1:
                mode = "gspmd"
            else:
                mode = "dp"
        if mode in ("dp", "gspmd", "seq") and mesh is None:
            raise ValueError(f"mode={mode!r} requires a mesh")
        if self.has_aux and mode != "local":
            raise ValueError(
                f"mode={mode!r}: step state beside parameters and velocity "
                "(an expert layer's selection bias, moved on the loads of "
                "ALL the step's tokens) is covered on one device only; a "
                "mesh needs the loads summed over it first")
        if mode == "seq":
            for u in self.forwards:
                if getattr(u, "parallel_mode", None) == "local":
                    raise ValueError(
                        f"{type(u).__name__} has parallel_mode='local' "
                        "under the seq-sharded step: attention would "
                        "silently stay shard-local (causality restarts "
                        "at every shard). Set parallel_mode='ring' or "
                        "'ulysses'.")
        self.mode = mode
        #: cached identity-jit that gathers cross-process shards to a
        #: replicated array (write_back's host() path); built lazily
        self._gather_fn = None
        #: cached per-n_classes confusion jits (see confusion())
        self._conf_fns = None
        # expert parallelism rides the data axis (DeepSpeed-MoE style: the
        # EP group IS the DP group): expert tensors shard over "data" in
        # the shard_map specs and MoE units run the all_to_all exchange
        if ep:
            if mode != "dp":
                raise ValueError(
                    f"ep=True needs the explicit shard_map 'dp' mode "
                    f"(got mode={mode!r}): expert tensors are sharded "
                    "via per-param shard_map specs")
            n_data = mesh.shape[DATA_AXIS]
            any_ep = False
            for u in self.forwards:
                for name in getattr(u, "ep_params", ()):
                    any_ep = True
                    e = u.param_arrays()[name].shape[0] \
                        if u.param_arrays()[name] else u.n_experts
                    if e % n_data:
                        raise ValueError(
                            f"{type(u).__name__}: {e} experts not "
                            f"divisible by the data axis ({n_data})")
            if not any_ep:
                raise ValueError(
                    "ep=True but no forward unit declares ep_params — "
                    "the step would silently run plain DP")
        self.ep = ep
        #: ZeRO update sharding (docstring above): resolved against the
        #: mode/mesh NOW so every later consumer (state specs, init,
        #: checkpoint geometry, auditor, reports) reads one verdict
        self.zero_active, self.zero_reason = \
            self._resolve_zero(zero_sharding)
        self._zero_plan_cache = None
        #: the grad_reduce variant this step traces, resolved ONCE (see
        #: _grad_reduce_variant — the EF state slot's geometry depends
        #: on it, so a mid-life registry re-selection must not split
        #: the state layout from the traced collective)
        self._gr_cache = None
        #: rows a chip of the last traced train step (what
        #: variant_table()'s `grad_exchange` reports), and the gathered
        #: units per row count (see _gathered_units)
        self._rows_traced = None
        self._gathered_cache: Dict[int, frozenset] = {}
        self.donate = donate
        self._train_fn = None
        self._eval_fn = None
        self._train_many_fn = None

    def _resolve_zero(self, req: Any) -> Tuple[bool, str]:
        """Gate the ZeRO sharded update: active only where this build
        covers it (explicit shard_map "dp" over a >1-shard single-host
        data axis, no EP). `req` is the CLI surface: "on"/True shards
        wherever that is covered and WARNs where it is not, "auto" (the
        default) shards where the replicated update's state passes
        ZERO_AUTO_STATE_SHARE of the device's memory limit and degrades
        quietly, "off"/False disables."""
        from veles_tpu.parallel.mesh import is_multihost
        if req in (False, "off"):
            return False, "zero-sharding disabled by request"
        if req not in (True, "on", "auto", None):
            raise ValueError(f"zero_sharding must be on/off/auto "
                             f"(got {req!r})")
        if self.mode != "dp":
            reason = (f"zero-sharding inactive: mode {self.mode!r} "
                      "(covered: the explicit shard_map 'dp' update; "
                      "gspmd relies on the partitioner, local has one "
                      "replica)")
        elif self.ep:
            reason = ("zero-sharding inactive: ep=True already shards "
                      "expert tensors over the data axis (the "
                      "composition is not covered by this build)")
        elif self.mesh.shape.get(DATA_AXIS, 1) < 2:
            reason = ("zero-sharding inactive: data axis has a single "
                      "shard (nothing to shard the update over)")
        elif is_multihost(self.mesh):
            reason = ("zero-sharding inactive: multi-host mesh "
                      "(cross-process sharded optimizer state is not "
                      "covered by this build)")
        elif req in (True, "on"):
            return True, "active"
        else:
            return self._zero_from_memory()
        import logging
        log = logging.getLogger("veles.fused")
        (log.warning if req in (True, "on") else log.debug)("%s", reason)
        return False, reason

    def _replicated_state_bytes(self) -> Tuple[int, int]:
        """(parameter bytes, optimizer-state bytes) one chip holds under
        the replicated update, from the units' HOST-side arrays: one
        velocity per parameter under SGD, two moments under Adam."""
        params = opt = 0
        for u, cfg in zip(self.forwards, self.cfgs):
            lb = _unit_param_bytes(u)
            params += lb
            opt += lb * (2 if isinstance(cfg, optim.AdamConfig) else 1)
        return params, opt

    def _zero_from_memory(self) -> Tuple[bool, str]:
        """`zero_sharding="auto"` on a mesh that could shard: does the
        replicated update's state (parameters, one gradient, optimizer
        state) pass ZERO_AUTO_STATE_SHARE of the device's limit?"""
        from veles_tpu.analysis.resources import device_limit
        params, opt = self._replicated_state_bytes()
        state = 2 * params + opt
        limit = device_limit()
        if limit is None:
            return False, (
                f"zero-sharding inactive: no device memory limit is known "
                f"here, so the replicated update's state of {state} B a "
                "chip has nothing to be held against")
        budget = int(ZERO_AUTO_STATE_SHARE * limit)
        held = (f"the replicated update's state is {state} B a chip "
                f"(parameters, one gradient, optimizer state) against "
                f"{budget} B, {ZERO_AUTO_STATE_SHARE:.0%} of the device's "
                f"limit of {limit} B")
        if state > budget:
            return True, f"active: {held}"
        return False, f"zero-sharding inactive: {held}"

    # -- ZeRO update-sharding plan (parallel.mesh.zero_plan) ----------------

    def zero_plans(self):
        """Per-layer {param: ZeroLeaf} plan over the data axis, from the
        units' HOST-side shapes (no device allocation) — cached: specs,
        init, the traced update, write_back and the checkpoint geometry
        all read the SAME plan."""
        if self._zero_plan_cache is None:
            n = self.mesh.shape[DATA_AXIS]
            self._zero_plan_cache = tuple(
                zero_plan({k: a.mem for k, a in u.param_arrays().items()},
                          n)
                for u in self.forwards)
        return self._zero_plan_cache

    def _grad_reduce_variant(self):
        """The grad_reduce registry variant this step traces — ONE
        resolution, cached on first read (the _sgd_variant precedent,
        hardened): the error-feedback state slot (init_state, specs,
        checkpoint geometry), the traced collective
        (_apply_update_zero), variant_table and the byte accounting all
        read the SAME verdict, so a registry re-selection between state
        construction and trace can never mis-size the state."""
        if self._gr_cache is None:
            from veles_tpu.ops import variants
            self._gr_cache = variants.resolve("grad_reduce")
        return self._gr_cache

    def ef_active(self) -> bool:
        """True when the update carries the error-feedback residual
        slot: ZeRO active and the selected grad_reduce variant is
        stateful (int8 + EF)."""
        return self.zero_active and self._grad_reduce_variant().stateful

    def ef_lens(self):
        """Per-layer {param: per-shard residual length} — the optional
        EF slot of the update-sharding plan (mesh.zero_ef_plan), sized
        by the selected variant's rule. Call only when ef_active()."""
        from veles_tpu.ops import variants
        from veles_tpu.parallel.mesh import zero_ef_plan
        name = self._grad_reduce_variant().name
        n = self.mesh.shape[DATA_AXIS]
        return tuple(
            zero_ef_plan(plan,
                         lambda padded: variants.grad_reduce_resid_len(
                             name, padded, n))
            for plan in self.zero_plans())

    def collective_accounting(self) -> Optional[Dict[str, Any]]:
        """Modeled per-device collective egress bytes per TRAIN step
        for the ZeRO grad_reduce exchange (+ the param all-gather leg),
        under the selected variant and link geometry — the producer
        behind the veles_collective_bytes_total counter family (the
        driver increments once per dispatched step;
        docs/OBSERVABILITY.md). None when no registry collective traces
        (zero inactive) — a counter fed here can never fabricate
        provenance, same rule as variant_table."""
        if not self.zero_active:
            return None
        from veles_tpu.ops import variants
        v = self._grad_reduce_variant()
        n = self.mesh.shape[DATA_AXIS]
        elems = sum(lp.padded for plan in self.zero_plans()
                    for lp in plan.values())
        acct = variants.grad_reduce_bytes(v.name, elems, n)
        acct.update(op="grad_reduce", variant=v.name, elements=elems,
                    n_shards=n)
        return acct

    def resource_profile(self) -> Dict[str, Any]:
        """Static per-device byte model of this step's persistent state
        (analysis pass 6, analysis/resources.py): params (modeled
        replicated over the data axis — exact for local/dp, an
        over-count under gspmd TP sharding, a documented blind spot),
        the transient full-size per-shard gradient, the optimizer flat
        vectors under the ZeRO plan (1/N per device, pad included) and
        the optional error-feedback residual slot. Host shapes only —
        no device allocation, callable before any compile."""
        from veles_tpu.parallel.mesh import zero_plan_local_elems
        n = (self.mesh.shape.get(DATA_AXIS, 1)
             if self.mesh is not None else 1)
        params, opt = self._replicated_state_bytes()
        ef = 0
        if self.zero_active:
            opt = sum(
                zero_plan_local_elems(plan)
                * (2 if isinstance(cfg, optim.AdamConfig) else 1) * 4
                for plan, cfg in zip(self.zero_plans(), self.cfgs))
            if self.ef_active():
                ef = sum(rl for lens in self.ef_lens()
                         for rl in lens.values()) * 4
        return {"n_data_shards": n, "params_bytes": params,
                "grads_bytes": params, "optimizer_state_bytes": opt,
                "ef_bytes": ef, "zero_active": self.zero_active}

    def optimizer_state_bytes(self, state) -> Dict[int, int]:
        """{device_id: bytes} the optimizer-state pytree (state["vel"])
        occupies per device — the measured form of the ZeRO memory claim
        (chip_smoke.py --four-chips, tests), attributed by the SAME
        shard rule as parallel.memstats (one ledger: the two can never
        silently diverge).
        Host (numpy) leaves occupy zero device bytes and are skipped —
        a measurement must never ALLOCATE device memory to take."""
        from veles_tpu.parallel.memstats import bytes_per_device
        return bytes_per_device(
            leaf for leaf in jax.tree_util.tree_leaves(state["vel"])
            if isinstance(leaf, jax.Array))

    # -- state <-> unit Arrays ----------------------------------------------

    @_tracer.in_phase("setup.init_state")
    def init_state(self) -> Dict[str, Any]:
        params = tuple(
            {k: jnp.asarray(a.mem) for k, a in u.param_arrays().items()}
            for u in self.forwards)

        zero_shard = (NamedSharding(self.mesh, P(DATA_AXIS))
                      if self.zero_active else None)

        def put_flat(flat):
            # flat (padded,) optimizer-state vector -> sharded over the
            # data axis: each device materializes only its 1/N slice
            return jax.device_put(flat, zero_shard)

        def seed_vel(u, g, p, cfg, plan):
            if isinstance(cfg, optim.AdamConfig):
                # Adam moments live only in the fused state (round-trip
                # via the sharded checkpoint, not the GD-twin Arrays)
                st = optim.adam_init(p, plan=plan)
                if plan is not None:
                    st["m"] = {k: put_flat(a) for k, a in st["m"].items()}
                    st["v"] = {k: put_flat(a) for k, a in st["v"].items()}
                return st
            # resume from the GD twin's velocity buffers when present
            # (written by write_back / restored from a snapshot)
            out = {}
            for k, a in p.items():
                vname = _vel_attr(g, k)
                varr = getattr(g, vname) if vname else None
                if plan is not None:
                    # host-side staging (np, not jnp): the sharded
                    # device_put is the FIRST device allocation, so no
                    # replica ever holds a full-size velocity leaf
                    lp = plan[k]
                    if varr is not None and varr:
                        flat = np.zeros(lp.padded, a.dtype)
                        flat[:lp.size] = \
                            np.asarray(varr.mem).reshape(-1)
                        out[k] = put_flat(flat)
                    else:
                        out[k] = put_flat(
                            np.zeros(lp.padded, a.dtype))
                elif varr is not None and varr:
                    out[k] = jnp.asarray(varr.mem)
                else:
                    out[k] = jnp.zeros_like(a)
            return out

        plans = (self.zero_plans() if self.zero_active
                 else (None,) * len(params))
        vel = tuple(seed_vel(u, g, p, c, pl) for u, g, p, c, pl in
                    zip(self.forwards, self.gd_units, params, self.cfgs,
                        plans))
        state = {"params": params, "vel": vel,
                 "key": prng.get().next_key(),
                 "lr_scale": jnp.float32(1.0)}
        if self.has_aux:
            state["aux"] = tuple(
                {k: jnp.asarray(a.mem) for k, a in u.aux_arrays().items()}
                if hasattr(u, "aux_arrays") else {} for u in self.forwards)
        if self.ef_active():
            # error-feedback residuals (stateful grad_reduce variants):
            # one flat per-shard vector per param leaf, zero at start,
            # sharded over the data axis like the rest of the ZeRO
            # state (global length = n_shards x per-shard length)
            n = self.mesh.shape[DATA_AXIS]
            state["ef"] = tuple(
                {k: put_flat(np.zeros(n * rl, np.float32))
                 for k, rl in lens.items()}
                for lens in self.ef_lens())
        if self.mode == "gspmd":
            state = self._shard_state(state)
        return state

    def write_back(self, state: Dict[str, Any]) -> None:
        """Copy fused-state params back into the unit Arrays so granular
        mode, snapshots and the C++ exporter see the trained weights.

        Tolerates donated-away buffers: if a step failed mid-dispatch the
        state it consumed is already deleted — skip those arrays (the unit
        Arrays keep their last written-back values) instead of raising a
        secondary error that would mask the original one. Only the
        deleted-buffer RuntimeError is swallowed, per-array, so a real
        error in one layer cannot silently abort the rest."""
        def deleted(a) -> bool:
            return getattr(a, "is_deleted", lambda: False)()

        def host(a):
            if getattr(a, "is_fully_addressable", True):
                return np.asarray(a)
            # sharded ACROSS processes (EP experts / TP shards over a
            # multi-host mesh): gather to a replicated global array
            # first — np.asarray on a non-addressable array raises.
            # NOTE this is a collective: callers must invoke write_back
            # on EVERY process (see Launcher's snapshotter.dry_run).
            if self._gather_fn is None:
                self._gather_fn = jax.jit(
                    lambda t: t,
                    out_shardings=NamedSharding(self.mesh, P()))
            return np.asarray(self._gather_fn(a))

        plans = (self.zero_plans() if self.zero_active
                 else (None,) * len(self.forwards))
        for u, g, p, v, cfg, plan in zip(self.forwards, self.gd_units,
                                         state["params"], state["vel"],
                                         self.cfgs, plans):
            adam = isinstance(cfg, optim.AdamConfig)
            for k, arr in u.param_arrays().items():
                if deleted(p[k]) or (not adam and deleted(v[k])):
                    continue  # donated-away buffer: keep last value
                arr.reset(host(p[k]))
                if adam:
                    continue  # moments stay in the fused state pytree
                # momentum velocities land in the GD twin so a snapshot
                # resumes with optimizer state intact (reference parity:
                # whole-workflow pickle includes optimizer state) — a
                # ZeRO-sharded velocity is gathered and unflattened to
                # the leaf shape the twin expects
                vname = _vel_attr(g, k)
                if vname is not None:
                    hv = host(v[k])
                    if plan is not None:
                        lp = plan[k]
                        hv = hv.reshape(-1)[:lp.size].reshape(lp.shape)
                    getattr(g, vname).reset(hv)
        for u, a in zip(self.forwards, state.get("aux", ())):
            for k, arr in (u.aux_arrays().items() if a else ()):
                if not deleted(a[k]):
                    arr.reset(host(a[k]))

    def local_rows(self, n: int):
        """Boolean (n,) mask of GLOBAL batch rows whose data-axis shards
        are addressable from THIS process — the rows a loader must
        actually materialize. Non-local rows may stay zero-filled: the
        uniform-host-input jit transfers only local shards, so their
        values are never read. All-true on single-process meshes (and
        for batch sizes the data axis doesn't divide — callers fall back
        to full decode rather than guessing the layout). Cached per n:
        it runs per produced batch on the host-decode hot path."""
        cache = getattr(self, "_local_rows_cache", None)
        if cache is None:
            cache = self._local_rows_cache = {}
        if n in cache:
            return cache[n]
        if self.mesh is None:
            mask = np.ones(n, bool)
        else:
            ndata = self.mesh.shape.get(DATA_AXIS, 1)
            if ndata <= 1 or n % ndata:
                mask = np.ones(n, bool)
            else:
                pidx = jax.process_index()
                block = n // ndata
                mask = np.zeros(n, bool)
                # mesh.devices is (data, seq, model): every device in
                # row d holds (a piece of) rows [d*block, (d+1)*block)
                for d in range(ndata):
                    if any(dev.process_index == pidx
                           for dev in self.mesh.devices[d].flat):
                        mask[d * block:(d + 1) * block] = True
        cache[n] = mask
        return mask

    def _check_batch(self, n: int) -> None:
        """The actual fed batch must divide the data axis (checked per call
        so callers that feed their own batches — e.g. the scaling harness —
        are validated on what they actually feed, not the loader's size)."""
        if self.mode in ("dp", "gspmd", "seq"):
            n_data = self.mesh.shape.get(DATA_AXIS, 1)
            if n % n_data:
                raise ValueError(
                    f"batch of {n} not divisible by the mesh data axis "
                    f"({n_data} shards)")

    def _seq_xy(self, x, y, batched: bool = False):
        """In "seq" mode the sequence dim is sharded, so labels must keep
        their (N, S) structure. The text loaders emit flat (N·S,) labels
        (the char-LSTM/evaluator convention) — reshape them here, and
        check S divides the seq axis. `batched` handles train_many's
        extra leading K dim."""
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        if self.mode != "seq":
            return x, y
        lead = (x.shape[0],) if batched else ()
        n, s = x.shape[len(lead)], x.shape[len(lead) + 1]
        n_seq = self.mesh.shape.get(SEQ_AXIS, 1)
        if s % n_seq:
            raise ValueError(f"sequence length {s} not divisible by the "
                             f"mesh seq axis ({n_seq} shards)")
        if y.ndim == 1 + len(lead) and y.size == np.prod(lead + (n, s)):
            y = y.reshape(lead + (n, s))
        elif (y.ndim != 2 + len(lead)
              or y.shape[len(lead):] != (n, s)):
            # fail HERE with shapes, not inside shard_map with an opaque
            # rank/spec mismatch: seq mode shards labels over (data, seq)
            # so they must be per-token
            raise ValueError(
                f"seq mode needs per-token labels shaped {lead + (n, s)} "
                f"or flat ({np.prod(lead + (n, s))},); got {y.shape}")
        return x, y

    # -- forward chain -------------------------------------------------------

    @property
    def allow_pallas(self) -> bool:
        """May this step trace Pallas kernels: everywhere except under
        GSPMD auto-partitioning, which cannot partition a pallas_call.
        Said once, here: `_chain` and `variant_table` hand it to every
        registry-consulting unit before they trace or report (several
        step objects over one workflow each trace the right lowering),
        and the step's own resolutions (`_pair_fusion`, `_sgd_variant`)
        pass the step as the unit, so `variants.resolve` reads it."""
        return self.mode != "gspmd"

    def _pair_fusion(self, u, nxt):
        """The FUSED registry variant claiming the adjacent (u, nxt)
        pair at trace time, or None (composed winner / pallas gated /
        per-layer overrides / incompatible flavors). One rule shared by
        _forward, variant_table and the jaxpr auditor's fused-pair pass
        — traced == reported == audited."""
        from veles_tpu.ops import templates
        if nxt is None:
            return None
        op_a = getattr(u, "variant_op", None)
        op_b = getattr(nxt, "variant_op", None)
        # a per-layer override pins a MEMBER lowering: claiming the pair
        # would silently bypass it
        if getattr(u, "variant_override", None) is not None \
                or getattr(nxt, "variant_override", None) is not None:
            return None
        # the FUSION op resolves against the step, not a member: the
        # step carries the pallas gate and no variant_override to leak
        if op_a == "lrn" and op_b == "maxpool" \
                and not getattr(nxt, "use_abs", False):
            return templates.fusion_point("lrn_maxpool", unit=self)
        if op_a == "conv_stem" and op_b == "lrn":
            # only auto-mode applicable stems consult the registry end
            # to end (the unit's own fused_apply gate)
            if getattr(u, "s2d", None) != "auto" \
                    or not getattr(u, "input", None) \
                    or not u._s2d_applicable(u.input.shape[-1]):
                return None
            return templates.fusion_point("conv_stem", unit=self)
        return None

    def fusion_pairs(self):
        """[(i, i+1, Variant), ...] adjacent unit pairs the CURRENT
        registry selections claim, left-to-right (a unit joins at most
        one pair — when both a conv epilogue and an lrn_maxpool winner
        want the same LRN unit, the earlier pair wins). Resolved fresh
        per call: trace-time state, like variants.resolve itself."""
        out = []
        claimed: set = set()
        fwds = self.forwards
        for i, u in enumerate(fwds[:-1]):
            if i in claimed or (i + 1) in claimed:
                continue
            v = self._pair_fusion(u, fwds[i + 1])
            if v is not None:
                out.append((i, i + 1, v))
                claimed.update((i, i + 1))
        return out

    def _apply_fused_pair(self, v, u, nxt, params_u, x):
        """Trace one claimed pair: the leading unit's op consumes both
        members' work through the fused variant; the trailing unit is a
        pass-through for this trace."""
        if getattr(u, "variant_op", None) == "lrn":
            return v.apply(x, k=u.k, alpha=u.alpha, beta=u.beta, n=u.n,
                           ksize=tuple(nxt.ksize),
                           stride=tuple(nxt.stride))
        # conv_stem epilogue: conv+bias+act with the successor LRN
        # folded in
        return v.apply(x, params_u["weights"], params_u["bias"],
                       u.stride, u.padding, u.activation,
                       epilogue={"k": nxt.k, "alpha": nxt.alpha,
                                 "beta": nxt.beta, "n": nxt.n})

    def _forward(self, params, x, key, train: bool,
                 local_trace: bool = False):
        """The forward chain's output alone (serving, export, the
        confusion companion): step state is read, not moved."""
        return self._chain(params, x, key, train, local_trace)[0]

    def _chain(self, params, x, key, train: bool,
               local_trace: bool = False, aux=None, loss_args=None):
        """(output of the last unit, what each unit with step state
        counted this step: one dict per forward unit). `loss_args`
        (targets, weights, denom) go to a last unit that owns its loss,
        whose output is then (loss, n_err)."""
        # uint8-wire prologue: traced into the step, so it fuses into
        # the first layer's HBM read. A first unit that says it takes
        # token ids (`fused_integer_input`) gets them as they are.
        ids = getattr(self.forwards[0], "fused_integer_input", False)
        with jax.named_scope("input_normalize"):
            if not ids:
                x = apply_input_normalize(self.input_normalize, x)
                if self.compute_dtype is not None:
                    x = x.astype(self.compute_dtype)
        if self.compute_dtype is not None:
            with jax.named_scope("cast_params"):
                # (a unit may name leaves that keep their master dtype:
                # `fused_float32_params`, a linear layer's decay)
                kept = [{k: p[k] for k in getattr(
                    u, "fused_float32_params", ()) if k in p}
                    for u, p in zip(self.forwards, params)]
                params = tuple(
                    {**c, **k} if k else c for c, k in zip(
                        _tree_cast(tuple(params), self.compute_dtype), kept))
        # local_trace: trace the DENSE single-program form (no bound
        # collective axis names) for use under plain jit — GSPMD handles
        # any param sharding, gathering EP experts where needed (the
        # confusion companion uses this)
        seq_axis = (SEQ_AXIS if self.mode == "seq" and not local_trace
                    else None)
        ep_axis = DATA_AXIS if self.ep and not local_trace else None
        gathered = (self._gathered_units(x.shape[0])
                    if train and not local_trace else frozenset())
        for i, u in enumerate(self.forwards):
            if hasattr(u, "grad_gather_axis_name"):
                # the dense units whose backward forms the GLOBAL weight
                # gradient from gathered operands: the same set that
                # _grad_params leaves invariant and the update does not
                # sum (one predicate, _gathered_units)
                u.grad_gather_axis_name = (DATA_AXIS if i in gathered
                                           else None)
            if hasattr(u, "seq_axis_name"):
                # set at trace time so several step objects (different
                # modes) over one workflow each trace the right kernel
                u.seq_axis_name = seq_axis
            if hasattr(u, "model_axis_name"):
                # shard_map TP (seq mode + model axis): the unit psums
                # over the model axis exactly when its params were
                # sharded by _seq_param_specs — same gate both places
                u.model_axis_name = (
                    MODEL_AXIS if self._seq_tp_active(u) else None)
            if hasattr(u, "ep_axis_name"):
                u.ep_axis_name = ep_axis
            if getattr(u, "variant_op", None) is not None:
                u.allow_pallas = self.allow_pallas
        # searched cross-op fusion (ISSUE 13): a fused winner lets the
        # leading unit claim its successor's work — the successor
        # becomes a pass-through for this trace. Key folds keep the
        # ABSOLUTE unit index either way, so fused and composed traces
        # draw identical RNG streams.
        fused = {i: (j, v) for i, j, v in self.fusion_pairs()}
        skip = {j for j, _ in fused.values()}
        counted = [{} for _ in self.forwards]
        for i, u in enumerate(self.forwards):
            if i in skip:
                continue
            if i in fused:
                j, v = fused[i]
                with jax.named_scope(f"{self.scopes[i]}+{self.scopes[j]}"):
                    x = self._apply_fused_pair(v, u, self.forwards[j],
                                               params[i], x)
                    x = self._constrain_tp_act(x, j)
                continue
            with jax.named_scope(self.scopes[i]):
                k = (jax.random.fold_in(key, i) if u.fused_needs_key
                     else None)
                kw = {}
                if aux is not None and aux[i]:
                    kw["aux"] = aux[i]
                if loss_args is not None and i == len(self.forwards) - 1:
                    kw.update(loss_args)

                def apply(p, xx, kw, u=u, k=k):
                    return u.fused_apply(p, xx, key=k, train=train, **kw)

                if getattr(u, "fused_remat", False) and train:
                    # the step keeps this unit's input and recomputes its
                    # inside in the backward pass, but for what the unit's
                    # policy names
                    apply = jax.checkpoint(apply, policy=getattr(
                        u, "fused_remat_policy", None))
                x = apply(params[i], x, kw)
                if "aux" in kw:
                    x, counted[i] = x
                x = self._constrain_tp_act(x, i)
        for i in gathered:
            # the traced backward holds the axis name itself; a later
            # caller of fused_apply outside a dp trace (the pipeline
            # step) must not find it on the unit
            self.forwards[i].grad_gather_axis_name = None
        if self.compute_dtype is not None and loss_args is None:
            with jax.named_scope("loss"):
                x = x.astype(jnp.float32)
        return x, tuple(counted)

    def input_put_specs(self):
        """Leading-dim PartitionSpecs for the device feed's async
        batch put ((x, y, w) order): the data-axis layout every sharded
        mode consumes — seq mode's sequence-dim split happens inside
        jit, a device-side reshard of already-resident arrays."""
        if self.mode in ("dp", "gspmd", "seq"):
            return (P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS))
        return (P(), P(), P())

    def _constrain_tp_act(self, x, i):
        """GSPMD mode: pin a TP plan's sharded activations to
        P(data, ..., model). Without this constraint the partitioner MAY
        keep activations sharded — with it, it MUST (or insert the
        collectives to get there), so tensor parallelism provably
        partitions the activation flops instead of silently replicating
        them (the failure mode the round-2 verdict flagged)."""
        if not (self.mode == "gspmd" and self.mesh is not None):
            return x
        if getattr(self, "_tp_out_sharded", None) is None:
            self._param_shardings()
        if not self._tp_out_sharded[i] or x.ndim < 2:
            return x
        spec = P(DATA_AXIS, *([None] * (x.ndim - 2)), MODEL_AXIS)
        return lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def _loss_metrics(self, params, x, y, key, train: bool, w, axes,
                      wsum=None, aux=None):
        """PARTIAL (loss, n_err, what the units with step state counted):
        the loss is normalized by the GLOBAL
        weight sum (psum over `axes` when sharded), so per-shard partials
        SUM to the exact global weighted mean — and because each shard's
        partial objective contributes additively, the gradient transpose
        of the replicated params psums to the exact global gradient with
        no per-shard renormalization. `w` is the Loader's (N,) pad mask
        (all-ones when absent): zero rows drop out of loss, n_err AND
        gradients, so wrapped final minibatches are exact.

        `wsum` overrides the normalizing per-SAMPLE weight total (already
        globally reduced): gradient accumulation passes the FULL batch's
        weight sum so microbatch partials sum to the exact full-batch
        mean (and its gradient)."""
        if self.unit_loss:
            # the last unit owns the loss: per-sample weights and their
            # global sum go in, the partial (loss, n_err) comes out
            denom = (wsum if wsum is not None
                     else self._global_wsum(w, 1, axes))
            (loss, n_err), counted = self._chain(
                params, x, key, train, aux=aux,
                loss_args={"targets": y, "weights": w, "denom": denom})
            return loss, n_err, counted
        out, counted = self._chain(params, x, key, train, aux=aux)
        with jax.named_scope("loss"):
            if self.loss_kind == "softmax":
                # broadcast per-sample weights over token dims: (N,) classifier
                # labels, (N, S) per-token LM labels, or flat (N·S,) labels
                # (the char-LSTM convention) where each sample weight covers
                # S consecutive tokens
                if y.ndim == w.ndim and y.shape[0] != w.shape[0] \
                        and y.shape[0] % w.shape[0] == 0:
                    wt = jnp.repeat(w, y.shape[0] // w.shape[0])
                else:
                    wt = jnp.broadcast_to(
                        w.reshape(w.shape + (1,) * (y.ndim - w.ndim)),
                        y.shape)
                wt = wt.astype(jnp.float32)
                tokens = wt.size // w.size
                denom = (wsum * tokens if wsum is not None
                         else self._global_wsum(w, tokens, axes))
                loss = ox.ce_loss_from_logits(out, y, self.n_classes,
                                              weights=wt, denom=denom)
                wrong = (out.reshape(-1, out.shape[-1]).argmax(axis=-1)
                         != y.reshape(-1))
                n_err = (wrong & (wt.reshape(-1) > 0)).sum()
            else:
                denom = (wsum if wsum is not None
                         else self._global_wsum(w, 1, axes))
                loss, _ = ox.mse(out, y, weights=w, denom=denom)
                n_err = loss
        return loss, n_err, counted

    def _global_wsum(self, w, tokens_per_sample: int, axes):
        """Global token-weight sum. The mask `w` is per-SAMPLE and varies
        only over the data axis (seq shards hold identical copies), so
        the psum rides "data" and the seq contribution is the static
        shard-count factor."""
        s = w.astype(jnp.float32).sum() * tokens_per_sample
        if axes:
            if DATA_AXIS in axes:
                s = lax.psum(s, (DATA_AXIS,))
            for a in axes:
                if a != DATA_AXIS:
                    s = s * self.mesh.shape[a]
        return s

    # -- step bodies ---------------------------------------------------------

    def _shard_step_key(self, state, axes):
        """Per-shard step key: decorrelate dropout/stochastic-pool across
        shards via the global linear shard index (shared by the plain and
        accumulated train bodies so their key streams stay in lockstep)."""
        step_key = state["key"]
        if axes:
            idx = lax.axis_index(axes[0])
            for a in axes[1:]:
                idx = idx * self.mesh.shape[a] + lax.axis_index(a)
            step_key = jax.random.fold_in(step_key, idx)
        return step_key

    def _train_body(self, state, x, y, w, *, axis):
        """axis: None (local/gspmd), a mesh axis name, or a tuple of axis
        names (the "seq" mode reduces over ("data", "seq"))."""
        axes = (axis,) if isinstance(axis, str) else axis
        step_key = self._shard_step_key(state, axes)

        def lf(p):
            # _loss_metrics normalizes by the GLOBAL weight sum, so the
            # sum of the per-shard partial gradients IS the exact
            # global-mean gradient: THE north-star collective
            # (BASELINE.json:5), right where the reference shipped
            # pickled deltas. Where the params are unvarying (seq, EP's
            # replicated leaves) the transpose of their broadcast is
            # that psum and jax inserts it (vma semantics); on the dp
            # mesh _grad_params makes them varying and the update sums
            # the partials itself, in float32 — all but the leaves of
            # the dense units in `gathered`, whose backward has formed
            # the global gradient on every chip already.
            loss, n_err, counted = self._loss_metrics(
                p, x, y, step_key, True, w, axes, aux=state.get("aux"))
            return loss, (loss, n_err, counted)

        gathered = self._gathered_units(x.shape[0])
        self._rows_traced = x.shape[0]
        (_, (loss, n_err, counted)), grads = jax.value_and_grad(
            lf, has_aux=True)(self._grad_params(state["params"], gathered))
        if axes:
            # partials with a global denominator: SUM to the global metric
            with jax.named_scope("loss"):
                loss = lax.psum(loss, axes)
                n_err = lax.psum(n_err, axes)
        return (self._apply_update(state, grads, gathered, counted), loss,
                n_err)

    def _exchanges_partials(self) -> bool:
        """True where the update itself sums the per-shard partial
        gradients: the dp step without EP, sharded update or replicated.
        (Under EP the expert tensors are sharded over the data axis and
        their gradients arrive through the all_to_all transpose, so
        autodiff's own psum of the replicated leaves stays; local, gspmd
        and seq have no hand-placed exchange.)"""
        return self.mode == "dp" and not self.ep

    def _dense_case(self, i: int, rows: int
                    ) -> Optional[Tuple[int, int, int, int, int]]:
        """The arguments of `dense_grad_form` / `dense_grad_wire` for
        forward unit `i` at `rows` rows a chip: (chips on the data axis,
        rows, fan_in, fan_out, the compute dtype's itemsize) if it is a
        dense layer that can form its weight gradient from gathered
        operands (it has the `grad_gather_axis_name` hook and a 2-D
        weight), else None. A conv never is: its operands are whole
        feature maps."""
        u = self.forwards[i]
        w = u.param_arrays().get("weights")
        if not hasattr(u, "grad_gather_axis_name") or not w \
                or len(w.shape) != 2:
            return None
        return (self.mesh.shape[DATA_AXIS], rows, int(w.shape[0]),
                int(w.shape[1]),
                jnp.dtype(self.compute_dtype or jnp.float32).itemsize)

    def _gathered_units(self, rows: int) -> frozenset:
        """Indices of the dense units whose weight gradient the backward
        forms from gathered operands at `rows` rows a chip. THE predicate:
        `_forward` hands these units the data axis, `_grad_params` leaves
        their leaves invariant, the update does not sum them, and
        `variant_table()` reports them. Engages only where the update
        itself sums partials and every replica applies it whole: the dp
        step without EP and without ZeRO (whose reduce-scatter would want
        only the shard's slice of such a gradient; no cell runs it)."""
        if not self._exchanges_partials() or self.zero_active:
            return frozenset()
        if rows not in self._gathered_cache:
            self._gathered_cache[rows] = frozenset(
                i for i in range(len(self.forwards))
                if (case := self._dense_case(i, rows)) is not None
                and dense_grad_form(*case) == "gather")
        return self._gathered_cache[rows]

    def _grad_params(self, params, gathered: frozenset):
        """The params autodiff differentiates against. Where the update
        sums the gradients itself they are cast to VARYING over the data
        axis first: the gradient of a varying value is this shard's
        partial (no transpose psum), a float32 leaf whatever the compute
        dtype. Left to autodiff, the psum lands on the cotangent of
        `cast_params`' output and reduces in the compute dtype. The ZeRO
        update reduce-scatters the partials through the `grad_reduce`
        registry op, the replicated one all-reduces them — one reduction
        either way, of the same float32 bytes. The units in `gathered`
        keep their leaves INVARIANT: their backward returns the global
        gradient, the same on every chip (ops.xla.dense_gathered_grad),
        and nothing is left to reduce."""
        if not self._exchanges_partials():
            return params
        return tuple(
            p if i in gathered else jax.tree.map(
                lambda a: lax.pcast(a, DATA_AXIS, to="varying"), p)
            for i, p in enumerate(params))

    def _sgd_variant(self):
        """The sgd_update registry variant this step traces — ONE
        resolution rule for the update itself (_apply_update) and the
        reported table (variant_table), so a record can never name a
        variant the step didn't trace. The step is the unit: GSPMD falls
        back (`allow_pallas`, same gate as the unit path)."""
        from veles_tpu.ops import variants
        return variants.resolve("sgd_update", unit=self)

    def _apply_update(self, state, grads, gathered: frozenset,
                      counted=()):
        """One optimizer step; advances the carried key identically on
        every shard (fold_in of the *unfolded* state key keeps it
        replicated). On a dp mesh the grads arrive UNREDUCED per-shard
        partials and the update performs the reduction itself
        (reduce-scatter under ZeRO, all-reduce otherwise), except the
        leaves of the units in `gathered`, which arrive global; elsewhere
        they arrive reduced. The SGD leg resolves through
        the `sgd_update` registry op (default xla_tree IS
        optim.sgd_update; the search-generated pallas row-blocked
        candidates slot in when selected — GSPMD falls back, a
        pallas_call cannot be auto-partitioned)."""
        with jax.named_scope("update"):
            new = (self._apply_update_zero(state, grads) if self.zero_active
                   else self._apply_update_replicated(state, grads, gathered))
            if "aux" in state:
                # state no gradient touches moves by its unit's own rule
                # from what the step counted (an expert layer's selection
                # bias from the loads)
                with jax.named_scope("balance"):
                    new["aux"] = tuple(
                        u.fused_aux_update(a, c) if a else a
                        for u, a, c in zip(self.forwards, state["aux"],
                                           counted))
            return new

    def _apply_update_replicated(self, state, grads, gathered: frozenset):
        """Every replica applies the full update. On a dp mesh a unit's
        grads arrive as per-shard partials (`_grad_params`) and each leaf
        is all-reduced here in its own dtype, float32 — unless the unit
        is in `gathered`: its backward formed the global gradient from
        gathered operands, on every chip the same, and a sum over the
        axis would count it once a chip. Everywhere else grads arrive
        already reduced."""
        sgd_apply = self._sgd_variant().apply
        exchange = self._exchanges_partials()
        new_params, new_vel = [], []
        for i, (scope, p, g, v, cfg) in enumerate(
                zip(self.scopes, state["params"], grads, state["vel"],
                    self.cfgs)):
            with jax.named_scope(scope):
                if p and exchange and i not in gathered:
                    with jax.named_scope("grad_exchange"):
                        g = {k: lax.psum(a, DATA_AXIS)
                             for k, a in g.items()}
                if p and isinstance(cfg, optim.AdamConfig):
                    np_, nv_ = optim.adam_update(
                        p, g, v, cfg, lr_scale=state["lr_scale"])
                elif p:
                    np_, nv_ = sgd_apply(p, g, v, cfg,
                                         lr_scale=state["lr_scale"])
                else:
                    np_, nv_ = p, v
            new_params.append(np_)
            new_vel.append(nv_)
        new_key = jax.random.fold_in(state["key"], 1)
        return {"params": tuple(new_params), "vel": tuple(new_vel),
                "key": new_key, "lr_scale": state["lr_scale"]}

    def _apply_update_zero(self, state, grads):
        """ZeRO weight-update sharding (arxiv 2004.13336), traced inside
        the dp shard_map body: per param leaf, reduce-SCATTER the
        per-shard partial gradient (registry op "grad_reduce" — the
        quantized/hierarchical EQuARX variants slot in there; stateful
        int8+EF variants thread the state's "ef" residual slot through
        the exchange and return it updated), apply the SAME
        per-leaf optimizer rule to this shard's 1/N slice of params over
        its slice-only momentum/Adam state, and all-gather the fresh
        param slices for the next forward. Same wire bytes as the psum
        it replaces; optimizer state never materializes beyond 1/N per
        device. The gather is the Varying -> Invariant form, so the
        returned params are provably identical on every replica
        (shard_map's varying-axes check stays on)."""
        gr = self._grad_reduce_variant()
        reduce = gr.apply
        # error-feedback residual slot (stateful variants): present in
        # the state exactly when ef_active() held at init (one rule);
        # threaded leaf-by-leaf through the reduce and returned updated
        ef_state = state.get("ef") if self.ef_active() else None
        new_ef: List[Any] = []
        idx = lax.axis_index(DATA_AXIS)
        new_params, new_vel = [], []
        for li, (p, g, v, cfg, plan) in enumerate(
                zip(state["params"], grads, state["vel"], self.cfgs,
                    self.zero_plans())):
            ef_layer = ef_state[li] if ef_state is not None else None
            nef: Dict[str, Any] = {}
            if not p:
                new_params.append(p)
                new_vel.append(v)
                new_ef.append(ef_layer if ef_layer is not None else {})
                continue
            with jax.named_scope(self.scopes[li]):
                adam = isinstance(cfg, optim.AdamConfig)
                if adam:
                    t = v["t"] + 1
                    b1t, b2t = optim.adam_step_factors(cfg, t)
                    nv: Dict[str, Any] = {"m": {}, "v": {}, "t": t}
                else:
                    nv = {}
                np_ = {}
                for k in p:
                    lp = plan[k]
                    with jax.named_scope("grad_exchange"):
                        flat_g = zero_flatten(g[k], lp)
                        if ef_layer is not None:
                            g_loc, nef[k] = reduce(flat_g, DATA_AXIS,
                                                   ef_layer[k])
                        else:
                            g_loc = reduce(flat_g, DATA_AXIS)
                    p_loc = lax.dynamic_slice(
                        zero_flatten(p[k], lp), (idx * lp.local,),
                        (lp.local,))
                    if adam:
                        p_new, m_new, v_new = optim.adam_leaf(
                            p_loc, g_loc, v["m"][k], v["v"][k], cfg,
                            b1t, b2t, cfg.lr * state["lr_scale"])
                        nv["m"][k] = m_new
                        nv["v"][k] = v_new
                    else:
                        lr = optim.sgd_leaf_lr(cfg, lp.ndim,
                                               lr_scale=state["lr_scale"])
                        p_new, v_new = optim.sgd_leaf(p_loc, g_loc, v[k],
                                                      cfg, lr)
                        nv[k] = v_new
                    with jax.named_scope("param_gather"):
                        full = all_gather_invariant(p_new, DATA_AXIS, axis=0,
                                                    tiled=True)
                        np_[k] = zero_unflatten(full, lp)
            new_params.append(np_)
            new_vel.append(nv)
            new_ef.append(nef)
        new_key = jax.random.fold_in(state["key"], 1)
        out = {"params": tuple(new_params), "vel": tuple(new_vel),
               "key": new_key, "lr_scale": state["lr_scale"]}
        if ef_state is not None:
            out["ef"] = tuple(new_ef)
        return out

    def _accum_body(self, state, xs, ys, ws, *, axis):
        """Gradient accumulation: grads of the FULL (K·m)-sample batch
        computed by scanning K microbatches (activation memory O(m)),
        then ONE optimizer update — the TPU-first form of the reference's
        `gradient_accumulation`/`apply_gradients` gate (SURVEY.md §2.8
        GradientDescentBase row). Each microbatch is normalized by the
        full batch's global weight sum, so the scanned grad SUM equals
        the full-batch mean gradient exactly (pad masks included); on the
        dp mesh the accumulated partials are exchanged once, by the
        update, and a gathered unit's gradient (global in every
        microbatch already) by nobody (where autodiff places the psum,
        seq and EP, it fires once per microbatch inside the scan)."""
        if self.has_aux:
            raise NotImplementedError(
                "gradient accumulation over units with step state (an "
                "expert layer's selection bias moves on a whole step's "
                "loads) is not covered by this build")
        axes = (axis,) if isinstance(axis, str) else axis
        step_key = self._shard_step_key(state, axes)
        wsum = self._global_wsum(ws.reshape(-1), 1, axes)
        gathered = self._gathered_units(xs.shape[1])
        self._rows_traced = xs.shape[1]

        def micro(carry, xyw):
            acc, loss_a, err_a, i = carry
            x, y, w = xyw

            def lf(p):
                loss, n_err, _ = self._loss_metrics(
                    p, x, y, jax.random.fold_in(step_key, i), True, w,
                    axes, wsum=wsum)
                return loss, (loss, n_err)

            (_, (loss, n_err)), grads = jax.value_and_grad(
                lf, has_aux=True)(gparams)
            acc = jax.tree.map(lambda a, g: a + g, acc, grads)
            return (acc, loss_a + loss,
                    err_a + n_err.astype(jnp.float32), i + 1), None

        gparams = self._grad_params(state["params"], gathered)
        zero = jax.tree.map(jnp.zeros_like, gparams)
        # the metric carries must be device-varying from step 0 under
        # shard_map (they mix with varying per-shard partials); deriving
        # them from ws inherits its varying axes (cf. ring_attention)
        zero_s = ws.reshape(-1)[0].astype(jnp.float32) * 0.0
        (grads, loss, n_err, _), _ = lax.scan(
            micro, (zero, zero_s, zero_s, jnp.int32(0)), (xs, ys, ws))
        if axes:
            loss = lax.psum(loss, axes)
            n_err = lax.psum(n_err, axes)
        if self.loss_kind == "softmax":
            n_err = n_err.astype(jnp.int32)
        return self._apply_update(state, grads, gathered), loss, n_err

    def _eval_body(self, params, x, y, w, *, axis):
        axes = (axis,) if isinstance(axis, str) else axis
        key = jax.random.PRNGKey(0)  # unused: eval paths need no RNG
        loss, n_err, _ = self._loss_metrics(params, x, y, key, False, w,
                                            axes)
        if axes:
            loss = lax.psum(loss, axes)
            n_err = lax.psum(n_err, axes)
        return loss, n_err

    # -- shard_map specs (dp mode) -------------------------------------------

    def _smap_param_specs(self):
        """Per-layer PartitionSpec dicts for shard_map state specs. All
        params replicate (P()) except expert tensors under ep=True, which
        shard their leading expert dim over the data axis — each shard
        then owns E/n_data experts and updates them locally (their grads
        arrive through the all_to_all transpose, not the broadcast-psum
        that replicated params get)."""
        specs = []
        for u in self.forwards:
            ep_names = getattr(u, "ep_params", ()) if self.ep else ()
            specs.append({k: P(DATA_AXIS) if k in ep_names else P()
                          for k in u.param_arrays()})
        return tuple(specs)

    def _seq_tp_active(self, u) -> bool:
        """True when seq-mode shard_map TP shards this unit's params."""
        if self.mode != "seq" or self.mesh is None:
            return False
        m = self.mesh.shape.get(MODEL_AXIS, 1)
        return (m > 1 and hasattr(u, "tp_param_specs")
                and u.tp_param_specs(MODEL_AXIS, m) is not None)

    def _seq_param_specs(self):
        """Per-layer shard_map param specs for seq mode: megatron TP over
        the mesh's model axis for units that declare a plan
        (tp_param_specs), replicated otherwise — the third axis of the
        data x seq x model long-context recipe."""
        m = self.mesh.shape.get(MODEL_AXIS, 1)
        specs = []
        for u in self.forwards:
            pd = {k: P() for k in u.param_arrays()}
            if m > 1 and hasattr(u, "tp_param_specs"):
                tp = u.tp_param_specs(MODEL_AXIS, m)
                if tp:
                    pd.update(tp)
            specs.append(pd)
        return tuple(specs)

    def _seq_state_spec(self):
        psp = self._seq_param_specs()
        return {"params": psp, "vel": self._vel_specs(psp, P()),
                "key": P(), "lr_scale": P()}

    def _vel_specs(self, per_layer, scalar):
        """Optimizer-state specs mirroring each layer's param specs —
        Adam layers carry {"m", "v", "t"} instead of a velocity dict."""
        return tuple(
            {"m": sp, "v": sp, "t": scalar}
            if isinstance(cfg, optim.AdamConfig) else sp
            for cfg, sp in zip(self.cfgs, per_layer))

    def _zero_vel_specs(self):
        """Optimizer-state specs under the ZeRO plan: every leaf is a
        flat (padded,) vector sharded over the data axis — the shard_map
        body sees only this shard's slice, matching what
        _apply_update_zero reads/writes. Adam's step counter stays
        replicated."""
        specs = []
        for u, cfg in zip(self.forwards, self.cfgs):
            sp = {k: P(DATA_AXIS) for k in u.param_arrays()}
            specs.append({"m": sp, "v": dict(sp), "t": P()}
                         if isinstance(cfg, optim.AdamConfig) else sp)
        return tuple(specs)

    def _smap_state_spec(self):
        psp = self._smap_param_specs()
        vsp = (self._zero_vel_specs() if self.zero_active
               else self._vel_specs(psp, P()))
        spec = {"params": psp, "vel": vsp, "key": P(), "lr_scale": P()}
        if self.ef_active():
            # the EF residual slot mirrors the flat optimizer-state
            # layout: every leaf a (per-shard-length,) slice of a
            # data-axis-sharded vector
            spec["ef"] = tuple({k: P(DATA_AXIS) for k in u.param_arrays()}
                               for u in self.forwards)
        return spec

    # -- compilation ---------------------------------------------------------

    def train_callable(self):
        """The UNJITTED (state, x, y, w) -> (state, loss, n_err)
        callable `_build` wraps in jax.jit — shard_map-wrapped in
        dp/seq modes so the jaxpr auditor (analysis/trace.py) abstractly
        traces exactly what trains, with zero compile.

        The callable is NAMED: the compiled program is `jit_train_step`
        in a profile, through every recompile. (The name is also part of
        jax's compile cache key, which leaves operation metadata out: an
        executable cached before the step's named scopes existed would
        otherwise be loaded with its old, scopeless operation names.)"""
        axis = {"dp": DATA_AXIS, "seq": (DATA_AXIS, SEQ_AXIS)}.get(
            self.mode)

        def train_step(s, x, y, w):
            return self._train_body(s, x, y, w, axis=axis)

        if self.mode in ("local", "gspmd"):
            return train_step
        if self.mode == "dp":
            ssp = self._smap_state_spec()
            return shard_map(
                train_step, mesh=self.mesh,
                in_specs=(ssp, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
                out_specs=(ssp, P(), P()))
        if self.mode == "seq":
            xspec = P(DATA_AXIS, SEQ_AXIS)  # (N, S, ...) batch x sequence
            ssp = self._seq_state_spec()    # TP-sharded when model axis
            return shard_map(
                train_step, mesh=self.mesh,
                in_specs=(ssp, xspec, xspec, P(DATA_AXIS)),
                out_specs=(ssp, P(), P()))
        raise ValueError(f"unknown mode {self.mode!r}")

    @_tracer.in_phase("setup.build_step")
    def _build(self) -> None:
        donate = (0,) if self.donate else ()
        axis = {"dp": DATA_AXIS, "seq": (DATA_AXIS, SEQ_AXIS)}.get(
            self.mode)

        def eval_step(p, x, y, w):      # named, as train_step is
            return self._eval_body(p, x, y, w, axis=axis)

        if self.mode == "local":
            self._train_fn = jax.jit(self.train_callable(),
                                     donate_argnums=donate)
            self._eval_fn = jax.jit(eval_step)
        elif self.mode == "dp":
            mesh = self.mesh
            ssp = self._smap_state_spec()
            wsp = P(DATA_AXIS)
            evalf = shard_map(
                eval_step, mesh=mesh,
                in_specs=(ssp["params"], P(DATA_AXIS), P(DATA_AXIS), wsp),
                out_specs=(P(), P()))
            self._train_fn = jax.jit(self.train_callable(),
                                     donate_argnums=donate)
            self._eval_fn = jax.jit(evalf)
        elif self.mode == "seq":
            mesh = self.mesh
            xspec = P(DATA_AXIS, SEQ_AXIS)  # (N, S, ...) batch x sequence
            wsp = P(DATA_AXIS)              # weights stay per-SAMPLE
            ssp = self._seq_state_spec()    # TP-sharded when model axis
            evalf = shard_map(
                eval_step, mesh=mesh,
                in_specs=(ssp["params"], xspec, xspec, wsp),
                out_specs=(P(), P()))
            self._train_fn = jax.jit(self.train_callable(),
                                     donate_argnums=donate)
            self._eval_fn = jax.jit(evalf)
        elif self.mode == "gspmd":
            mesh = self.mesh
            xsh = NamedSharding(mesh, P(DATA_AXIS))
            ssh = self._state_shardings()
            repl = NamedSharding(mesh, P())
            # out_shardings pins the NEW state to the same TP plan the
            # inputs carry: without it the partitioner is free to return
            # updated params under propagated shardings that drift from
            # the plan (observed: a small replicated bias coming back
            # P("model")), and the eval jit's in_shardings then rejects
            # the trained state with a sharding-mismatch ValueError
            self._train_fn = jax.jit(
                self.train_callable(),
                in_shardings=(ssh, xsh, xsh, xsh),
                out_shardings=(ssh, repl, repl),
                donate_argnums=donate)
            self._eval_fn = jax.jit(
                eval_step,
                in_shardings=(self._param_shardings(), xsh, xsh, xsh))
        else:
            raise ValueError(f"unknown mode {self.mode!r}")
        # each behind its first call's phase (`setup.first_dispatch`)
        _tracer.FirstCall.on(self, "_train_fn")
        _tracer.FirstCall.on(self, "_eval_fn")

    # -- GSPMD shardings: params TP-sharded over "model", batch over "data" --

    def _tp_plan(self):
        """Megatron-style tensor-parallel plan, computed once from host
        shapes: per-layer param PartitionSpecs plus a per-layer flag for
        whether the layer's OUTPUT activation is feature-sharded.

        Single-weight layers (all2all, conv) alternate column-parallel
        (output dim sharded -> activation stays sharded, zero forward
        comms) with row-parallel (contraction dim sharded -> one psum,
        activation comes back replicated) — the classic pairing that
        partitions both weights of an FC/conv pair while communicating
        once. Multi-matrix families (attention/LSTM/MoE) fall back to
        last-dim sharding of every divisible param. Non-divisible params
        replicate (XLA would pad-shard them inefficiently, and they are
        small by definition)."""
        m = self.mesh.shape.get(MODEL_AXIS, 1)
        plan, out_flags = [], []
        act_sh = False
        for u in self.forwards:
            arrs = {k: np.asarray(a.mem)
                    for k, a in u.param_arrays().items() if a}
            pd = {k: P() for k in u.param_arrays()}
            if m == 1:
                plan.append(pd)
                out_flags.append(False)
                continue
            out_sh = act_sh if not arrs else False
            w = arrs.get("weights")
            if w is not None and w.ndim in (2, 4):
                # 2-D (in, out) matmul or 4-D HWIO conv (kh, kw, cin, cout)
                in_ax = 0 if w.ndim == 2 else 2
                out_ax = w.ndim - 1
                if act_sh and w.shape[in_ax] % m == 0:
                    spec = [None] * w.ndim
                    spec[in_ax] = MODEL_AXIS
                    pd["weights"] = P(*spec)      # row-parallel
                    out_sh = False
                elif w.shape[out_ax] % m == 0:
                    spec = [None] * w.ndim
                    spec[out_ax] = MODEL_AXIS
                    pd["weights"] = P(*spec)      # column-parallel
                    b = arrs.get("bias")
                    if b is not None and b.ndim == 1 and not b.shape[0] % m:
                        pd["bias"] = P(MODEL_AXIS)
                    out_sh = True
                else:
                    out_sh = False
            elif arrs:
                out_dim = (u.output.shape[-1]
                           if getattr(u, "output", None) else None)
                for k, a in arrs.items():
                    if a.ndim >= 2 and a.shape[-1] % m == 0:
                        pd[k] = P(*([None] * (a.ndim - 1) + [MODEL_AXIS]))
                        if out_dim is not None and a.shape[-1] == out_dim:
                            out_sh = True
            plan.append(pd)
            out_flags.append(out_sh)
            act_sh = out_sh
        return tuple(plan), out_flags

    def _param_shardings(self):
        plan, self._tp_out_sharded = self._tp_plan()
        return tuple(
            {k: NamedSharding(self.mesh, spec) for k, spec in pd.items()}
            for pd in plan)

    def _state_shardings(self):
        psh = self._param_shardings()
        repl = NamedSharding(self.mesh, P())
        return {"params": psh, "vel": self._vel_specs(psh, repl),
                "key": repl, "lr_scale": repl}

    def _shard_state(self, state):
        from veles_tpu.parallel.mesh import is_multihost
        shardings = self._state_shardings()
        if is_multihost(self.mesh):
            # multi-process global mesh (dp x tp over DCN): device_put
            # rejects shardings with non-addressable devices; jit treats
            # the uniform host state (single-controller convention, see
            # parallel/distributed.py) as replicated input and emits
            # global arrays laid out per `shardings`
            return jax.jit(lambda s: s, out_shardings=shardings)(state)
        return jax.device_put(state, shardings)

    # -- public API ----------------------------------------------------------

    def _weights_or_ones(self, w, n: int, lead=()):
        """Normalize the optional pad mask to a concrete (…, N) array so
        every call hits ONE compiled signature (all-ones cached per
        shape)."""
        if w is not None:
            return jnp.asarray(w, jnp.float32)
        cache = getattr(self, "_ones_cache", None)
        if cache is None:
            cache = self._ones_cache = {}
        shape = tuple(lead) + (n,)
        if shape not in cache:
            cache[shape] = jnp.ones(shape, jnp.float32)
        return cache[shape]

    def release(self) -> None:
        """Unload the compiled programs. A loaded executable keeps its
        temporaries reserved on the device for as long as it lives (6.7 GB
        for a language model that fills the chip, chip runs of PR 32):
        whoever needs the device for something else afterwards calls this
        once the last state of this step is gone. The next `train` or
        `evaluate` compiles again."""
        self._train_fn = self._eval_fn = self._train_many_fn = None
        self._gather_fn = None
        self._train_accum_fns = None
        # (jax's own caches hold an executable past its function)
        jax.clear_caches()

    def train(self, state, x, y, w=None):
        """One fused training step. Returns (new_state, (loss, n_err)).
        `w` is the Loader's (N,) pad mask (None == all-ones)."""
        if self._train_fn is None:
            self._build()
        # the HOST side of the async dispatch, numbered by this step's
        # own count of dispatches (in a train-only loop that is the
        # batch's number too: loader.produce#k -> feed.device_put#k ->
        # train.dispatch#k)
        with _tracer.span("train.dispatch", "step", self.n_dispatched):
            self._check_batch(np.shape(x)[0])
            x, y = self._seq_xy(x, y)
            w = self._weights_or_ones(w, np.shape(x)[0])
            new_state, loss, n_err = self._train_fn(state, x, y, w)
        self.n_dispatched += 1
        return new_state, (loss, n_err)

    def confusion(self, state, x, y, n_classes: int, w=None):
        """(C, C) confusion counts (true row, predicted col) for one
        minibatch, pad-mask weighted — the fused-mode companion of
        EvaluatorSoftmax's per-minibatch accumulation (the granular
        graph fills it unit-side; the fused step otherwise never
        materializes predictions). Traced dense (`local_trace`): plain
        jit + GSPMD propagation covers sharded params. Returns None for
        non-classifier output shapes (seq heads etc.)."""
        if getattr(self._last_fwd(), "output", None) is None:
            return None
        out_shape = getattr(self._last_fwd().output, "shape", ())
        if len(out_shape) != 2 or np.size(y) != np.shape(x)[0]:
            # (N, C) one-label-per-sample classifier heads only: flat
            # (N*S,) sequence heads would need per-position pad-weight
            # repeats (granular mode's _w_repeat) — not worth a second
            # convention here
            return None
        from veles_tpu.parallel.mesh import is_multihost
        if is_multihost(self.mesh):
            # multi-host: the per-host input sharding zero-fills
            # non-local rows, which a dense plain-jit forward WOULD read
            # (unlike the sharded evaluate) — skip rather than corrupt
            return None
        if self._conf_fns is None:
            self._conf_fns = {}
        fn = self._conf_fns.get(n_classes)
        if fn is None:
            def body(params, xb, yb, wb):
                out = self._forward(params, xb,
                                    jax.random.PRNGKey(0), False,
                                    local_trace=True)
                pred = jnp.argmax(out, axis=-1).reshape(-1)
                yr = yb.reshape(-1).astype(jnp.int32)
                m = jnp.zeros((n_classes, n_classes), jnp.float32)
                return m.at[yr, pred].add(wb.reshape(-1))
            fn = self._conf_fns[n_classes] = jax.jit(body)
        w = self._weights_or_ones(w, np.shape(x)[0])
        # DEVICE array by design: callers accumulate on device across the
        # class pass and sync once at the boundary (the loop's
        # one-host-sync-per-pass pipelining contract)
        return fn(state["params"], x, y, w)

    def _last_fwd(self):
        return self.forwards[-1] if self.forwards else None

    def variant_table(self) -> Dict[str, str]:
        """{op: variant-name} this step would trace right now, for every
        tunable op its forward chain contains — what bench records and
        the supervisor's exit report embed so a measured number always
        names the lowerings that produced it. A claimed fused pair
        reports the FUSED winner for the fusion op itself, and for each
        member op (qualified as ``<fusion-op>/<winner>``) UNLESS an
        unclaimed unit of that op still traces a normal lowering — an
        op-level entry must never name a lowering no unit traced, and a
        still-composed sibling's (possibly overridden) name must not be
        clobbered by the pair's claim."""
        from veles_tpu.ops import variants
        table: Dict[str, str] = {}
        pairs = self.fusion_pairs()           # mirror _forward's claims
        claimed = {i for i, _, _ in pairs} | {j for _, j, _ in pairs}
        for i, u in enumerate(self.forwards):
            op = getattr(u, "variant_op", None)
            if op is None or i in claimed:
                # a claimed unit traces the fused kernel, not its own
                # registry resolution — reported below, qualified
                continue
            u.allow_pallas = self.allow_pallas      # mirror _chain
            # units whose traced lowering can diverge from the raw
            # registry resolution (conv per-layer s2d override /
            # inapplicable auto stems) report through variant_effective;
            # None = no decision traced for this layer, don't report it
            eff = getattr(u, "variant_effective", None)
            name = eff() if eff is not None \
                else variants.resolve(op, unit=u).name
            if name is not None:
                table[op] = name
            # a unit that resolves further ops at trace time (a block's
            # latent attention beside its hyper-connections) names them
            more = getattr(u, "variant_more", None)
            if more is not None:
                table.update(more())
        for i, j, v in pairs:
            a, b = self.forwards[i], self.forwards[j]
            if getattr(a, "variant_op", None) == "lrn":
                table["lrn_maxpool"] = v.name
                table.setdefault("lrn", f"lrn_maxpool/{v.name}")
                table.setdefault("maxpool", f"lrn_maxpool/{v.name}")
            else:       # conv_stem epilogue claiming the successor LRN
                table.setdefault("conv_stem", v.name)
                table.setdefault(getattr(b, "variant_op", "lrn"),
                                 f"conv_stem/{v.name}")
        if self.zero_active:
            # the ZeRO reduce-scatter resolves through the registry like
            # any tunable lowering: a measured number must name which
            # grad_reduce variant moved the gradient bytes. Read through
            # the step's cached resolution so reported == traced even
            # across a registry re-selection.
            table["grad_reduce"] = self._grad_reduce_variant().name
        if not self.zero_active and any(
                isinstance(c, optim.SGDConfig) for c in self.cfgs):
            # the replicated SGD leg resolves through the registry (see
            # _apply_update); ZeRO's slice-wise update does not.
            table["sgd_update"] = self._sgd_variant().name
        exchange = self.grad_exchange()
        if exchange is not None:
            table["grad_exchange"] = exchange
        return table

    def grad_exchange(self) -> Optional[str]:
        """How the replicated dp update's gradients cross the mesh a step,
        at the rows a chip the step last traced (before any trace: the
        rows the units were initialised for): the dense units whose
        weight gradient is formed from gathered operands, over the units
        with parameters; the operand bytes that puts on the wire and the
        gradient bytes it takes off; the gradient bytes still all-reduced.
        None where the update exchanges no partials of its own or shards
        them (local, gspmd, seq, EP, ZeRO): a report never names what the
        step did not trace."""
        if not self._exchanges_partials() or self.zero_active:
            return None
        n = self.mesh.shape[DATA_AXIS]
        rows = self._rows_traced
        if rows is None:
            first = getattr(self.forwards[0], "input", None)
            if not first:
                return None
            rows = first.shape[0] // n
        gathered = self._gathered_units(rows)
        off = on = summed = with_params = 0
        for i, u in enumerate(self.forwards):
            lb = _unit_param_bytes(u)
            with_params += bool(lb)
            if i in gathered:
                off += lb
                on += dense_grad_wire(*self._dense_case(i, rows))[1]
            else:
                summed += lb
        return (f"{len(gathered)} of {with_params} units gather at {rows} "
                f"rows x {n} chips: {on / 1e6:.1f} MB of operands "
                f"all-gathered for {off / 1e6:.1f} MB of gradient not "
                f"all-reduced, {summed / 1e6:.1f} MB all-reduced")

    def evaluate(self, state, x, y, w=None):
        """Forward-only metrics (validation/test minibatches)."""
        if self._eval_fn is None:
            self._build()
        self._check_batch(np.shape(x)[0])
        x, y = self._seq_xy(x, y)
        w = self._weights_or_ones(w, np.shape(x)[0])
        return self._eval_fn(state["params"], x, y, w)

    def train_repeat(self, state, x, y, k: int, w=None):
        """K sequential updates on ONE device-resident minibatch in a
        single dispatch (lax.scan with no scanned inputs). Same scanned
        hot loop as train_many but device memory holds one batch
        regardless of K — the benchmark path, where K× input copies
        would dominate HBM at large batch. Returns
        (state, (losses, n_errs)) with leading dim K."""
        self._check_batch(np.shape(x)[0])
        x, y = self._seq_xy(x, y)
        w = self._weights_or_ones(w, np.shape(x)[0])
        cache = getattr(self, "_train_repeat_fns", None)
        if cache is None:
            cache = self._train_repeat_fns = {}
        if k not in cache:
            axis = {"dp": DATA_AXIS, "seq": (DATA_AXIS, SEQ_AXIS)}.get(
                self.mode)

            def train_repeat_steps(state, x, y, w):
                def step(st, _):
                    st2, loss, n_err = self._train_body(st, x, y, w,
                                                        axis=axis)
                    return st2, (loss, n_err)
                return lax.scan(step, state, None, length=k)

            donate = (0,) if self.donate else ()
            if self.mode == "local":
                cache[k] = jax.jit(train_repeat_steps, donate_argnums=donate)
            elif self.mode in ("dp", "seq"):
                spec = (P(DATA_AXIS, SEQ_AXIS) if self.mode == "seq"
                        else P(DATA_AXIS))
                ssp = (self._smap_state_spec() if self.mode == "dp"
                       else self._seq_state_spec())
                sm = shard_map(
                    train_repeat_steps, mesh=self.mesh,
                    in_specs=(ssp, spec, spec, P(DATA_AXIS)),
                    out_specs=(ssp, (P(), P())))
                cache[k] = jax.jit(sm, donate_argnums=donate)
            elif self.mode == "gspmd":
                xsh = NamedSharding(self.mesh, P(DATA_AXIS))
                ssh = self._state_shardings()
                repl = NamedSharding(self.mesh, P())
                cache[k] = jax.jit(
                    train_repeat_steps, in_shardings=(ssh, xsh, xsh, xsh),
                    out_shardings=(ssh, (repl, repl)),  # see _build: pin
                    # the returned state to the plan, not propagation
                    donate_argnums=donate)
            else:
                raise ValueError(f"unknown mode {self.mode!r}")
            cache[k] = _tracer.FirstCall(
                cache[k], functools.partial(cache.__setitem__, k))
        return cache[k](state, x, y, w)

    def train_accum(self, state, x, y, k: int, w=None):
        """ONE optimizer update from the full (N,)-batch gradient,
        computed as K scanned microbatches of N/K samples — activation
        memory O(N/K), numerics equal to `train()` on the full batch
        (same global weight normalization; dropout draws per-microbatch
        keys). The TPU-first form of the reference's gradient
        accumulation (`apply_gradients` gate, SURVEY.md §2.8): use it to
        train at effective batch sizes whose activations do not fit HBM.
        Returns (state, (loss, n_err)) for the whole batch."""
        n = np.shape(x)[0]
        if n % k:
            raise ValueError(f"batch {n} not divisible by k={k}")
        m = n // k
        self._check_batch(m)   # each MICROBATCH must divide the data axis
        x, y = self._seq_xy(x, y)
        w = self._weights_or_ones(w, n)
        xs = jnp.reshape(x, (k, m) + tuple(np.shape(x)[1:]))
        ys = jnp.reshape(y, (k, m) + tuple(np.shape(y)[1:]))
        ws = jnp.reshape(w, (k, m))
        cache = getattr(self, "_train_accum_fns", None)
        if cache is None:
            cache = self._train_accum_fns = {}
        if k not in cache:
            axis = {"dp": DATA_AXIS, "seq": (DATA_AXIS, SEQ_AXIS)}.get(
                self.mode)

            def train_accum_step(state, xs, ys, ws):
                st2, loss, n_err = self._accum_body(state, xs, ys, ws,
                                                    axis=axis)
                return st2, (loss, n_err)

            donate = (0,) if self.donate else ()
            if self.mode == "local":
                cache[k] = jax.jit(train_accum_step, donate_argnums=donate)
            elif self.mode in ("dp", "seq"):
                spec = (P(None, DATA_AXIS, SEQ_AXIS)
                        if self.mode == "seq" else P(None, DATA_AXIS))
                ssp = (self._smap_state_spec() if self.mode == "dp"
                       else self._seq_state_spec())
                sm = shard_map(
                    train_accum_step, mesh=self.mesh,
                    in_specs=(ssp, spec, spec, P(None, DATA_AXIS)),
                    out_specs=(ssp, (P(), P())))
                cache[k] = jax.jit(sm, donate_argnums=donate)
            elif self.mode == "gspmd":
                xsh = NamedSharding(self.mesh, P(None, DATA_AXIS))
                ssh = self._state_shardings()
                repl = NamedSharding(self.mesh, P())
                cache[k] = jax.jit(
                    train_accum_step, in_shardings=(ssh, xsh, xsh, xsh),
                    out_shardings=(ssh, (repl, repl)),  # see _build
                    donate_argnums=donate)
            else:
                raise ValueError(f"unknown mode {self.mode!r}")
            cache[k] = _tracer.FirstCall(
                cache[k], functools.partial(cache.__setitem__, k))
        with _tracer.span("train.dispatch", "step", self.n_dispatched):
            out = cache[k](state, xs, ys, ws)
        self.n_dispatched += 1
        return out

    def train_many(self, state, xs, ys, ws=None):
        """K training steps in ONE dispatch: xs (K, batch, ...), ys
        (K, batch). A lax.scan over minibatches inside jit — K real
        sequential updates, one host->device round trip. This is the
        dispatch-amortized hot loop (the reference's analog was K×dozens
        of kernel enqueues). Works in every mode: local plain scan,
        "dp" as scan INSIDE the shard_map (collectives fire per scan
        iteration), "gspmd" as a scan whose per-step batch carries the
        data-axis sharding. Returns (state, (losses, n_errs)) with
        leading dim K."""
        self._check_batch(np.shape(xs)[1])
        xs, ys = self._seq_xy(xs, ys, batched=True)
        ws = self._weights_or_ones(ws, np.shape(xs)[1],
                                   lead=(np.shape(xs)[0],))
        if self._train_many_fn is None:
            axis = {"dp": DATA_AXIS, "seq": (DATA_AXIS, SEQ_AXIS)}.get(
                self.mode)

            def train_many_steps(state, xs, ys, ws):
                def step(st, xyw):
                    st2, loss, n_err = self._train_body(
                        st, xyw[0], xyw[1], xyw[2], axis=axis)
                    return st2, (loss, n_err)
                return lax.scan(step, state, (xs, ys, ws))

            donate = (0,) if self.donate else ()
            if self.mode == "local":
                self._train_many_fn = jax.jit(train_many_steps,
                                              donate_argnums=donate)
            elif self.mode in ("dp", "seq"):
                spec = (P(None, DATA_AXIS, SEQ_AXIS)
                        if self.mode == "seq" else P(None, DATA_AXIS))
                wspec = P(None, DATA_AXIS)
                ssp = (self._smap_state_spec() if self.mode == "dp"
                       else self._seq_state_spec())
                sm = shard_map(
                    train_many_steps, mesh=self.mesh,
                    in_specs=(ssp, spec, spec, wspec),
                    out_specs=(ssp, (P(), P())))
                self._train_many_fn = jax.jit(sm, donate_argnums=donate)
            elif self.mode == "gspmd":
                xsh = NamedSharding(self.mesh, P(None, DATA_AXIS))
                ssh = self._state_shardings()
                repl = NamedSharding(self.mesh, P())
                self._train_many_fn = jax.jit(
                    train_many_steps, in_shardings=(ssh, xsh, xsh, xsh),
                    out_shardings=(ssh, (repl, repl)),  # see _build
                    donate_argnums=donate)
            else:
                raise ValueError(f"unknown mode {self.mode!r}")
            _tracer.FirstCall.on(self, "_train_many_fn")
        return self._train_many_fn(state, xs, ys, ws)
