"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh
"stage" axis.

Absent in the reference (SURVEY.md §2.4: PP = NO) — added so the parallel
layer covers the full dp/tp/sp/ep/pp axis set. The TPU-native shape of
the idea (scaling-book recipe): each device owns ONE stage; a `lax.scan`
runs M + S − 1 ticks; per tick every device applies its stage to its
current activation and `ppermute`s the result to the next stage — at
steady state all S stages compute concurrently on different
microbatches. The bubble is the standard (S−1)/(M+S−1). Autodiff flows
through scan+ppermute, so `jax.grad` yields per-stage parameter
gradients — no hand-written backward schedule.

Two layers here:
- `pipeline_apply`/`make_pipeline` — the homogeneous-stage primitive
  (every stage same width; stacked per-stage params sharded over the
  stage axis);
- `PipelineTrainStep` — the WORKFLOW integration: partitions a
  StandardWorkflow's forward chain into S contiguous HETEROGENEOUS
  stages (different widths/ranks), runs each device's stage via
  `lax.switch` on its stage index over width-padded flat activations,
  computes the evaluator loss on the last stage's logits and applies
  each GD twin's SGD hyperparameters — the same training semantics as
  FusedTrainStep, scheduled as a pipeline.

  Params are STAGE-RESIDENT (v2): each stage's heterogeneous param
  dicts flatten into one row of an (S, L) f32 array sharded over the
  stage axis, so per-device param HBM is the largest stage (≈ total/S),
  not the whole model — the reason pipeline parallelism exists. Each
  branch statically unflattens ITS stage's layout from the local row;
  gradients stay stage-local (the flat array enters shard_map varying,
  so no cross-stage psum touches params), and the SGD+momentum update
  runs elementwise on the flat rows with per-element coefficient groups
  (layer lr / bias-lr / decay looked up by group id), which is exactly
  the per-layer `sgd_update` math fused into one VPU pass.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from jax import shard_map
from jax.lax import axis_size as _axis_size
from jax.lax import pcast

from veles_tpu.telemetry import tracer as _tracer

STAGE_AXIS = "stage"


def pipeline_apply(stage_fn: Callable, params, xs, axis_name: str = STAGE_AXIS):
    """Run microbatches through the pipeline. Call INSIDE shard_map with:
    - `params`: this device's stage params (leading stage dim already
      split away by the shard_map in_spec);
    - `xs`: (M, mb, D) microbatches, replicated (only stage 0 reads them);
    - `stage_fn(params, x) -> y` with y.shape == x.shape.
    Returns (M, mb, D) outputs (valid on every device after the final
    psum-broadcast from the last stage)."""
    s = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m, mb, d = xs.shape
    ticks = m + s - 1

    def tick(carry, t):
        act, outputs = carry
        mb_idx = t - idx                       # which microbatch this
        # stage would be processing at tick t
        inject = xs[jnp.clip(t, 0, m - 1)]
        is_first = (idx == 0)
        x_in = jnp.where(is_first, inject, act)
        y = stage_fn(params, x_in)
        valid = (mb_idx >= 0) & (mb_idx < m)
        is_last = (idx == s - 1)
        write = (valid & is_last).astype(y.dtype)
        outputs = outputs.at[jnp.clip(mb_idx, 0, m - 1)].add(write * y)
        act_next = lax.ppermute(y, axis_name,
                                [(i, (i + 1) % s) for i in range(s)])
        return (act_next, outputs), None

    # the scan carry mixes with device-varying values (idx, params), so
    # it must start varying over the stage axis (shard_map vma typing;
    # pcast is the non-deprecated spelling of pvary)
    act0 = pcast(jnp.zeros((mb, d), xs.dtype), (axis_name,),
                 to="varying")
    out0 = pcast(jnp.zeros_like(xs), (axis_name,), to="varying")
    (act, outputs), _ = lax.scan(tick, (act0, out0),
                                 jnp.arange(ticks))
    # broadcast the last stage's outputs to every device (simple v1
    # epilogue; a real deployment would keep them stage-resident)
    last = (idx == s - 1).astype(outputs.dtype)
    return lax.psum(outputs * last, axis_name)


def make_pipeline(mesh: Mesh, stage_fn: Callable,
                  axis_name: str = STAGE_AXIS):
    """jit-compiled pipeline runner over `mesh`:
    `run(params_stacked, xs)` with params_stacked leading dim = S (sharded
    over the stage axis) and xs (M, mb, D) microbatches. Differentiable."""

    def inner(params, xs):
        # shard_map splits the leading stage dim; squeeze it away
        local = jax.tree_util.tree_map(lambda a: a[0], params)
        return pipeline_apply(stage_fn, local, xs, axis_name)

    pspec = P(axis_name)   # prefix spec: applies to every params leaf
    return jax.jit(shard_map(
        inner, mesh=mesh, in_specs=(pspec, P()), out_specs=P()))


# ---------------------------------------------------------------------------
# workflow integration: heterogeneous stages, trained
# ---------------------------------------------------------------------------


def make_stage_mesh(devices=None) -> Mesh:
    """1-D mesh over the "stage" axis (one device per pipeline stage)."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (STAGE_AXIS,))


def split_stages(forwards: Sequence, n_stages: int,
                 boundaries: Optional[Sequence[int]] = None) -> List[List]:
    """Partition the forward chain into contiguous stages. Default
    boundaries balance cumulative parameter bytes (the dominant per-stage
    cost for FC chains); pass explicit `boundaries` (unit indices where a
    new stage starts) to override."""
    units = list(forwards)
    if n_stages > len(units):
        raise ValueError(
            f"{n_stages} stages but only {len(units)} units — build the "
            "stage mesh over at most len(forwards) devices")
    if boundaries is not None:
        if len(boundaries) != n_stages - 1:
            raise ValueError(
                f"boundaries must list the {n_stages - 1} stage-start "
                f"indices (got {len(boundaries)})")
        if list(boundaries) != sorted(set(boundaries)) or (
                boundaries and (boundaries[0] < 1
                                or boundaries[-1] >= len(units))):
            raise ValueError(f"boundaries must be strictly increasing "
                             f"unit indices in [1, {len(units) - 1}]: "
                             f"{boundaries}")
        bounds = [0] + list(boundaries) + [len(units)]
    else:
        costs = np.asarray([
            max(1.0, sum(float(np.prod(a.shape)) if a else 0.0
                         for a in u.param_arrays().values()))
            for u in units])
        cum = np.cumsum(costs) / costs.sum()
        bounds = [0]
        for s in range(1, n_stages):
            target = s / n_stages
            i = int(np.searchsorted(cum, target)) + 1
            bounds.append(min(max(i, bounds[-1] + 1),
                              len(units) - (n_stages - s)))
        bounds.append(len(units))
    stages = [units[bounds[i]:bounds[i + 1]] for i in range(n_stages)]
    assert all(stages), f"empty stage: bounds={bounds}"
    return stages


class PipelineTrainStep:
    """Train a StandardWorkflow chain as an S-stage GPipe pipeline.

    The loader minibatch (N, …) splits into M microbatches of N/M; each
    tick runs ONE stage per device (lax.switch on the stage index) on a
    flat activation padded to the widest inter-stage boundary. Loss and
    n_err use the same weighted forms as FusedTrainStep (evaluator
    parity), and the per-layer SGD update applies each GD twin's
    hyperparameters. Stochastic units (dropout/stochastic pooling) are
    not yet supported in the pipeline schedule — build the step with a
    deterministic chain."""

    def __init__(self, workflow, mesh: Mesh, n_microbatches: int,
                 boundaries: Optional[Sequence[int]] = None,
                 compute_dtype: Optional[Any] = None,
                 dispatch: str = "auto",
                 input_normalize: Optional[Dict[str, Any]] = None) -> None:
        from veles_tpu.parallel.fused import pair_gd_configs
        self.mesh = mesh
        self.n_micro = n_microbatches
        #: on-device input prologue {"scale", "offset", "mean"} (the
        #: uint8-wire contract, loader wire_format/device_feed): raw
        #: integer batches are normalized on device in _microbatch,
        #: BEFORE flattening/padding — the mean is image-shaped, and the
        #: pipeline scan carries activations in one dtype, so the
        #: conversion must land before microbatches enter the schedule.
        self.input_normalize = (dict(input_normalize)
                                if input_normalize else None)
        #: how a device picks its stage each tick:
        #: - "switch": lax.switch — only the selected stage's ops execute
        #:   (the pipelining point). VALIDATED ONLY ON TPU MESHES: on the
        #:   CPU backend, switch over heterogeneous branches inside
        #:   scan+shard_map corrupts the allocator heap (reproduced on
        #:   jax 0.9 / 8-device virtual CPU: "free(): invalid next size"
        #:   AND silently wrong step-2 numerics), so
        #: - "select": compute every stage and lax.select_n the result —
        #:   branchless and correct everywhere, at S× per-tick compute;
        #:   the CPU-mesh default (tests, dryrun).
        #: - "auto": "switch" on TPU devices, "select" otherwise.
        if dispatch == "auto":
            plat = mesh.devices.flat[0].platform
            dispatch = "switch" if plat == "tpu" else "select"
        assert dispatch in ("switch", "select"), dispatch
        self.dispatch = dispatch
        self.forwards = list(workflow.forwards)
        for u in self.forwards:
            if getattr(u, "fused_needs_key", False):
                raise ValueError(
                    f"{type(u).__name__} needs per-step RNG; the pipeline "
                    "schedule does not thread keys yet (SURVEY.md §2.4 "
                    "PP row) — use FusedTrainStep for stochastic chains")
        self.loss_kind = workflow.loss
        self.n_classes = getattr(workflow, "n_classes", None)
        self.compute_dtype = compute_dtype
        self.gd_units, self.cfgs = pair_gd_configs(workflow)
        from veles_tpu.ops import optim as _optim
        if any(isinstance(c, _optim.AdamConfig) for c in self.cfgs):
            raise ValueError(
                "PipelineTrainStep supports the SGD family only "
                "(gd_config optimizer='adam' -> use FusedTrainStep)")
        s = mesh.shape[STAGE_AXIS]
        self.stages = split_stages(self.forwards, s, boundaries)
        # unit index ranges per stage + boundary activation shapes
        self._ranges = []
        i = 0
        for st in self.stages:
            self._ranges.append((i, i + len(st)))
            i += len(st)
        # per-stage input sample shapes (known post-initialize)
        self.in_shapes = [tuple(st[0].input.shape[1:])
                          for st in self.stages]
        self.out_shape = tuple(self.forwards[-1].output.shape[1:])
        widths = [int(np.prod(sh)) for sh in
                  self.in_shapes + [self.out_shape]]
        self.pad_width = max(widths)
        self._build_param_layout()
        self._train_fn = None
        #: train steps dispatched: the number a train.dispatch span carries
        self.n_dispatched = 0
        self._eval_fn = None

    # -- stage-resident flat parameter layout (v2) ---------------------------

    def _build_param_layout(self) -> None:
        """Each stage's params flatten into one row of an (S, L) array
        (L = widest stage); `_layouts[si]` records (unit, name, shape,
        lo, hi) slices. Every flat element gets a coefficient GROUP id
        (2·unit + is_bias; L-padding -> the frozen group 0 with lr=0) so
        the fused elementwise update applies exactly the per-layer /
        per-bias SGD hyperparameters of `ops.optim.sgd_update`."""
        self._layouts = []
        rows = []
        for lo_u, hi_u in self._ranges:
            off, lay = 0, []
            for i in range(lo_u, hi_u):
                for name, arr in self.forwards[i].param_arrays().items():
                    if not arr:
                        continue
                    size = int(np.prod(arr.shape))
                    lay.append((i, name, tuple(arr.shape), off, off + size))
                    off += size
            self._layouts.append(lay)
            rows.append(off)
        self.param_row = max(rows + [1])
        s = len(self.stages)
        gid = np.zeros((s, self.param_row), np.int32)   # 0 = frozen pad
        n_groups = 2 * len(self.forwards) + 1
        tabs = np.zeros((4, n_groups), np.float32)      # lr/mom/wd/l1
        for si, lay in enumerate(self._layouts):
            for i, name, shape, lo, hi in lay:
                cfg = self.cfgs[i]
                bias = len(shape) == 1
                g = 1 + 2 * i + int(bias)
                gid[si, lo:hi] = g
                lr = cfg.lr * (cfg.lr_bias_mult
                               if bias and cfg.lr_bias_mult != 1.0
                               else 1.0)
                tabs[:, g] = (lr, cfg.momentum, cfg.weight_decay,
                              cfg.l1_decay)
        self._gid_host = gid
        self._coef_tabs = tabs

    def _stage_sharding(self):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, P(STAGE_AXIS))

    # -- state ----------------------------------------------------------------

    def _put_staged(self, x, sh):
        """device_put, or — when the stage mesh spans processes (PP over
        DCN) — a jit reshard, since device_put rejects shardings with
        non-addressable devices (same convention as FusedTrainStep.
        _shard_state: the host value is identical on every process)."""
        from veles_tpu.parallel.mesh import is_multihost
        if is_multihost(self.mesh):
            return jax.jit(lambda t: t, out_shardings=sh)(x)
        return jax.device_put(x, sh)

    @_tracer.in_phase("setup.init_state")
    def init_state(self) -> Dict[str, Any]:
        from veles_tpu import prng
        s = len(self.stages)
        flat = np.zeros((s, self.param_row), np.float32)
        for si, lay in enumerate(self._layouts):
            for i, name, shape, lo, hi in lay:
                flat[si, lo:hi] = \
                    self.forwards[i].param_arrays()[name].mem.ravel()
        sh = self._stage_sharding()
        if getattr(self, "_gid", None) is None:
            self._gid = self._put_staged(self._gid_host, sh)
        return {"params": self._put_staged(flat, sh),
                "vel": self._put_staged(np.zeros_like(flat), sh),
                "key": prng.get().next_key(),
                "lr_scale": jnp.float32(1.0)}

    def params_dicts(self, state) -> tuple:
        """Host-side per-layer param dicts recovered from the flat rows
        (tests/introspection; write_back uses the same unflatten)."""
        flat = state["params"]
        if not getattr(flat, "is_fully_addressable", True):
            # stage rows live on remote processes (PP over DCN): gather
            # to replicated first. COLLECTIVE — every process must call
            # write_back/params_dicts at the same point (they do: the
            # _run_with_step paths are symmetric). Cached like fused's
            # _gather_fn so repeated write_backs reuse the executable.
            if getattr(self, "_gather_fn", None) is None:
                from jax.sharding import NamedSharding
                self._gather_fn = jax.jit(
                    lambda t: t,
                    out_shardings=NamedSharding(self.mesh, P()))
            flat = self._gather_fn(flat)
        flat = np.asarray(flat)
        out = [dict() for _ in self.forwards]
        for si, lay in enumerate(self._layouts):
            for i, name, shape, lo, hi in lay:
                out[i][name] = flat[si, lo:hi].reshape(shape)
        return tuple(out)

    def write_back(self, state: Dict[str, Any]) -> None:
        for u, p in zip(self.forwards, self.params_dicts(state)):
            for k, arr in u.param_arrays().items():
                if k in p:
                    arr.reset(p[k])

    # -- stage bodies ---------------------------------------------------------

    def _stage_branch(self, si: int):
        lo, hi = self._ranges[si]
        in_shape = self.in_shapes[si]
        d_in = int(np.prod(in_shape))
        lay = self._layouts[si]

        def branch(flat_row, x2d):
            params = {i: {} for i in range(lo, hi)}
            for i, name, shape, p_lo, p_hi in lay:
                params[i][name] = flat_row[p_lo:p_hi].reshape(shape)
            mb = x2d.shape[0]
            x = x2d[:, :d_in].reshape((mb,) + in_shape)
            for i in range(lo, hi):
                p = params[i]
                if self.compute_dtype is not None:
                    from veles_tpu.parallel.fused import _tree_cast
                    p = _tree_cast(p, self.compute_dtype)
                x = self.forwards[i].fused_apply(p, x)
            flat = x.reshape(mb, -1)
            pad = self.pad_width - flat.shape[1]
            if pad:
                flat = jnp.pad(flat, ((0, 0), (0, pad)))
            return flat

        return branch

    def _pipe_forward(self, flat_row, xs_pad):
        """flat_row: this device's (param_row,) stage params;
        xs_pad: (M, mb, pad_width) padded input microbatches ->
        (M, mb, pad_width) last-stage outputs (psum-broadcast)."""
        branches = [self._stage_branch(si)
                    for si in range(len(self.stages))]

        def stage_fn(p, x2d):
            idx = lax.axis_index(STAGE_AXIS)
            if self.dispatch == "switch":
                # params ride the closure, not the switch operands: only
                # the selected branch executes per tick — and each branch
                # reads its OWN stage's layout from the local row
                return lax.switch(idx, [
                    (lambda xx, b=b: b(p, xx)) for b in branches], x2d)
            # select_n: every branch unflattens the local row with ITS
            # layout; non-selected results (garbage reinterpretations of
            # another stage's bytes) are discarded, and select_n's VJP
            # routes cotangents only to the selected branch, so grads
            # stay exact
            return lax.select_n(idx, *[b(p, x2d) for b in branches])

        return pipeline_apply(stage_fn, flat_row, xs_pad, STAGE_AXIS)

    def _loss(self, flat_row, xs_pad, y, w):
        from veles_tpu.ops import xla as ox
        outs = self._pipe_forward(flat_row, xs_pad)   # (M, mb, pad)
        c = int(np.prod(self.out_shape))
        logits = outs[..., :c].astype(jnp.float32)    # f32 loss/metrics
        if self.loss_kind == "softmax":
            wt = jnp.broadcast_to(w.reshape(y.shape[:w.ndim] +
                                            (1,) * (y.ndim - w.ndim)),
                                  y.shape).astype(jnp.float32)
            loss = ox.ce_loss_from_logits(logits, y, self.n_classes,
                                          weights=wt)
            n_err = ((logits.reshape(-1, c).argmax(-1) != y.reshape(-1))
                     & (wt.reshape(-1) > 0)).sum()
        else:
            loss, _ = ox.mse(logits.reshape((-1,) + (c,)),
                             y.reshape(-1, c), weights=w.reshape(-1))
            n_err = loss
        return loss, n_err

    # -- public API -----------------------------------------------------------

    def input_put_specs(self):
        """Device-feed put layout: the pipeline's shard_map consumes
        replicated inputs (only stage 0 reads them), so the async put
        replicates — still issued one step ahead of consumption."""
        return (P(), P(), P())

    def _microbatch(self, x, y, w):
        m = self.n_micro
        n = x.shape[0]
        assert n % m == 0, (n, m)
        mb = n // m
        x = jnp.asarray(x)
        if self.input_normalize is not None:
            # uint8 wire: eager DEVICE ops (x is already resident when a
            # DeviceFeed delivers it) — the transfer stays raw bytes
            from veles_tpu.parallel.fused import apply_input_normalize
            x = apply_input_normalize(self.input_normalize, x)
        flat = x.reshape(n, -1)
        if self.compute_dtype is not None:
            # inter-stage activations (and the ppermute traffic) ride the
            # compute dtype; the loss head casts back to f32
            flat = flat.astype(self.compute_dtype)
        pad = self.pad_width - flat.shape[1]
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        xs = flat.reshape(m, mb, self.pad_width)
        y = jnp.asarray(y).reshape((m, mb) + jnp.asarray(y).shape[1:])
        w = jnp.asarray(w, jnp.float32).reshape(m, mb)
        return xs, y, w

    def train_callable(self):
        """The UNJITTED shard_map-wrapped train body (state, gid, xs, y,
        w) -> (state, loss, n_err) that `_build` wraps in jax.jit —
        exposed for the jaxpr auditor (analysis/trace.py), which traces
        it abstractly without compiling."""
        tabs = jnp.asarray(self._coef_tabs)   # (4, G): lr/mom/wd/l1

        def train_body(state, gid, xs, y, w):
            def lf(pf):
                loss, n_err = self._loss(pf[0], xs, y, w)
                return loss, (loss, n_err)

            (_, (loss, n_err)), g = jax.value_and_grad(
                lf, has_aux=True)(state["params"])
            p, v = state["params"], state["vel"]
            # fused elementwise SGD over the local stage row: exactly
            # sgd_update's per-layer math, coefficients gathered by group
            lr = jnp.take(tabs[0], gid) * state["lr_scale"]
            mom = jnp.take(tabs[1], gid)
            wd = jnp.take(tabs[2], gid)
            l1 = jnp.take(tabs[3], gid)
            reg = g + wd * p + l1 * jnp.sign(p)
            v2 = mom * v - lr * reg
            p2 = p + v2
            new_state = {"params": p2, "vel": v2, "key": state["key"],
                         "lr_scale": state["lr_scale"]}
            return new_state, loss, n_err

        ssp = {"params": P(STAGE_AXIS), "vel": P(STAGE_AXIS),
               "key": P(), "lr_scale": P()}
        return shard_map(
            train_body, mesh=self.mesh,
            in_specs=(ssp, P(STAGE_AXIS), P(), P(), P()),
            out_specs=(ssp, P(), P()))

    @_tracer.in_phase("setup.build_step")
    def _build(self) -> None:

        def eval_body(params, xs, y, w):
            return self._loss(params[0], xs, y, w)

        self._train_fn = jax.jit(self.train_callable())
        self._eval_fn = jax.jit(shard_map(
            eval_body, mesh=self.mesh,
            in_specs=(P(STAGE_AXIS), P(), P(), P()),
            out_specs=(P(), P())))
        # each behind its first call's phase (`setup.first_dispatch`)
        _tracer.FirstCall.on(self, "_train_fn")
        _tracer.FirstCall.on(self, "_eval_fn")

    def train(self, state, x, y, w=None):
        if self._train_fn is None:
            self._build()
        if w is None:
            w = np.ones(np.shape(x)[0], np.float32)
        with _tracer.span("train.dispatch", "step", self.n_dispatched):
            xs, y, w = self._microbatch(x, y, w)
            new_state, loss, n_err = self._train_fn(state, self._gid, xs,
                                                    y, w)
        self.n_dispatched += 1
        return new_state, (loss, n_err)

    def evaluate(self, state, x, y, w=None):
        if self._eval_fn is None:
            self._build()
        if w is None:
            w = np.ones(np.shape(x)[0], np.float32)
        xs, y, w = self._microbatch(x, y, w)
        return self._eval_fn(state["params"], xs, y, w)
