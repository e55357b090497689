"""StandardWorkflow: declarative model builder.

Parity: reference `veles/znicz/standard_workflow.py` — builds
`loader → forwards… → evaluator → decision → gds…(reverse) → (loop)` from a
declarative `layers` list (`root.<model>.layers` in sample configs), with
the Decision's `complete` Bool gating the loop-back Repeater and EndPoint.

Layer dicts: {"type": <name>, ...kwargs}. Types live in the LAYER_TYPES
registry: the all2all family + softmax here; conv/pooling/normalization/
dropout modules append theirs when imported. An unknown type raises with
the currently-registered list.

TPU-first: the same graph can run granular (one jitted XLA computation per
unit — the debuggable mode, and the numpy golden mode for tests) or FUSED —
`build_fused_step()` compiles the entire forward+backward+update chain into
ONE donated XLA computation per minibatch, optionally sharded over a device
mesh (veles_tpu.parallel). That single fused step is the analog of the
reference's whole hot loop of §3.1 kernel enqueues.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from veles_tpu.loader.base import Loader
from veles_tpu.mutable import Bool
from veles_tpu.telemetry import tracer as _ttracer
from veles_tpu.units import Unit
from veles_tpu.workflow import Repeater, Workflow
from veles_tpu.znicz import all2all, gd  # noqa: F401 (gd registers pairs)
from veles_tpu.znicz.decision import DecisionGD
from veles_tpu.znicz.evaluator import EvaluatorMSE, EvaluatorSoftmax
from veles_tpu.znicz.nn_units import Forward, gd_for

#: layer-type name -> forward unit class (conv/pool types appended by
#: veles_tpu.znicz.conv/pooling at import time to avoid import cycles).
LAYER_TYPES: Dict[str, type] = {
    "all2all": all2all.All2All,
    "all2all_tanh": all2all.All2AllTanh,
    "all2all_relu": all2all.All2AllRELU,
    "all2all_strictrelu": all2all.All2AllStrictRELU,
    "all2all_sigmoid": all2all.All2AllSigmoid,
    "softmax": all2all.All2AllSoftmax,
}


class StandardWorkflow(Workflow):
    """loader + declarative layer list -> full supervised training graph."""

    def __init__(self, workflow=None,
                 layers: Sequence[Dict[str, Any]] = (),
                 loader: Optional[Loader] = None,
                 loss: str = "softmax",
                 n_classes: int = 10,
                 decision_config: Optional[Dict[str, Any]] = None,
                 gd_config: Optional[Dict[str, Any]] = None,
                 snapshot_config: Optional[Dict[str, Any]] = None,
                 plot_config: Optional[Dict[str, Any]] = None,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.layers_config = list(layers)
        self.loss = loss
        self.n_classes = n_classes
        self.repeater = Repeater(self, name="repeater")
        assert loader is not None, "StandardWorkflow needs a loader"
        self.loader = loader
        if loader.workflow is not self:
            self.add_unit(loader)
            loader.workflow = self

        # -- forwards --------------------------------------------------------
        self.forwards: List[Forward] = []
        prev: Unit = self.loader
        prev_attr = "minibatch_data"
        for spec in self.layers_config:
            spec = dict(spec)
            kind = spec.pop("type")
            if kind not in LAYER_TYPES:
                raise ValueError(
                    f"unknown layer type {kind!r}; registered types: "
                    f"{sorted(LAYER_TYPES)}")
            fwd = LAYER_TYPES[kind](self, **spec)
            fwd.link_attrs(prev, ("input", prev_attr))
            if hasattr(fwd, "link_loader"):  # dropout needs minibatch_class
                fwd.link_loader(self.loader)
            self.forwards.append(fwd)
            prev, prev_attr = fwd, "output"

        # -- evaluator ------------------------------------------------------
        if loss == "softmax":
            # a head that owns its loss (fused only) gives the evaluator
            # nothing to count: no vocabulary-squared confusion matrix
            owns = getattr(prev, "fused_emits_loss", False)
            self.evaluator = EvaluatorSoftmax(
                self, n_classes=1 if owns else n_classes,
                compute_confusion=not owns)
            self.evaluator.link_attrs(self.loader,
                                      ("labels", "minibatch_labels"))
        elif loss == "mse":
            self.evaluator = EvaluatorMSE(self)
            self.evaluator.link_attrs(self.loader,
                                      ("target", "minibatch_labels"))
        else:
            raise ValueError(f"unknown loss {loss!r}")
        # the Loader's pad mask weights the metrics: exact epoch totals
        # even when the final minibatch wraps (loader/base.py docstring)
        self.evaluator.link_attrs(self.loader,
                                  ("sample_weights", "minibatch_valid"))
        self.evaluator.link_attrs(prev, ("input", "output"))

        # -- decision -------------------------------------------------------
        self.decision = DecisionGD(self, **(decision_config or {}))
        self.decision.link_attrs(self.loader, "minibatch_class",
                                 "last_minibatch", "class_lengths")
        self.decision.link_attrs(self.evaluator, "n_err", "loss")

        # -- gradient chain (reverse order) ---------------------------------
        gd_kw = gd_config or {}
        self.gds: List[Unit] = []
        err_src: Unit = self.evaluator
        err_attr = "err_output"
        for fwd in reversed(self.forwards):
            g = gd_for(type(fwd))(self, **gd_kw)
            g.link_forward(fwd)
            g.link_attrs(err_src, ("err_output", err_attr))
            self.gds.append(g)
            err_src, err_attr = g, "err_input"

        # -- snapshotter (optional; gated on validation improvement) ---------
        self.snapshotter = None
        if snapshot_config is not None:
            from veles_tpu.snapshotter import Snapshotter
            self.snapshotter = Snapshotter(self, **snapshot_config)
            # gating (link_decision) happens in _wire_gates below

        # -- plotters (optional; reference StandardWorkflow wired error
        # curves / confusion / weight tiles from config the same way) ----
        self.plotters: List[Unit] = []
        if plot_config:
            self._build_plotters(plot_config)

        # -- control wiring --------------------------------------------------
        # start → repeater → loader → fwds → evaluator → decision → gds
        #   … last gd → repeater (loop); decision → end_point when complete
        self.repeater.link_from(self.start_point)
        self.loader.link_from(self.repeater)
        prev_u: Unit = self.loader
        for fwd in self.forwards:
            fwd.link_from(prev_u)
            prev_u = fwd
        self.evaluator.link_from(prev_u)
        self.decision.link_from(self.evaluator)
        prev_u = self.decision
        for g in self.gds:
            g.link_from(prev_u)
            prev_u = g
        self.repeater.link_from(prev_u)
        self.end_point.link_from(self.decision)
        if self.snapshotter is not None:
            self.snapshotter.link_from(self.decision)
        self._wire_gates()

    def _build_plotters(self, cfg: Dict[str, Any]) -> None:
        """Wire the reference's standard plot set from a config dict:
        {"error_curve": True, "confusion": True, "weights": True} (any
        subset). Plotters fire once per epoch (gated on the loader's
        epoch boundary) in granular mode; run_fused drives the same
        units at its epoch boundaries, accumulating the validation
        confusion matrix through the step's `confusion()` companion
        (single-host classifier heads; sequence heads and multi-host
        meshes skip it — see FusedTrainStep.confusion)."""
        from veles_tpu.plotting_units import (AccumulatingPlotter,
                                              MatrixPlotter, Weights2D)
        if cfg.get("error_curve"):
            for cls_idx, label in ((1, "validation"), (2, "train")):
                p = AccumulatingPlotter(self, plot_name="epoch_err",
                                        label=label,
                                        name=f"plot_err_{label}")
                p._metric_class = cls_idx
                self.plotters.append(p)
        if cfg.get("confusion") and self.loss == "softmax":
            p = MatrixPlotter(self, name="plot_confusion")
            p.link_attrs(self.evaluator, ("input", "confusion_matrix"))
            # per-epoch VALIDATION confusion (the reference's plot), not
            # an all-splits all-epochs accumulation: restrict the
            # evaluator's accumulation and reset it after each render
            self.evaluator.confusion_split = 1  # VALIDATION
            self.evaluator.link_attrs(self.loader, "minibatch_class")
            self.plotters.append(p)
        if cfg.get("weights") and self.forwards:
            p = Weights2D(self, name="plot_weights")
            p.link_attrs(self.forwards[0], ("input", "weights"))
            self.plotters.append(p)
        # one driver unit fires the whole set at epoch boundaries in the
        # granular pulse graph (run_fused calls _fire_plotters directly)
        driver = Unit(self, name="plot_driver")
        driver.run = self._fire_plotters  # type: ignore[method-assign]
        driver.link_from(self.decision)
        driver.gate_skip = ~self.loader.epoch_ended
        self._plot_driver = driver

    def _fire_plotters(self) -> None:
        """Refresh every plotter from current state (epoch boundary)."""
        from veles_tpu.config import root
        if root.common.get("plotting_disabled", False):
            return      # --no-plot: no specs, and no renderer ever starts
        from veles_tpu.plotting_units import MatrixPlotter
        if not getattr(self, "_plot_series_cleared", False):
            # a NEW workflow plotting under names an earlier run used in
            # this process starts clean (lazy: first fire, so building a
            # workflow that never runs starts no renderer thread)
            for p in self.plotters:
                if hasattr(p, "values"):
                    p.renderer.clear_series(p.plot_name)
            self._plot_series_cleared = True
        for p in self.plotters:
            cls_idx = getattr(p, "_metric_class", None)
            if cls_idx is not None:
                if self.loader.class_lengths[cls_idx] == 0:
                    continue    # no such split: don't plot a fake curve
                m = self.decision.epoch_metrics[cls_idx]
                if m is None:
                    continue
                p.input = float(m)
            if isinstance(p, MatrixPlotter) and p.input is not None \
                    and p.input and not np.any(p.input.mem):
                continue    # never accumulated (fused mode): a zeros
                # heatmap would read as a real (perfect-failure) matrix
            p.run()
        if getattr(self.evaluator, "confusion_split", None) is not None:
            self.evaluator.reset_metrics()   # next epoch starts fresh

    def _wire_gates(self) -> None:
        """(Re)build the derived gate Bools. Called from __init__ AND from
        initialize(): pickle snapshots freeze derived Bools to plain values
        (Bool.__getstate__ drops the closure), so a restored workflow must
        re-derive them or gates stay stuck at their snapshot-time values
        (e.g. gate_skip frozen True → silently no more weight updates)."""
        # re-link GD twins to their forwards: link_forward is idempotent,
        # and units that keep a direct forward reference (GDLSTM._fwd)
        # drop it from pickles and need it re-established after restore
        for g, fwd in zip(self.gds, reversed(self.forwards)):
            g.link_forward(fwd)
        if getattr(self, "_plot_driver", None) is not None:
            # derived Bool: freezes to a plain value in snapshots like
            # every other gate — re-derive or restored runs plot never
            # (frozen True) or per-minibatch (frozen False)
            self._plot_driver.gate_skip = ~self.loader.epoch_ended
        # skip weight updates on test/validation minibatches; freeze the
        # chain entirely once training completed
        for g in self.gds:
            g.gate_skip = self.loader.not_train | self.decision.complete
        self.end_point.gate_block = ~self.decision.complete
        # once complete, the loop-back pulse must die at the repeater
        self.repeater.gate_block = self.decision.complete
        if self.snapshotter is not None:
            self.snapshotter.link_decision(self.decision)

    # -- conveniences --------------------------------------------------------

    def __getstate__(self):
        d = super().__getstate__()
        # device-feed runtime (device arrays in flight, sharded-put
        # closures) and its counters are process-local volatile state:
        # dropping them keeps snapshots loadable AND byte-deterministic
        # for unchanged model state — the property the mirror's
        # digest-keyed idempotent push relies on (resilience/mirror.py)
        d.pop("device_feed", None)
        d.pop("feed_stats", None)
        d.pop("fused_step", None)      # jitted callables + mesh handles
        # ditto the pre-flight prediction (analysis pass 6): it embeds
        # the HOST's device limit, which must not leak into a snapshot
        # another host restores
        d.pop("resource_report", None)
        return d

    def initialize(self, device=None, **kwargs: Any) -> None:
        self._wire_gates()
        super().initialize(device=device, **kwargs)

    def run_epochs(self, n: Optional[int] = None, device=None) -> None:
        """Initialize (if needed) and run until the decision completes."""
        if n is not None:
            self.decision.max_epochs = n
        if not self.is_initialized:
            self.initialize(device=device)
        self.run()

    # -- fused/sharded execution (veles_tpu.parallel) -------------------------

    @_ttracer.in_phase("setup.build_step")
    def build_fused_step(self, mesh=None, mode: str = "auto",
                         compute_dtype=None, ep: bool = False,
                         input_normalize=None, zero_sharding="auto"):
        """Compile the whole forward+backward+update chain into one donated
        XLA step, optionally sharded over `mesh` (data/model axes; ep=True
        additionally shards MoE expert tensors over the data axis).
        `input_normalize` is the uint8-wire prologue spec (see
        `_wire_spec`); `zero_sharding` gates the ZeRO sharded weight
        update ("auto": in dp mode where memory asks for it — CLI
        `--zero-sharding`). See parallel.fused.FusedTrainStep."""
        from veles_tpu.parallel.fused import FusedTrainStep
        return FusedTrainStep(self, mesh=mesh, mode=mode,
                              compute_dtype=compute_dtype, ep=ep,
                              input_normalize=input_normalize,
                              zero_sharding=zero_sharding)

    def autotune(self, mesh=None, compute_dtype=None, **kwargs: Any):
        """Pick the fastest registered lowering for every tunable op this
        workflow contains (LRN, max-pooling, s2d stem, dropout RNG, and
        anything registered since) by timing candidates in-graph, and
        persist the decisions (ops.autotune cache). Selections are left
        in the registry, so the next build_fused_step/run_fused traces
        the winners. Returns the per-op report. CLI: `--autotune`."""
        from veles_tpu.ops.autotune import autotune_workflow
        return autotune_workflow(self, mesh=mesh,
                                 compute_dtype=compute_dtype, **kwargs)

    @_ttracer.in_phase("setup.build_step")
    def build_pipeline_step(self, mesh, n_microbatches: int = 4,
                            boundaries=None, compute_dtype=None,
                            input_normalize=None):
        """Compile the chain as an S-stage GPipe pipeline over `mesh`'s
        "stage" axis (see parallel.pipeline.PipelineTrainStep). The
        workflow must be initialized first (stage shapes come from the
        units' allocated activations)."""
        from veles_tpu.parallel.pipeline import PipelineTrainStep
        return PipelineTrainStep(self, mesh, n_microbatches,
                                 boundaries=boundaries,
                                 compute_dtype=compute_dtype,
                                 input_normalize=input_normalize)

    def _wire_spec(self, uint8_wire="auto"):
        """uint8-over-the-wire negotiation with the loader (the device
        feed, loader/device_feed.py): when the loader offers a raw-bytes
        wire (`wire_format()`) and the graph does not already carry its
        own `input_normalize` layer, return the prologue spec the step
        builder should trace and the emit format the loader should
        switch to — host conversion work and H2D bytes both drop 4x,
        normalization fuses into the first layer's device read.
        `uint8_wire=False` PINS the host-normalized float wire (golden
        comparisons): a loader constructed with `emit="uint8"` is
        switched to float emission for the run — leaving it raw with no
        prologue would silently train on un-normalized 0..255 bytes."""
        from veles_tpu.znicz.normalization import InputNormalize
        if any(isinstance(u, InputNormalize) for u in self.forwards):
            return None     # the graph normalizes on device already
        if not uint8_wire:
            if getattr(self.loader, "emit", None) == "uint8" \
                    and hasattr(self.loader, "set_emit"):
                return {"emit": "float32", "normalize": None}
            return None
        wf = getattr(self.loader, "wire_format", None)
        return wf() if wf is not None else None

    def run_fused(self, epochs: Optional[int] = None, device=None,
                  mesh=None, mode: str = "auto", compute_dtype=None,
                  ep: bool = False,
                  accum_steps: Optional[int] = None,
                  nonfinite_guard: bool = False,
                  uint8_wire="auto",
                  feed_ahead: Optional[int] = None,
                  zero_sharding="auto") -> None:
        """Train with the fused step while keeping the graph semantics:
        the real Loader drives minibatches and the real Decision unit does
        the epoch/stop bookkeeping (so snapshot gating, best-error tracking
        and the `complete` Bool behave exactly as in granular mode).
        Batches reach the device through the async DeviceFeed
        (loader/device_feed.py): host prep AND the H2D transfer overlap
        device compute, and loaders offering a uint8 wire ship raw bytes
        with an on-device normalize prologue (`uint8_wire=False` opts
        out; `feed_ahead` sets the lookahead depth, default 1).

        `accum_steps=K` computes each minibatch's gradient as K scanned
        microbatches before the single update (train_accum) — activation
        memory O(minibatch/K), numerics equal to the plain step (the
        reference's gradient_accumulation slot, SURVEY.md §2.8).

        `nonfinite_guard=True` aborts with NonFiniteLossError the moment
        a class pass's loss goes NaN/inf — checked only at the class-pass
        boundary where the loss is already host-synced, so the guard adds
        no device syncs (resilience layer; the Launcher maps the error to
        a distinct exit code the Supervisor rolls back a snapshot on)."""
        if epochs is not None:
            self.decision.max_epochs = epochs
        if not self.is_initialized:
            self.initialize(device=device)
        wire = self._wire_spec(uint8_wire)
        step = self.build_fused_step(
            mesh=mesh, mode=mode, compute_dtype=compute_dtype, ep=ep,
            input_normalize=wire["normalize"] if wire else None,
            zero_sharding=zero_sharding)
        #: observability handle (like device_feed/fused_state): the step
        #: that trained, for variant_table()/collective_accounting()
        self.fused_step = step
        self._run_with_step(step, accum_steps=accum_steps,
                            nonfinite_guard=nonfinite_guard,
                            wire=wire, feed_ahead=feed_ahead)

    def run_pipelined(self, mesh=None, n_microbatches: int = 4,
                      epochs: Optional[int] = None, device=None,
                      boundaries=None, compute_dtype=None,
                      nonfinite_guard: bool = False,
                      uint8_wire="auto",
                      feed_ahead: Optional[int] = None) -> None:
        """Train as a GPipe pipeline over `mesh`'s "stage" axis (default:
        one stage per device) with the same Loader/Decision/Snapshotter
        semantics (and the same DeviceFeed input path) as run_fused. The
        CLI exposes this as `--pp M` (M = microbatches)."""
        if epochs is not None:
            self.decision.max_epochs = epochs
        if not self.is_initialized:
            self.initialize(device=device)
        if mesh is None:
            import jax

            from veles_tpu.parallel.pipeline import make_stage_mesh
            # one stage per device, capped at one UNIT per stage
            mesh = make_stage_mesh(
                jax.devices()[:max(1, len(self.forwards))])
        wire = self._wire_spec(uint8_wire)
        step = self.build_pipeline_step(
            mesh, n_microbatches, boundaries=boundaries,
            compute_dtype=compute_dtype,
            input_normalize=wire["normalize"] if wire else None)
        self._run_with_step(step, nonfinite_guard=nonfinite_guard,
                            wire=wire, feed_ahead=feed_ahead)

    def _run_with_step(self, step, accum_steps: Optional[int] = None,
                       nonfinite_guard: bool = False,
                       wire=None, feed_ahead: Optional[int] = None) -> None:
        """Drive any train/evaluate/write_back step object through the
        Loader + Decision bookkeeping (shared by run_fused /
        run_pipelined). Batches arrive through the async DeviceFeed —
        while step k executes, batch k+1's sharded device_put is already
        in flight (feed.prefetch() at the loop bottom, AFTER the
        snapshot window so pickled loader cursors stay exact-resume
        correct) — and each FeedBatch's Decision metadata is replayed
        onto the loader, so the epoch bookkeeping below is unchanged
        from the synchronous loop it replaces."""
        # static resource pre-flight (analysis pass 6, docs/ANALYSIS.md
        # — ISSUE 14): predict the per-device HBM footprint BEFORE the
        # first compile. The cheap resident model always runs (it rides
        # the heartbeat, so the supervisor reports predicted-vs-
        # measured); the traced high-water walk + limit comparison run
        # only when a device limit is known (TPU) — warn above 80%,
        # refuse above it with a per-component byte breakdown instead
        # of OOMing minutes into the compile.
        from veles_tpu.analysis import resources as _resources
        try:
            self.resource_report = _resources.preflight(
                self, step, feed_ahead=feed_ahead)
        except _resources.ResourcePreflightError:
            raise
        except Exception as e:  # noqa: BLE001 — an estimate must never
            # kill a run the measurement machinery exists to observe
            self.debug("resource pre-flight unavailable: %s", e)
            self.resource_report = None
        if accum_steps and accum_steps > 1:
            import types
            base = step
            step = types.SimpleNamespace(
                train=lambda s, x, y, w=None: base.train_accum(
                    s, x, y, accum_steps, w),
                evaluate=base.evaluate, init_state=base.init_state,
                write_back=base.write_back,
                # keep the full step surface: the confusion companion,
                # local_rows, sharding specs and mesh drive features
                # below this wrapper
                confusion=getattr(base, "confusion", None),
                local_rows=getattr(base, "local_rows", None),
                input_put_specs=getattr(base, "input_put_specs", None),
                collective_accounting=getattr(
                    base, "collective_accounting", None),
                mesh=getattr(base, "mesh", None))
        import time as _time

        from veles_tpu.config import root as _root
        from veles_tpu.loader.base import TRAIN
        from veles_tpu.loader.device_feed import DeviceFeed
        from veles_tpu.resilience.faults import active_plan
        from veles_tpu.telemetry import metrics as _tmetrics
        fault_plan = active_plan()   # None in production: zero per-step cost
        # telemetry plane (docs/OBSERVABILITY.md): the metric instruments
        # are PRE-BOUND here, outside the loop — the hot path pays float
        # adds, never a name lookup (the velint hot-metric contract).
        # Spans go through _ttracer.span (the --trace ring AND any open
        # profiler session; a shared no-op when neither is on). tr is
        # the ring alone, for the one span no `with` can hold: the
        # in-flight "step" window. The profile controller's disarmed
        # on_step is one attribute check.
        span = _ttracer.span
        tr = _ttracer.active()
        prof = _ttracer.profile_controller()
        mh = _tmetrics.step_handles()
        # per-collective byte attribution (ISSUE 12): the ZeRO
        # grad_reduce exchange's modeled egress, pre-bound like every
        # other hot-path instrument; None when the step traces no
        # registry collective — the counters can't fabricate provenance
        _acct_fn = getattr(step, "collective_accounting", None)
        ch = _tmetrics.collective_handles(
            _acct_fn() if _acct_fn is not None else None)
        state = step.init_state()
        loader, ev, dec = self.loader, self.evaluator, self.decision
        # the feed uploads (sharded, async) itself; the loader's granular-
        # path device push would be a second, wasted H2D per minibatch
        prev_on_device, loader.on_device = loader.on_device, False
        # uint8 wire negotiated (run_fused/_wire_spec): raw bytes leave
        # the host, the step's input_normalize prologue converts on
        # device — restore the loader's emit format afterwards
        prev_emit = getattr(loader, "emit", None)
        if wire is not None and hasattr(loader, "set_emit"):
            loader.set_emit(wire["emit"])
            # mid-run snapshots pickle the CONSTRUCTED emit, not the
            # run-scoped negotiated one (Loader.__getstate__)
            loader._emit_pristine = prev_emit
        # multi-host input sharding: tell a prefetching loader which
        # global batch rows this process's shards own, so host decode
        # divides by the host count (non-local rows zero-fill; the jit
        # never transfers or reads them)
        prev_rows_fn = getattr(loader, "local_rows_fn", None)
        mesh = getattr(step, "mesh", None)
        if (hasattr(loader, "local_rows_fn")
                and hasattr(step, "local_rows") and mesh is not None):
            from veles_tpu.parallel.mesh import is_multihost
            if is_multihost(mesh):
                loader.local_rows_fn = step.local_rows
        ahead = 1 if feed_ahead is None else feed_ahead
        if self.snapshotter is not None and ahead > 1:
            # a snapshot taken with k pending batches pickles a loader
            # cursor k past the trained batch — the restore would skip
            # them, forking the resumed trajectory. Exact resume beats
            # deeper lookahead; loops that never pickle the loader
            # (bench) may run deeper.
            self.warning("feed_ahead=%d clamped to 1: snapshots require "
                         "an exact-resume loader cursor "
                         "(loader/device_feed.py)", ahead)
            ahead = 1
        feed = DeviceFeed.for_step(loader, step, ahead=ahead)
        #: observability handle: heartbeats/reports read feed_stats
        self.device_feed = feed
        try:
            # Metrics accumulate ON DEVICE across each class pass (lazy
            # scalar adds); the single host sync happens at last_minibatch,
            # so device execution pipelines across minibatches (the
            # evaluator docstring's fused-mode contract).
            acc_loss = acc_err = acc_conf = None
            acc_w = 0.0
            step_idx = 0
            #: the open in-flight "step" span: dispatch k .. dispatch
            #: k+1 (or the class-pass-boundary device sync, whichever
            #: first) — the host-visible window the device is executing
            #: step k in, which batch k+1's feed.device_put span rides
            #: under when the overlap works
            step_tok = None
            t_iter = _time.perf_counter()
            ep_examples = 0.0
            t_epoch = t_iter
            while not bool(dec.complete):
                prof.on_step(step_idx)
                with span("feed.next", "feed"):
                    b = feed.next()
                x, y, w = b.x, b.y, b.w
                if tr is not None and step_tok is not None:
                    tr.end(step_tok)     # step k-1's window closes at
                    step_tok = None      # the next dispatch
                if b.minibatch_class == TRAIN:
                    # the step records its own train.dispatch span
                    state, (loss, n_err) = step.train(state, x, y, w)
                    if ch is not None:
                        # the exchange rides inside the step just
                        # dispatched; count its modeled bytes now (when
                        # it ran on the device, only a profiler capture
                        # says: the collective ops under the step's
                        # grad_exchange / param_gather scopes)
                        ch.dcn.inc(ch.dcn_bytes)
                        ch.ici.inc(ch.ici_bytes)
                        ch.ag_dcn.inc(ch.ag_dcn_bytes)
                        ch.ag_ici.inc(ch.ag_ici_bytes)
                    if fault_plan is not None and fault_plan.nan_at_step():
                        loss = float("nan")   # deterministic divergence
                else:
                    with span("eval.dispatch", "step"):
                        loss, n_err = step.evaluate(state, x, y, w)
                    # fused-mode confusion accumulation (the granular
                    # graph's evaluator fills it per minibatch; without
                    # this the confusion plot would silently skip).
                    # Accumulated as LAZY DEVICE adds like loss/err; the
                    # host sync stays at the class-pass boundary.
                    cs = getattr(ev, "confusion_split", None)
                    if (cs is not None and b.minibatch_class == cs
                            and getattr(self, "plotters", None)
                            and getattr(ev, "compute_confusion", True)
                            and not _root.common.get("plotting_disabled",
                                                     False)
                            and getattr(step, "confusion", None)
                            is not None):
                        m = step.confusion(state, x, y, ev.n_classes, w)
                        if m is not None:
                            acc_conf = (m if acc_conf is None
                                        else acc_conf + m)
                if tr is not None:
                    step_tok = tr.begin("step", "step")
                # step losses are weighted MEANS over the minibatch; scale
                # by the batch's valid-row weight so the class-pass total
                # is the EXACT weighted mean (a wrapped final minibatch
                # with few valid rows must not count as a full one)
                bw = float(b.w_host.sum())
                wl = loss * bw
                acc_loss = wl if acc_loss is None else acc_loss + wl
                acc_w += bw
                acc_err = n_err if acc_err is None else acc_err + n_err
                step_idx += 1
                mh.steps.inc()
                if b.minibatch_class == TRAIN:
                    mh.examples.inc(bw)
                    ep_examples += bw
                now = _time.perf_counter()
                mh.step_seconds.observe(now - t_iter)
                t_iter = now
                if b.last_minibatch:
                    # Decision's improvement/stop logic only reads totals
                    # at the class-pass boundary; feeding the accumulated
                    # value here (zeros in between) preserves its
                    # semantics. This float() is THE driver-side device
                    # sync — timed so the feed's stats decompose blocked
                    # time into loader vs device.
                    t_sync = _time.perf_counter()
                    ev.loss = float(acc_loss) / max(acc_w, 1.0)
                    if tr is not None and step_tok is not None:
                        tr.end(step_tok)   # the float() drained the
                        step_tok = None    # device: the window is over
                    mh.loss.set(ev.loss)
                    if nonfinite_guard and not np.isfinite(ev.loss):
                        # raised BEFORE dec.run()/the snapshot branch: a
                        # poisoned state must never be snapshotted. The
                        # check rides the boundary's existing host sync,
                        # so the guard costs no extra device round-trips.
                        from veles_tpu.resilience import NonFiniteLossError
                        raise NonFiniteLossError(
                            f"non-finite loss {ev.loss!r} at epoch "
                            f"{dec.epoch_number} (class "
                            f"{int(b.minibatch_class)} pass)")
                    ev.n_err = (int(acc_err) if self.loss == "softmax"
                                else float(acc_err))
                    if acc_conf is not None:
                        ev.confusion_matrix.map_write()
                        # class-pass-boundary sync by design: confusion
                        # accumulated as lazy device adds above, pulled
                        # host-side ONCE per pass, not per batch
                        # velint: disable=sync-feed
                        ev.confusion_matrix.mem += np.asarray(
                            acc_conf).astype(ev.confusion_matrix.mem.dtype)
                    t_done = _time.perf_counter()
                    feed.note_device_sync(t_done - t_sync)
                    if tr is not None:
                        tr.add_span("device_sync", "step", t_sync,
                                    t_done)
                    acc_loss = acc_err = acc_conf = None
                    acc_w = 0.0
                else:
                    ev.loss = 0.0
                    ev.n_err = 0
                if b.epoch_ended:
                    # BEFORE dec.run(): the Decision's epoch hooks write
                    # the heartbeat, which carries these counters to the
                    # supervisor's exit report
                    self.feed_stats = feed.stats()
                    # the feed writes its own counters to the one
                    # registry; the epoch-boundary rates go in here, and
                    # a JSONL sink (if installed) gets one line per
                    # epoch for offline analysis
                    t_ep = _time.perf_counter()
                    if ep_examples and t_ep > t_epoch:
                        mh.examples_per_s.set(
                            ep_examples / (t_ep - t_epoch))
                    ep_examples, t_epoch = 0.0, t_ep
                with span("decision", "bookkeeping"):
                    dec.run()
                if b.epoch_ended:
                    mh.epoch.set(dec.epoch_number)
                    _tmetrics.flush_installed(
                        extra={"source": "driver",
                               "epoch": int(dec.epoch_number)})
                if getattr(self, "plotters", None) \
                        and b.epoch_ended \
                        and not _root.common.get("plotting_disabled",
                                                 False):
                    # weight plots need the CURRENT fused params in the
                    # unit Arrays, not the init-time values
                    from veles_tpu.plotting_units import Weights2D
                    if any(isinstance(p, Weights2D)
                           for p in self.plotters):
                        step.write_back(state)
                    self._fire_plotters()   # same per-epoch plot set as
                    # the granular graph's plot_driver
                # fused mode bypasses the pulse graph, so the snapshot
                # gating is applied here by hand: same improved-gated
                # behavior as granular mode (run_fused's contract)
                if self.snapshotter is not None and bool(dec.improved):
                    with span("snapshot", "bookkeeping"):
                        step.write_back(state)
                        self.snapshotter.run()
                # NOW produce batch k+1 and issue its async put: the
                # step dispatched above is still executing on device,
                # so the H2D transfer hides under it — and the snapshot
                # (if any) already pickled the pristine loader cursor
                if not bool(dec.complete):
                    with span("feed.prefetch", "feed"):
                        feed.prefetch()
        finally:
            if tr is not None and step_tok is not None:
                tr.end(step_tok)
            prof.finalize()
            feed.stop()
            self.feed_stats = feed.stats()
            loader.on_device = prev_on_device
            if wire is not None and hasattr(loader, "set_emit") \
                    and prev_emit is not None:
                loader.set_emit(prev_emit)
                loader._emit_pristine = None
            if hasattr(loader, "local_rows_fn"):
                loader.local_rows_fn = prev_rows_fn
            step.write_back(state)
            self.fused_state = state
            self._stop_units()   # release loader prefetch threads etc.
