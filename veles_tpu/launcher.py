"""Launcher: orchestration of a workflow run.

Parity: reference `veles/launcher.py` (SURVEY.md §2.9) — mode selection
(standalone / master / slave), workflow registration, lifecycle (initialize,
run, shutdown, exit codes), auxiliary services (web status, graphics).

TPU-first mapping of the reference's roles:
- standalone  -> single-process run on the local device(s);
- master (-l) -> distributed COORDINATOR (`jax.distributed.initialize`
  process 0) — the reference's Twisted job server has no analog because
  gradient averaging is an in-graph ICI all-reduce, not a host protocol;
- slave (-m)  -> distributed WORKER process joining the coordinator.
All processes run the same SPMD program; there is no per-unit job/update
pickling (reference §3.2) to orchestrate.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Callable, Optional

from veles_tpu.analysis.resources import ResourcePreflightError
from veles_tpu.config import root
from veles_tpu.logger import Logger
from veles_tpu.resilience import EXIT_NONFINITE, NonFiniteLossError
from veles_tpu.snapshotter import Snapshotter


class Launcher(Logger):
    """Drives one workflow: load (or restore), initialize, run, report."""

    def __init__(self, snapshot: str = "",
                 listen: str = "", master: str = "",
                 process_id: int = 0, n_processes: int = 1,
                 device: Any = None, stats: bool = True,
                 web_status: bool = False, web_port: int = 8090,
                 profile_dir: str = "", debug_nans: bool = False,
                 fused: bool = False, autotune: bool = False,
                 autotune_budget: Optional[int] = None,
                 manhole: Optional[int] = None,
                 pp: Optional[int] = None, serve: Optional[int] = None,
                 serve_ring: Optional[int] = None,
                 serve_dispatch: Optional[str] = None,
                 serve_quantize: Optional[str] = None,
                 serve_mesh: Optional[str] = None,
                 serve_batch: Optional[int] = None,
                 serve_watch_mirror: Optional[str] = None,
                 serve_replicas: Optional[int] = None,
                 serve_announce: Optional[str] = None,
                 accum: Optional[int] = None, report: str = "",
                 tp: Optional[int] = None, sp: Optional[int] = None,
                 ep: bool = False,
                 nonfinite_guard: bool = False,
                 verify_workflow: str = "",
                 mirror: str = "",
                 feed_ahead: Optional[int] = None,
                 zero_sharding: str = "auto",
                 trace: str = "",
                 profile_window: str = "",
                 **kwargs: Any) -> None:
        super().__init__()
        self.snapshot_path = snapshot
        #: when set, the run is wrapped in jax.profiler.trace (TensorBoard/
        #: Perfetto), on top of the per-unit wall-time table — SURVEY.md
        #: §5.1's "strictly better than the reference" tracing story
        self.profile_dir = profile_dir
        self.debug_nans = debug_nans
        #: run via the one-dispatch-per-minibatch fused XLA step instead
        #: of the granular unit graph (same Decision/Snapshotter behavior)
        self.fused = fused
        #: time every registered lowering variant of the workflow's
        #: tunable ops before training and train with the winners
        #: (ops.autotune; decisions persist in the on-disk cache)
        if autotune and serve is not None:
            raise SystemExit("--autotune tunes a training step; it "
                             "conflicts with --serve")
        if autotune and (listen or master):
            # per-process timing noise could elect DIFFERENT winners on
            # different processes -> diverged SPMD programs -> deadlock.
            raise SystemExit(
                "--autotune is single-process: tune standalone first "
                "(tools/autotune.py), then run distributed with "
                "VELES_AUTOTUNE_CACHE pointing every process at the "
                "SAME cache file to inherit the decisions")
        if autotune and not (fused or pp):
            # the granular per-unit graph (xla_init paths) does not
            # consult the variants registry: tuning would burn minutes
            # and then be ignored by the run
            raise SystemExit("--autotune tunes the fused-step lowerings: "
                             "combine with --fused or --pp")
        if autotune_budget is not None and not autotune:
            # the --feed-ahead/--zero-sharding precedent: a budget that
            # nothing consumes is a silent no-op — reject it
            raise SystemExit("--autotune-budget bounds the generated-"
                             "candidate search of --autotune: combine "
                             "with --autotune")
        if autotune_budget is not None and autotune_budget < 1:
            raise SystemExit("--autotune-budget must be >= 1")
        self.autotune = autotune
        #: trial budget for the generated-candidate search (ops.templates
        #: spaces); None = flat enumeration of hand-written variants only
        self.autotune_budget = autotune_budget
        #: serve-only mode: skip training, expose the (typically
        #: snapshot-restored) model over HTTP on this port (0 = auto)
        if serve is not None and (pp or fused or listen or master):
            raise SystemExit(
                "--serve is a serve-only mode: it conflicts with "
                "--pp/--fused and distributed -l/-m")
        self.serve_port = serve
        #: serving-tier knobs (ISSUE 15): ring geometry, dispatch core,
        #: quantized wire, mesh request, per-request row cap — rejected
        #: without --serve (the --feed-ahead precedent: a knob nothing
        #: consumes must fail loud, not be silently inert)
        if serve is None and any(
                v is not None for v in (serve_ring, serve_dispatch,
                                        serve_quantize, serve_mesh,
                                        serve_batch,
                                        serve_watch_mirror,
                                        serve_replicas,
                                        serve_announce)):
            raise SystemExit(
                "--serve-ring/--serve-dispatch/--serve-quantize/"
                "--serve-mesh/--serve-batch/--serve-watch-mirror/"
                "--serve-replicas/--serve-announce "
                "configure the serving tier: combine with --serve")
        if serve_ring is not None and serve_ring < 1:
            raise SystemExit(f"--serve-ring needs N >= 1 "
                             f"(got {serve_ring})")
        if serve_replicas is not None and serve_replicas < 1:
            raise SystemExit(f"--serve-replicas needs N >= 1 "
                             f"(got {serve_replicas})")
        if serve_batch is not None and serve_batch < 1:
            raise SystemExit(f"--serve-batch needs N >= 1 "
                             f"(got {serve_batch})")
        if serve_ring is not None \
                and serve_ring < (serve_batch or 64):
            # fail at flag-parse time with the flag names, not a
            # traceback from deep inside the server build (the ring
            # must hold a whole max_batch request; 64 = the server's
            # max_batch default)
            raise SystemExit(
                f"--serve-ring ({serve_ring}) must hold a whole "
                f"--serve-batch request ({serve_batch or 64}): raise "
                f"--serve-ring or lower --serve-batch")
        if (serve_dispatch or "ring") == "merge":
            # every ring-only capability knob fails at flag-parse time
            # with the flag names, not a traceback after the workflow
            # initialize (the --serve-ring precedent below)
            if serve_ring is not None:
                raise SystemExit("--serve-ring sizes the ring core: it "
                                 "conflicts with --serve-dispatch merge")
            if serve_watch_mirror is not None:
                raise SystemExit(
                    "--serve-watch-mirror hot-swaps into the ring core "
                    "(the merge baseline binds params at build time): "
                    "drop --serve-dispatch merge")
            if serve_quantize not in (None, "f32"):
                raise SystemExit(
                    "--serve-quantize rides the ring core (the merge "
                    "baseline serves f32): drop --serve-dispatch merge "
                    "or --serve-quantize")
            if serve_mesh == "on":
                raise SystemExit(
                    "--serve-mesh on requires the ring core (the merge "
                    "baseline serves unsharded): drop --serve-dispatch "
                    "merge or use --serve-mesh off")
        self.serve_ring = serve_ring
        self.serve_dispatch = serve_dispatch or "ring"
        self.serve_quantize = serve_quantize or "f32"
        self.serve_mesh = serve_mesh or "auto"
        self.serve_batch = serve_batch
        #: mirror spec (dir or http(s) URL) the serving tier polls for
        #: new digest-addressed snapshots to hot-swap (ISSUE 16)
        self.serve_watch_mirror = serve_watch_mirror
        #: fleet knobs (ISSUE 19): N independent slot rings in this
        #: process (replica != process — each with its own port,
        #: ledger, watcher and metric labels, sharing ONE AOT cache so
        #: replica 2..N start with zero compiles), and the mirror bus
        #: the replicas announce themselves on for router discovery
        self.serve_replicas = serve_replicas or 1
        self.serve_announce = serve_announce
        #: GPipe pipeline mode: microbatch count (stages = local devices)
        if pp is not None and pp < 1:
            raise SystemExit(f"--pp needs a microbatch count >= 1 "
                             f"(got {pp})")
        if pp and fused:
            raise SystemExit("--pp and --fused are mutually exclusive "
                             "execution modes")
        self.pp = pp
        #: gradient accumulation microbatch count for fused/distributed
        #: training (run_fused accum_steps; SURVEY.md §2.8 slot)
        if accum is not None and accum < 1:
            raise SystemExit(f"--accum needs K >= 1 (got {accum})")
        if accum and accum > 1 and not (fused or listen or master):
            raise SystemExit("--accum applies to the fused step: combine "
                             "with --fused or a distributed -l/-m run")
        if accum and accum > 1 and pp:
            raise SystemExit("--accum applies to the fused step, not the "
                             "GPipe pipeline (--pp already microbatches)")
        self.accum = accum
        #: tensor-parallel degree for distributed runs: the global mesh
        #: becomes (data = n_devices/K, model = K) and the fused step
        #: runs in gspmd mode (megatron col/row plan) — a v5e-pod-style
        #: dp x tp hybrid where TP collectives ride the fast links
        if tp is not None and tp < 1:
            raise SystemExit(f"--tp needs K >= 1 (got {tp})")
        if tp and tp > 1 and not (listen or master):
            raise SystemExit("--tp shards over the distributed global "
                             "mesh: combine with -l/-m (single-process "
                             "TP uses build_fused_step(mesh=...) directly)")
        self.tp = tp
        #: sequence-parallel degree (ring attention over the mesh "seq"
        #: axis) for distributed runs — the long-context axis, spanning
        #: hosts the same way --tp does
        if sp is not None and sp < 1:
            raise SystemExit(f"--sp needs K >= 1 (got {sp})")
        if sp and sp > 1 and not (listen or master):
            raise SystemExit("--sp shards over the distributed global "
                             "mesh: combine with -l/-m")
        self.sp = sp
        #: expert parallelism for distributed runs: MoE expert tensors
        #: sharded over the data axis, all_to_all token exchange (dp
        #: mode only — the fused step composes it with the data mesh)
        if ep and (tp and tp > 1 or sp and sp > 1):
            raise SystemExit("--ep composes with the data axis; it is "
                             "exclusive with --tp/--sp in this launcher")
        if pp and (ep or (tp and tp > 1) or (sp and sp > 1)):
            raise SystemExit("--pp is its own partitioning (one stage "
                             "per mesh device); it is exclusive with "
                             "--tp/--sp/--ep")
        if ep and not (listen or master):
            raise SystemExit("--ep shards experts over the distributed "
                             "global mesh: combine with -l/-m "
                             "(single-process EP uses "
                             "build_fused_step(ep=True) directly)")
        self.ep = bool(ep)
        #: abort training with a distinct exit code the moment a class
        #: pass's loss goes non-finite — fused/pipelined AND granular
        #: modes (resilience layer: the Supervisor rolls back one
        #: snapshot before retrying)
        self.nonfinite_guard = nonfinite_guard
        #: static-analysis-only mode ("", "graph" or "audit"): verify
        #: the constructed workflow graph — "audit" ALSO runs the jaxpr
        #: auditor over the initialized workflow's fused step — print
        #: findings, exit nonzero on errors, never train
        if verify_workflow is True:     # pre-PR-4 boolean callers
            verify_workflow = "graph"
        self.verify_workflow = verify_workflow or ""
        #: snapshot durability mirror spec (resilience/mirror.py):
        #: wired onto the workflow's Snapshotter before the run so
        #: every snapshot write pushes a verified durable copy
        self.mirror = mirror
        #: device-feed lookahead depth for fused/pipelined runs
        #: (loader/device_feed.py): None = the feed's default (1, the
        #: classic double buffer); 0 disables lookahead. CLI --feed-ahead
        if feed_ahead is not None and feed_ahead < 0:
            raise SystemExit(f"--feed-ahead needs N >= 0 (got "
                             f"{feed_ahead})")
        if feed_ahead is not None and not (fused or pp
                                           or listen or master):
            # same precedent as --autotune: the granular unit graph
            # never consumes the feed, and silently ignoring the knob
            # would let an operator believe lookahead is active
            raise SystemExit("--feed-ahead tunes the device feed of the "
                             "fused/pipelined loops: combine with "
                             "--fused, --pp or a distributed -l/-m run")
        self.feed_ahead = feed_ahead
        #: ZeRO weight-update sharding gate for the fused dp step
        #: (parallel/fused.py, arxiv 2004.13336): "auto" (default) turns
        #: it on wherever the dp shard_map update runs single-host,
        #: "on" warns loudly when the step cannot apply it, "off" pins
        #: the replicated update. GPipe is not covered by this build —
        #: degrade with a logged reason instead of silently ignoring.
        if zero_sharding not in ("on", "off", "auto"):
            raise SystemExit(f"--zero-sharding takes on/off/auto "
                             f"(got {zero_sharding!r})")
        if zero_sharding == "on" and pp:
            self.warning("zero-sharding degrades for --pp: the GPipe "
                         "pipeline step partitions by stage, not by "
                         "data replica — the replicated update stays "
                         "(ZeRO covers the fused dp path this build)")
        if zero_sharding != "auto" and not (fused or pp
                                            or listen or master):
            # same precedent as --feed-ahead/--autotune: the granular
            # unit graph never consumes the knob, and silently ignoring
            # an explicit on/off would let an operator believe the
            # optimizer state is (or isn't) sharded
            raise SystemExit("--zero-sharding gates the fused dp "
                             "update: combine with --fused, --pp or a "
                             "distributed -l/-m run")
        self.zero_sharding = zero_sharding
        #: step-timeline tracing (telemetry/tracer.py): record driver
        #: spans into the ring buffer and export a Perfetto-loadable
        #: trace.json here at the end of the run. Only the fused/
        #: pipelined driver loop (and the serving dispatch path) emit
        #: spans — same validation precedent as --feed-ahead: silently
        #: ignoring the flag would let an operator believe a trace is
        #: being captured.
        if trace and not (fused or pp or listen or master
                          or serve is not None):
            raise SystemExit(
                "--trace records the fused/pipelined driver loop (or "
                "the serving dispatch path): combine with --fused, "
                "--pp, a distributed -l/-m run or --serve")
        self.trace_path = trace
        #: --profile-window N:M — bracket driver steps N..M with
        #: jax.profiler start/stop (the on-chip capture path); only the
        #: stepped training drivers consume it
        if profile_window:
            from veles_tpu.telemetry.tracer import ProfileController
            try:
                ProfileController.parse_spec(profile_window)
            except ValueError as e:
                raise SystemExit(f"--profile-window: {e}")
            if not (fused or pp or listen or master):
                raise SystemExit(
                    "--profile-window brackets training steps of the "
                    "fused/pipelined drivers: combine with --fused, "
                    "--pp or a distributed -l/-m run")
        self.profile_window = profile_window
        self.listen = listen            # coordinator address to bind
        self.master = master            # coordinator address to join
        self.process_id = process_id
        self.n_processes = n_processes
        self.device = device
        self.show_stats = stats
        self.web_status_enabled = web_status
        self.web_port = web_port
        #: None = disabled; int = port to listen on (0 auto-picks).
        #: External live-attach REPL (reference manhole, SURVEY.md §2.5)
        self.manhole_port = manhole
        #: end-of-run publishing: "x.html" writes the self-contained HTML
        #: report (+ x.json machine summary); "x.json" the summary only
        self.report_path = report
        self.workflow = None
        self.snapshot_loaded = False
        self._web = None
        self._manhole = None

    # -- distributed bootstrap ----------------------------------------------

    @property
    def mode(self) -> str:
        if self.listen:
            return "coordinator"
        if self.master:
            return "worker"
        return "standalone"

    def boot_distributed(self) -> None:
        """Multi-host init over DCN (reference master/slave -> JAX
        coordinator/worker; see parallel.distributed)."""
        if self.mode == "standalone":
            return
        from veles_tpu.parallel.distributed import initialize_distributed
        from veles_tpu.telemetry import tracer as _ttracer
        addr = self.listen or self.master
        with _ttracer.phase("setup.backend"):   # the wait for the job
            initialize_distributed(coordinator=addr,
                                   process_id=self.process_id,
                                   n_processes=self.n_processes)

    # -- the reference's run(load, main) module convention --------------------

    def load(self, workflow_factory: Callable, **kwargs: Any):
        """Build the workflow, or restore it from `--snapshot`.
        Returns (workflow, snapshot_was_loaded)."""
        if self.snapshot_path:
            # restoring unpickles device Arrays, which can initialize the
            # XLA backend — in distributed mode that must happen AFTER
            # jax.distributed.initialize (idempotent; main() re-calls it)
            self.boot_distributed()
            self.info("restoring snapshot %s", self.snapshot_path)
            self.workflow = Snapshotter.import_(self.snapshot_path)
            self.snapshot_loaded = True
        else:
            self.workflow = workflow_factory(**kwargs)
            self.snapshot_loaded = False
        return self.workflow, self.snapshot_loaded

    def _run_verify(self) -> int:
        """--verify-workflow: run the static graph verifier plus the
        config-level environment findings over the CONSTRUCTED (not
        initialized) workflow, print every finding, and exit nonzero on
        errors — no training. The default "graph" mode never
        initializes and never touches a device; "audit" additionally
        initializes the workflow (host-side) and runs the jaxpr auditor
        over its fused step — `make_jaxpr` only traces, it never
        compiles, so the promise "exit without training" still holds."""
        from veles_tpu.analysis.graph import verify_workflow
        from veles_tpu.analysis.trace import environment_findings
        findings = list(verify_workflow(self.workflow))
        findings += environment_findings(
            pp=self.pp, tp=self.tp, sp=self.sp,
            nonfinite_guard=(self.nonfinite_guard or self.debug_nans))
        if self.verify_workflow == "audit":
            if not hasattr(self.workflow, "build_fused_step"):
                print(f"verify-workflow: audit skipped — "
                      f"{type(self.workflow).__name__} has no fused "
                      f"step (StandardWorkflow-family only)",
                      flush=True)
            else:
                from veles_tpu.analysis.trace import audit_workflow
                # nonfinite_guard=None: environment_findings above
                # already emitted the guard-off warning once
                audit_finds = audit_workflow(self.workflow,
                                             nonfinite_guard=None)
                print(f"verify-workflow: audit traced the fused step "
                      f"({len(audit_finds)} finding(s))", flush=True)
                findings += audit_finds
        elif self.verify_workflow == "resources":
            # pass 6 (analysis/resources.py): both static memory
            # ledgers — the kernel VMEM verdicts for the current
            # registry selections and the per-device workflow HBM
            # model (params + grads + ZeRO optimizer vectors + ef +
            # liveness-walk activations + feed buffers) vs the device
            # limit. Traces, never compiles — "exit without training"
            # still holds.
            if not hasattr(self.workflow, "build_fused_step"):
                print(f"verify-workflow: resources skipped — "
                      f"{type(self.workflow).__name__} has no fused "
                      f"step (StandardWorkflow-family only)",
                      flush=True)
            else:
                from veles_tpu.analysis.resources import \
                    workflow_resource_findings
                res_finds, rep = workflow_resource_findings(
                    self.workflow)
                comps = ", ".join(
                    f"{k}={v}" for k, v in
                    sorted(rep.get("components", {}).items()))
                print(f"verify-workflow: resources predicted "
                      f"{rep.get('highwater_per_device', 0)} B/device "
                      f"high-water, {rep.get('resident_per_device', 0)}"
                      f" B resident (limit "
                      f"{rep.get('limit_per_device') or 'unknown'}; "
                      f"{comps})", flush=True)
                print(f"verify-workflow: resources section "
                      f"({len(res_finds)} finding(s))", flush=True)
                findings += res_finds
        elif self.verify_workflow == "modelcheck":
            # pass 8 (analysis/modelcheck.py): a small fixed-budget
            # bounded-interleaving sweep of the real election /
            # membership / hot-swap protocol logic under a simulated
            # world. Deterministic and jax-free (seconds); the full
            # exhaustiveness budget lives in tools/modelcheck.py --ci.
            from veles_tpu.analysis.modelcheck import quick_check
            mc_finds, mc_stats = quick_check()
            print(f"verify-workflow: modelcheck explored "
                  f"{mc_stats['schedules']} schedule(s) across "
                  f"{len(mc_stats['scenarios'])} scenario(s) "
                  f"({len(mc_finds)} finding(s))", flush=True)
            findings += mc_finds
        # concurrency section: the whole-program thread/endpoint
        # contracts (analysis passes 4/5) over the installed package —
        # the same findings tools/velint.py --ci ratchets on, surfaced
        # here so one --verify-workflow run answers "is this tree
        # statically sound" end to end (graph + environment + races +
        # protocol). Converted to the shared Finding record; errors
        # count toward the exit code like every other pass.
        import veles_tpu as _pkg
        from veles_tpu.analysis import concurrency as _conc
        from veles_tpu.analysis import protocol as _proto
        from veles_tpu.analysis.findings import Finding as _Finding
        pkg_dir = os.path.dirname(os.path.abspath(_pkg.__file__))
        conc = _conc.analyze_paths([pkg_dir],
                                   root=os.path.dirname(pkg_dir))
        conc += _proto.analyze_paths([pkg_dir],
                                     root=os.path.dirname(pkg_dir))
        print(f"verify-workflow: concurrency pass over the installed "
              f"package ({len(conc)} finding(s))", flush=True)
        findings += [_Finding(rule=f.rule, severity=f.severity,
                              unit=f"{f.path}:{f.line}",
                              message=f.message)
                     for f in conc]
        for f in findings:
            print(f.format(), flush=True)
        n_err = sum(1 for f in findings if f.severity == "error")
        print(f"verify-workflow: {n_err} error(s), "
              f"{len(findings) - n_err} warning(s)", flush=True)
        return 1 if n_err else 0

    def main(self, **kwargs: Any) -> int:
        """Initialize + run the loaded workflow; returns an exit code."""
        if self.workflow is None:
            raise RuntimeError("Launcher.main() before load()")
        if self.verify_workflow:
            return self._run_verify()
        # telemetry plane (docs/OBSERVABILITY.md): install the tracer
        # BEFORE any step/server construction so every pre-bound
        # tracer handle captures it; the metrics JSONL sink rides the
        # trace flag (trace.json.metrics.jsonl) or VELES_METRICS_JSONL
        from veles_tpu.telemetry import metrics as _tmetrics
        from veles_tpu.telemetry import tracer as _ttracer
        tracer_obj = None
        if self.trace_path:
            tracer_obj = _ttracer.install()
        jsonl_path = (os.environ.get("VELES_METRICS_JSONL")
                      or (self.trace_path + ".metrics.jsonl"
                          if self.trace_path else ""))
        if jsonl_path:
            _tmetrics.install_jsonl(jsonl_path)
        if self.profile_window:
            ctl = _ttracer.profile_controller()
            start, stop = ctl.parse_spec(self.profile_window)
            ctl.arm(start, stop,
                    self.profile_dir or ctl._default_dir())
        from veles_tpu.caches import enable_compilation_cache
        self.debug("compile cache: %s", enable_compilation_cache())
        self.boot_distributed()
        if self.debug_nans:
            import jax
            jax.config.update("jax_debug_nans", True)
        if self.web_status_enabled:
            from veles_tpu.parallel.distributed import is_coordinator

            # shared heartbeat token: VELES_WEB_TOKEN, or a random value
            # minted by process 0 and agreed over the job control plane
            token = None
            if self.mode != "standalone":
                import os as _os
                token = _os.environ.get("VELES_WEB_TOKEN")
                if not token:
                    # a RANDOM token minted by process 0 and agreed over
                    # the jax.distributed control plane (boot_distributed
                    # already ran): workers learn it through the
                    # authenticated job channel, network bystanders can't
                    # derive it from public facts
                    import secrets

                    import numpy as _np
                    from jax.experimental import multihost_utils
                    local = _np.frombuffer(
                        secrets.token_bytes(16) if self.process_id == 0
                        else b"\x00" * 16, dtype=_np.uint8)
                    token = bytes(_np.asarray(
                        multihost_utils.broadcast_one_to_all(local))).hex()
            if self.mode == "standalone" or is_coordinator():
                from veles_tpu.web_status import WebStatusServer
                # distributed: bind all interfaces so worker heartbeats
                # from OTHER hosts can reach the cluster view (loopback
                # binding would silently drop them); standalone stays
                # loopback-only
                host = ("127.0.0.1" if self.mode == "standalone"
                        else "0.0.0.0")
                self._web = WebStatusServer(
                    self.workflow, host=host, port=self.web_port,
                    token=token,
                    # POST /profile arms an on-chip capture window on
                    # the live driver (telemetry/tracer.py); serve-only
                    # runs have no stepped driver to bracket
                    profile_controller=(
                        _ttracer.profile_controller()
                        if self.serve_port is None else None),
                    # VELES_WEB_FLEET=http://host:port points the
                    # dashboard at a serving router (--route): the
                    # status page then carries the per-replica fleet
                    # table (generation digest/age, capacity, circuit)
                    fleet_source=os.environ.get("VELES_WEB_FLEET"))
                self._web.start()
            else:
                # workers report into the coordinator's cluster view
                # (reference master's slave registry, SURVEY.md §2.5)
                from veles_tpu.web_status import HeartbeatReporter
                host = (self.master or self.listen).rsplit(":", 1)[0]
                self._web = HeartbeatReporter(
                    host, self.web_port, self.process_id,
                    token=token, workflow=self.workflow).start()
        if self.manhole_port is not None:
            from veles_tpu.manhole import ManholeServer
            self._manhole = ManholeServer(self.workflow,
                                          port=self.manhole_port).start()
        # resilience plumbing: when a Supervisor spawned this process it
        # exports VELES_HEARTBEAT_FILE — touch it now (startup liveness,
        # covers the first compile) and at every epoch boundary. A fault
        # plan (VELES_FAULT_PLAN) rides the same epoch hook registry;
        # heartbeat hooks register FIRST so a hang fault's last epoch is
        # still reported before the process stops heartbeating.
        from veles_tpu.resilience import faults as _faults
        from veles_tpu.resilience import hooks as _rhooks
        if self.mirror and getattr(self.workflow, "snapshotter",
                                   None) is not None:
            # durability plumbing (--mirror / cluster member child):
            # every snapshot write pushes a verified copy to the mirror
            self.workflow.snapshotter.mirror = self.mirror
        installed_hooks = []
        hb_path = os.environ.get("VELES_HEARTBEAT_FILE", "")
        if hb_path:
            from veles_tpu.resilience.supervisor import write_heartbeat
            epoch0 = getattr(getattr(self.workflow, "decision", None),
                             "epoch_number", 0)
            write_heartbeat(hb_path, epoch0)
            wf = self.workflow

            def _hb(epoch: int) -> None:
                # the device feed's overlap counters AND a per-device
                # memory snapshot ride the heartbeat payload so the
                # supervisor's JSON exit report shows the input-pipeline
                # health and the measured memory footprint of the
                # supervised child (loader/device_feed.py,
                # parallel/memstats.py; None for granular/jax-free runs)
                feed = getattr(wf, "feed_stats", None)
                try:
                    from veles_tpu.parallel.memstats import \
                        device_memory_stats
                    mem = device_memory_stats()
                    # the pass-6 pre-flight prediction rides the same
                    # payload, so the supervisor's exit report can
                    # promote the predicted-vs-measured memory delta
                    # next to the measured snapshot (ISSUE 14)
                    rep = getattr(wf, "resource_report", None)
                    if mem is not None and rep:
                        mem = dict(mem)
                        mem["predicted"] = {
                            "resident_per_device":
                                rep.get("resident_per_device"),
                            "highwater_per_device":
                                rep.get("highwater_per_device"),
                        }
                except Exception:  # noqa: BLE001 — stats never kill a beat
                    mem = None
                try:
                    # the one-registry snapshot rides the beat too, so
                    # the supervisor/cluster exit reports and the
                    # coordinator's fleet /metrics see the child's step
                    # counters without instrumenting the child further
                    from veles_tpu.telemetry.metrics import snapshot_flat
                    msnap = snapshot_flat()
                except Exception:  # noqa: BLE001
                    msnap = None
                write_heartbeat(hb_path, epoch, feed=feed, mem=mem,
                                metrics=msnap)
            installed_hooks.append(_rhooks.add_epoch_hook(_hb))
        plan = _faults.active_plan()
        if plan is not None:
            self.warning("fault plan active: %s", plan)
            installed_hooks.append(_rhooks.add_epoch_hook(plan.on_epoch))
        profiling = False
        if self.profile_dir and not self.profile_window:
            # whole-run profiler trace; with --profile-window the dir
            # instead receives the windowed captures (telemetry/tracer)
            import jax
            jax.profiler.start_trace(self.profile_dir)
            profiling = True
        try:
            if self.serve_port is not None:
                # serve-only: the reference's "run the forward sub-graph
                # per request" path (SURVEY.md §3.4). Typically paired
                # with -s <snapshot>; an unrestored workflow serves its
                # initialization (useful for smoke tests only).
                if not hasattr(self.workflow, "build_fused_step"):
                    raise SystemExit(
                        f"--serve: {type(self.workflow).__name__} has no "
                        "fused forward (StandardWorkflow-family only)")
                import os as _os

                from veles_tpu.serving import InferenceServer
                self.workflow.initialize(device=self.device, **kwargs)
                srv_kwargs = {}
                if self.serve_batch is not None:
                    srv_kwargs["max_batch"] = self.serve_batch
                # replica != process (ISSUE 19): N independent slot
                # rings in this one process, each with its own port
                # (explicit --serve PORT -> PORT+i; 0 -> auto), its own
                # generation ledger/watcher/beacon and its own metric
                # labels. They share the workflow build and the AOT
                # cache: replica 0 compiles-or-loads, replicas 1..N-1
                # deserialize the same signature (0 compiles).
                n = self.serve_replicas
                fleet = n > 1 or self.serve_announce is not None
                # VELES_SERVE_ADVERTISE: the host other fleet members
                # can reach THIS process at (pod IP / DNS name). It
                # becomes the beacon URL host and the rid suffix —
                # container PIDs collide across pods, advertise hosts
                # don't. Loopback fleets keep the pid suffix.
                adv = _os.environ.get("VELES_SERVE_ADVERTISE",
                                      "").strip()
                rid_suffix = (adv.replace(":", "-") if adv
                              else str(_os.getpid()))
                servers = []
                for i in range(n):
                    port = self.serve_port + i if self.serve_port else 0
                    rid = f"r{i}-{rid_suffix}" if fleet else None
                    servers.append(InferenceServer(
                        self.workflow, port=port,
                        dispatch=self.serve_dispatch,
                        ring_slots=self.serve_ring,
                        quantize=self.serve_quantize,
                        mesh=self.serve_mesh,
                        replica=rid, **srv_kwargs).start())
                info = servers[0].model_info()
                self.info("serving: replicas=%d dispatch=%s ring=%s "
                          "sharded=%s quantize=%s aot=%s",
                          n, info["dispatch"], info["ring_slots"],
                          info.get("sharded"), info["quantize"],
                          info.get("aot"))
                watchers = []
                if self.serve_watch_mirror:
                    # train→serve hot-swap loop (ISSUE 16): each
                    # replica polls the mirror for new digest-addressed
                    # snapshots and swaps them in between ring rounds.
                    # Poll cadence via VELES_WATCH_POLL_S (default 10 s
                    # — the HttpMirror retry budget stays below it).
                    from veles_tpu.resilience.mirror import get_mirror
                    from veles_tpu.serving_watch import WeightWatcher
                    try:
                        poll_s = float(_os.environ.get(
                            "VELES_WATCH_POLL_S", "10") or 10)
                    except ValueError:
                        poll_s = 10.0
                    for srv in servers:
                        watchers.append(WeightWatcher(
                            srv,
                            get_mirror(self.serve_watch_mirror,
                                       token=srv.token),
                            poll_s=poll_s).start())
                beacons = []
                if self.serve_announce:
                    # fleet presence beacons (ISSUE 19): announce each
                    # replica on the mirror bus so a `--route` front
                    # door discovers it — no config push, join-mid-run
                    from veles_tpu.resilience.mirror import get_mirror
                    from veles_tpu.serving_router import ReplicaBeacon
                    bus = get_mirror(self.serve_announce,
                                     token=servers[0].token)
                    for srv in servers:
                        beacons.append(ReplicaBeacon(
                            bus, srv.replica,
                            f"http://{adv or '127.0.0.1'}:{srv.port}",
                            health=srv.health).start())
                for srv in servers:
                    print(f"SERVING http://127.0.0.1:{srv.port}",
                          flush=True)
                try:
                    while True:
                        import time
                        time.sleep(3600)
                except KeyboardInterrupt:
                    # drain protocol: announce draining FIRST (the
                    # router stops picking us), finish in-flight via
                    # stop()'s drain wait, then say goodbye
                    for b in beacons:
                        b.drain()
                    for w in watchers:
                        w.stop()
                    for srv in servers:
                        srv.stop()
                    for b in beacons:
                        b.stop()
                return 0
            if self.autotune:
                if not hasattr(self.workflow, "autotune"):
                    raise SystemExit(
                        f"--autotune: {type(self.workflow).__name__} has "
                        "no fused step (StandardWorkflow-family only)")
                self.workflow.initialize(device=self.device, **kwargs)
                tune_rep = self.workflow.autotune(
                    budget=self.autotune_budget)
                self.info("autotune: %s", {
                    op: f"{r['variant']} ({r['source']})"
                    for op, r in sorted(tune_rep.items())})
            elif hasattr(self.workflow, "autotune") \
                    and (self.fused or self.pp
                         or self.mode != "standalone"):
                # inherit a past tuning session's persisted winners
                # (cache hits only, zero timing). Standalone always;
                # distributed only when the operator points every
                # process at the SAME cache file explicitly — per-host
                # default caches could diverge and desync the SPMD
                # programs.
                if self.mode == "standalone" \
                        or os.environ.get("VELES_AUTOTUNE_CACHE"):
                    from veles_tpu.ops.autotune import apply_cached
                    self.workflow.initialize(device=self.device, **kwargs)
                    applied = apply_cached(self.workflow)
                    if applied:
                        self.info("autotune cache applied: %s", applied)
            if self.mode != "standalone":
                # distributed run: every process executes the same SPMD
                # program over the GLOBAL device mesh; gradient averaging
                # is the in-graph psum (reference §3.2's pickled-deltas
                # loop has no analog). Granular per-unit execution is
                # single-device by construction, so distributed implies
                # the fused step.
                if not hasattr(self.workflow, "run_fused"):
                    raise SystemExit(
                        f"distributed mode: {type(self.workflow).__name__} "
                        "has no fused step (StandardWorkflow-family only)")
                import jax

                from veles_tpu.parallel.distributed import is_coordinator
                if not is_coordinator() and getattr(
                        self.workflow, "snapshotter", None) is not None:
                    # FILE writes are coordinator-only (two processes
                    # racing os.replace can publish a truncated file) —
                    # but the unit must KEEP EXISTING on workers: the
                    # snapshot branch in _run_with_step is keyed on it,
                    # and under EP/TP its write_back is a cross-process
                    # all-gather that every process must enter (an
                    # asymmetric collective deadlocks the job). Routed
                    # through the reference's IDistributable protocol.
                    self.workflow.snapshotter.apply_data_from_master(
                        {"dry_run": True})
                if self.pp:
                    # GPipe stages over the GLOBAL device set, spread
                    # ROUND-ROBIN over processes: a first-N prefix could
                    # leave a process with no stage device, and a
                    # process outside the mesh cannot join the param
                    # gathers at write_back (asymmetric crash)
                    from veles_tpu.parallel.pipeline import make_stage_mesh
                    n_stages = max(1, min(len(jax.devices()),
                                          len(self.workflow.forwards)))
                    if n_stages < self.n_processes:
                        raise SystemExit(
                            f"distributed --pp needs >= one stage per "
                            f"process: {n_stages} stages < "
                            f"{self.n_processes} processes")
                    by_proc: dict = {}
                    for d in jax.devices():
                        by_proc.setdefault(d.process_index, []).append(d)
                    stage_devs, i = [], 0
                    procs = sorted(by_proc)
                    while len(stage_devs) < n_stages:
                        p = by_proc[procs[i % len(procs)]]
                        if p:
                            stage_devs.append(p.pop(0))
                        i += 1
                    smesh = make_stage_mesh(stage_devs)
                    self.info(
                        "distributed %s: %d processes, stage mesh %s",
                        self.mode, self.n_processes, dict(smesh.shape))
                    self.workflow.run_pipelined(
                        mesh=smesh, n_microbatches=self.pp,
                        device=self.device,
                        feed_ahead=self.feed_ahead, **kwargs)
                else:
                    from veles_tpu.parallel.mesh import make_mesh
                    mesh = make_mesh(jax.devices(), model=self.tp or 1,
                                     seq=self.sp or 1)
                    self.info(
                        "distributed %s: %d processes, %d global "
                        "devices, mesh %s", self.mode, self.n_processes,
                        jax.device_count(), dict(mesh.shape))
                    # mode="auto": FusedTrainStep derives seq/gspmd/dp
                    # from the mesh axis sizes — one source of truth
                    self.workflow.run_fused(
                        device=self.device, mesh=mesh,
                        mode="auto", ep=self.ep,
                        accum_steps=self.accum,
                        nonfinite_guard=self.nonfinite_guard,
                        feed_ahead=self.feed_ahead,
                        zero_sharding=self.zero_sharding, **kwargs)
            elif self.pp:
                if not hasattr(self.workflow, "run_pipelined"):
                    raise SystemExit(
                        f"--pp: {type(self.workflow).__name__} has no "
                        "pipeline step (StandardWorkflow-family only)")
                self.workflow.run_pipelined(
                    n_microbatches=self.pp, device=self.device,
                    nonfinite_guard=self.nonfinite_guard,
                    feed_ahead=self.feed_ahead, **kwargs)
            elif self.fused:
                if not hasattr(self.workflow, "run_fused"):
                    raise SystemExit(
                        f"--fused: {type(self.workflow).__name__} has no "
                        "fused step (StandardWorkflow-family only)")
                self.workflow.run_fused(
                    device=self.device, accum_steps=self.accum,
                    nonfinite_guard=self.nonfinite_guard,
                    feed_ahead=self.feed_ahead,
                    zero_sharding=self.zero_sharding, **kwargs)
            else:
                if self.nonfinite_guard and hasattr(self.workflow,
                                                    "decision"):
                    # granular graph: the Decision unit raises at the
                    # minibatch whose (already host-synced) loss goes
                    # non-finite — closing the ROADMAP gap "granular
                    # mode has no non-finite guard"; same exit-81 ->
                    # supervisor-rollback contract as the fused path
                    self.workflow.decision.nonfinite_guard = True
                self.workflow.initialize(device=self.device, **kwargs)
                self.workflow.run()
        except KeyboardInterrupt:
            self.warning("interrupted; stopping workflow")
            self.workflow.stop()
            return 130
        except NonFiniteLossError as e:
            # distinct exit code: the Supervisor maps it to "roll back
            # one snapshot before retrying" (the newest snapshot may
            # already embed the divergence)
            self.error("training aborted: %s (exit %d)", e,
                       EXIT_NONFINITE)
            self.workflow.stop()
            return EXIT_NONFINITE
        except ResourcePreflightError as e:
            # pass-6 pre-flight (analysis/resources.py): the static HBM
            # model says this (model, mesh, batch, ZeRO) combination
            # exceeds the device limit — refuse in seconds, with the
            # per-component breakdown, instead of OOMing minutes into
            # the compile
            self.error("run refused by the resource pre-flight: %s", e)
            self.workflow.stop()
            return 1
        finally:
            for fn in installed_hooks:   # next run re-registers fresh
                _rhooks.remove_epoch_hook(fn)
            if profiling:
                import jax
                jax.profiler.stop_trace()
                self.info("profiler trace -> %s", self.profile_dir)
            # close a window the run ended inside of — ALWAYS, not
            # only under --profile-window: POST /profile arms windows
            # on runs launched without the flag, and an interrupt
            # mid-window must still flush the capture (no-op when
            # nothing is armed)
            _ttracer.profile_controller().finalize()
            if tracer_obj is not None:
                try:
                    tracer_obj.export(self.trace_path)
                    self.info("step timeline -> %s (%d span(s), %d "
                              "dropped)", self.trace_path,
                              tracer_obj._n, tracer_obj.dropped)
                except OSError as e:
                    self.warning("trace export failed: %s", e)
                _ttracer.uninstall()
            # final metrics flush so short runs land at least one
            # JSONL row (guarded: report cosmetics never mask errors)
            try:
                _tmetrics.flush_installed(extra={"source": "exit"})
            except Exception:  # noqa: BLE001
                pass
            if self._web is not None:
                self._web.stop()
            if self._manhole is not None:
                self._manhole.stop()
            if self.show_stats and hasattr(self.workflow, "print_stats"):
                self.workflow.print_stats()
            if self.report_path:
                # guarded like _stop_units: a bad report path must not
                # mask the run's real exception or fail a finished run
                try:
                    # flush queued plot specs to files first so the HTML
                    # embeds the final epoch's curves, not a stale state —
                    # and remember where that renderer actually wrote
                    from veles_tpu import plotter as _plotter
                    plots_dir = getattr(_plotter._default_renderer,
                                        "directory", "plots")
                    _plotter.stop_default_renderer()
                    from veles_tpu.publishing import (write_report,
                                                      write_results)
                    base, ext = os.path.splitext(self.report_path)
                    if ext.lower() in (".html", ".htm"):
                        write_report(self.workflow, self.report_path,
                                     plots_dir=plots_dir)
                        write_results(self.workflow, base + ".json")
                    else:
                        write_results(self.workflow, self.report_path)
                    self.info("run report -> %s", self.report_path)
                except Exception as e:  # noqa: BLE001
                    self.warning("report writing failed: %s", e)
        return 0

    def run_module(self, module) -> int:
        """Invoke a sample module's `run(load, main)` entry."""
        status = {"code": 0}

        def main(**kwargs: Any) -> None:
            status["code"] = self.main(**kwargs)

        module.run(self.load, main)
        return status["code"]


def apply_overrides(args) -> None:
    """Apply trailing CLI `root.a.b=value` overrides to the global root."""
    from veles_tpu.config import parse_override
    for arg in args:
        dotted, value = parse_override(arg)
        if dotted.startswith("root."):
            dotted = dotted[len("root."):]
        root.override(dotted, value)
