"""CLI entry: `python -m veles_tpu [flags] workflow.py [config.py] [root.x=y ...]`.

Parity: reference `veles/__main__.py` (SURVEY.md §2.9) — imports the config
module (which mutates the global `root`), applies trailing dotted-path
overrides, builds a Launcher (standalone / coordinator `-l` / worker `-m`),
imports the workflow module and calls its `run(load, main)`.

Flags map 1:1 where the concept survives the TPU redesign; the reference's
backend-selection flags become `--backend numpy|xla` (golden host path vs
jit path), and master/slave become distributed coordinator/worker roles.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

from veles_tpu import prng
from veles_tpu.launcher import Launcher, apply_overrides
from veles_tpu.logger import add_log_file, set_verbosity


def _import_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SystemExit(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="veles_tpu",
        description="Run a workflow: veles_tpu workflow.py [config.py] "
                    "[root.path.key=value ...]",
        # --daemon re-execs the original argv minus the exact "--daemon"
        # tokens; an abbreviated "--daemo" would survive that filter and
        # respawn forever, so abbreviations are off
        allow_abbrev=False)
    # nargs="?": the --serve-rollback CLIENT mode needs no workflow to
    # import; every other mode validates its presence in main()
    p.add_argument("workflow", nargs="?", default="",
                   help="workflow module (.py) with run(load, main)")
    p.add_argument("config", nargs="?", default="",
                   help="config module (.py) mutating the global root")
    p.add_argument("overrides", nargs="*", default=[],
                   help="trailing root.a.b=value overrides")
    p.add_argument("-s", "--snapshot", default="",
                   help="resume from a snapshot file")
    p.add_argument("-b", "--backend", default="xla",
                   choices=("xla", "numpy"),
                   help="compute backend (numpy = golden host path)")
    p.add_argument("-r", "--random-seed", type=int, default=None,
                   help="seed all PRNGs for a deterministic run")
    p.add_argument("-l", "--listen", default="",
                   help="distributed coordinator bind address host:port")
    p.add_argument("-m", "--master", default="",
                   help="join a distributed coordinator at host:port")
    p.add_argument("--process-id", type=int, default=0,
                   help="this process's index in the distributed job")
    p.add_argument("--n-processes", type=int, default=1,
                   help="total process count in the distributed job")
    p.add_argument("-v", "--verbose", action="count", default=0,
                   help="-v info, -vv debug")
    p.add_argument("--log-file", default="", metavar="PATH",
                   help="also write DEBUG-level logs to this file")
    p.add_argument("--no-stats", action="store_true",
                   help="skip the per-unit run-time table")
    p.add_argument("-w", "--web-status", action="store_true",
                   help="serve the status dashboard while running")
    p.add_argument("--web-port", type=int, default=8090)
    p.add_argument("--manhole", nargs="?", const=0, default=None,
                   type=int, metavar="PORT",
                   help="listen for live-attach REPL connections on "
                        "127.0.0.1:PORT (0 = auto-pick); attach with "
                        "python -m veles_tpu.manhole <port>")
    p.add_argument("-p", "--profile", default="", metavar="DIR",
                   help="write a jax.profiler trace (TensorBoard/Perfetto)")
    p.add_argument("--trace", default="", metavar="PATH",
                   help="step-timeline tracing (docs/OBSERVABILITY.md): "
                        "record driver-loop spans (feed pops, async "
                        "dispatch, the in-flight device window, "
                        "Decision/snapshot bookkeeping, the next "
                        "batch's device_put) into a bounded ring "
                        "buffer and write a Chrome-trace/Perfetto-"
                        "loadable trace.json to PATH at the end of the "
                        "run; a metrics JSONL sink mirrors every flush "
                        "to PATH.metrics.jsonl. Consumed by --fused/"
                        "--pp/-l/-m runs and --serve")
    p.add_argument("--profile-window", default="", metavar="N:M",
                   help="bracket driver steps N..M (inclusive) with "
                        "jax.profiler start/stop — an on-chip capture "
                        "window instead of profiling the whole run "
                        "(-p DIR sets the output directory; default "
                        "telemetry_profile/). A live run can also be "
                        "captured via POST /profile on the web-status "
                        "control plane. Combine with --fused/--pp/-l/-m")
    p.add_argument("--debug-nans", action="store_true",
                   help="enable jax NaN checking (debug runs)")
    p.add_argument("--verify-workflow", nargs="?", const="graph",
                   default=None,
                   choices=("graph", "audit", "resources", "modelcheck"),
                   metavar="{graph,audit,resources,modelcheck}",
                   help="statically verify the constructed workflow "
                        "(analysis pass: dangling/shadowed link_attrs "
                        "aliases, AND-gate control cycles, unreachable "
                        "units, read-before-write flows, plus "
                        "environment findings like the non-finite guard "
                        "left off), "
                        "print the findings and exit nonzero on errors "
                        "WITHOUT training — docs/ANALYSIS.md. "
                        "--verify-workflow=audit ALSO runs the jaxpr "
                        "auditor over the initialized workflow's fused "
                        "step (f64 promotion, host syncs, dropped "
                        "donation, sharding drift; traces, never "
                        "compiles). --verify-workflow=resources ALSO "
                        "runs the static resource analyzer (pass 6): "
                        "kernel VMEM footprints vs the device budget "
                        "and the per-device HBM model (params + grads "
                        "+ ZeRO optimizer vectors + activation "
                        "high-water + feed buffers) vs the memstats "
                        "device limit. --verify-workflow=modelcheck "
                        "ALSO runs a small fixed-budget sweep of the "
                        "protocol model checker (pass 8): bounded "
                        "interleaving exploration of the election / "
                        "membership / hot-swap planes — the full CI "
                        "gate is tools/modelcheck.py --ci")
    p.add_argument("--serve", nargs="?", const=0, default=None, type=int,
                   metavar="PORT",
                   help="serve the (snapshot-restored) model over HTTP "
                        "instead of training: POST /predict, GET /info. "
                        "Default core: a continuous-batching slot ring, "
                        "GSPMD-sharded over the local devices, with the "
                        "compiled serving step persisted in the AOT "
                        "cache so a replica restart skips compile "
                        "(docs/SERVING.md)")
    p.add_argument("--serve-ring", type=int, default=None, metavar="N",
                   help="rows in the serving slot ring (the fixed-shape "
                        "device-resident batch the dispatch loop runs "
                        "every round; default = --serve-batch). Frozen "
                        "into the AOT-compiled executable's shape — "
                        "combine with --serve")
    p.add_argument("--serve-dispatch", default=None,
                   choices=("ring", "merge"),
                   help="serving execution core: 'ring' (default) = "
                        "continuous batching on the slot ring; 'merge' "
                        "= the pre-ring bucketed micro-batching core "
                        "(the tools/loadtest.py A/B baseline). Combine "
                        "with --serve")
    p.add_argument("--serve-quantize", default=None,
                   choices=("f32", "bf16", "int8"),
                   help="serving wire format for model params (the "
                        "serve_forward registry op): bf16 halves model "
                        "bytes, int8 is weight-only blockwise (~/4); "
                        "both are REFUSED unserved without a passing "
                        "ops.reference equivalence record. Combine "
                        "with --serve")
    p.add_argument("--serve-mesh", default=None,
                   choices=("auto", "on", "off"),
                   help="GSPMD-shard the served forward over the local "
                        "device mesh via the trainer's NamedSharding "
                        "plan: auto (default) shards when >1 device "
                        "and the ring divides the data axis, on "
                        "insists, off serves unsharded. Combine with "
                        "--serve")
    p.add_argument("--serve-batch", type=int, default=None, metavar="N",
                   help="per-request row cap for --serve (default 64); "
                        "the ring size defaults to it")
    p.add_argument("--serve-watch-mirror", default=None, metavar="SPEC",
                   help="hot-swap deployment (train→serve): poll this "
                        "snapshot mirror (a directory or http(s) URL, "
                        "the --mirror grammar) for new digest-addressed "
                        "snapshots, verify + validate each candidate, "
                        "and swap it into the running slot ring between "
                        "rounds — no recompile, no drain; any failure "
                        "keeps the current generation serving "
                        "(docs/SERVING.md 'Continuous deployment'). "
                        "Poll cadence via VELES_WATCH_POLL_S (10 s). "
                        "Combine with --serve")
    p.add_argument("--serve-rollback", default=None, metavar="URL",
                   help="client mode: POST /rollback to the running "
                        "server at URL — re-point its ring at the "
                        "PREVIOUS weight generation — print the "
                        "response and exit (no workflow argument; "
                        "token from VELES_WEB_TOKEN). Pointed at a "
                        "--route front door it fans out to every live "
                        "replica and reports per-replica outcomes")
    p.add_argument("--serve-replicas", type=int, default=None,
                   metavar="N",
                   help="run N independent serving replicas in this "
                        "process (each its own slot ring, port "
                        "[--serve PORT -> PORT..PORT+N-1], generation "
                        "ledger, watcher and metric labels; shared AOT "
                        "cache so replicas 2..N start with 0 "
                        "compiles). Combine with --serve")
    p.add_argument("--serve-announce", default=None, metavar="SPEC",
                   help="announce each serving replica on this mirror "
                        "bus (the --mirror grammar) as a presence "
                        "beacon, so a --route front door discovers it "
                        "— join-mid-run needs no config push. Combine "
                        "with --serve")
    p.add_argument("--route", default=None, metavar="SPEC",
                   help="fleet front door (no workflow, no jax): "
                        "discover serving replicas announced on this "
                        "mirror bus and route POST /predict across "
                        "them by live capacity — bounded "
                        "retry/backoff, per-replica circuit breaker, "
                        "p99 hedging, drain awareness; POST /rollback "
                        "fans out fleet-wide (docs/SERVING.md "
                        "'Fleet'; token from VELES_WEB_TOKEN)")
    p.add_argument("--route-port", type=int, default=None,
                   metavar="PORT",
                   help="listen port for --route (default: auto)")
    p.add_argument("--pp", type=int, default=None, metavar="MICROBATCHES",
                   help="train as a GPipe pipeline over the local devices "
                        "(one stage per device) with this many microbatches")
    p.add_argument("--fused", action="store_true",
                   help="train via the fused one-dispatch-per-minibatch "
                        "XLA step instead of the granular unit graph")
    p.add_argument("--autotune", action="store_true",
                   help="before training, time every registered lowering "
                        "variant of the workflow's tunable ops (LRN, "
                        "pooling backward, s2d stem, dropout RNG) via a "
                        "short fused microbench and train with the "
                        "winners; decisions persist in the on-disk "
                        "autotune cache, so reruns are pure cache hits "
                        "(docs/AUTOTUNE.md)")
    p.add_argument("--autotune-budget", type=int, default=None,
                   metavar="N",
                   help="with --autotune: spend up to N trials per "
                        "tuning pass on a coordinate-descent search "
                        "over the GENERATED kernel candidates "
                        "(ops.templates config spaces), priority-"
                        "ordered by LAYER_PROFILE.json; every generated "
                        "point is equivalence-gated against "
                        "ops.reference before it may be timed "
                        "(docs/AUTOTUNE.md)")
    p.add_argument("--tp", type=int, default=None, metavar="K",
                   help="tensor-parallel degree for distributed runs: "
                        "global mesh (data x model=K), megatron gspmd "
                        "step; combine with -l/-m")
    p.add_argument("--sp", type=int, default=None, metavar="K",
                   help="sequence-parallel degree for distributed runs: "
                        "ring attention over the mesh 'seq' axis "
                        "(long-context); combine with -l/-m")
    p.add_argument("--ep", action="store_true",
                   help="expert parallelism for distributed MoE runs: "
                        "expert tensors sharded over the data axis, "
                        "all_to_all token exchange; combine with -l/-m")
    p.add_argument("--feed-ahead", type=int, default=None, metavar="N",
                   help="device-feed lookahead depth for --fused/--pp "
                        "runs (loader/device_feed.py): while step k "
                        "computes, the next N batches' async sharded "
                        "device_put is already in flight. Default 1 "
                        "(the classic double buffer); 0 disables "
                        "lookahead")
    p.add_argument("--zero-sharding", nargs="?", const="on",
                   default="auto", choices=("on", "off", "auto"),
                   metavar="{on,off,auto}",
                   help="ZeRO-style sharded weight update for the fused "
                        "dp step (arxiv 2004.13336): reduce-scatter "
                        "grads, update this replica's 1/N slice of "
                        "params + optimizer state, all-gather fresh "
                        "params — optimizer-state memory /N, at the "
                        "price of the gather. Default auto = on where "
                        "the replicated update's state (12-16 B a "
                        "parameter) passes half the device's memory "
                        "limit, off below it; on/auto degrade with a "
                        "logged reason for GPipe, gspmd/seq, EP and "
                        "multi-host meshes. Bare "
                        "--zero-sharding means 'on' — place it AFTER "
                        "the positional workflow/config arguments (or "
                        "spell the value) so it cannot swallow them")
    p.add_argument("--accum", type=int, default=None, metavar="K",
                   help="gradient accumulation: compute each minibatch's "
                        "gradient as K scanned microbatches before the "
                        "single update (fused/distributed modes; "
                        "activation memory /K, numerics unchanged)")
    p.add_argument("--no-plot", action="store_true",
                   help="disable all plotting units (reference CLI flag):"
                        " plotters become no-ops, no renderer starts")
    p.add_argument("--report", default="", metavar="PATH",
                   help="write an end-of-run report: PATH.html = "
                        "self-contained HTML (metrics, config snapshot, "
                        "unit times, embedded plots) plus the .json "
                        "summary; PATH.json = machine summary only")
    p.add_argument("--daemon", default="", metavar="LOGFILE",
                   help="run detached in the background (reference "
                        "background/daemon mode): re-exec this command "
                        "line in a new session with stdio redirected to "
                        "LOGFILE, print the background pid on stdout and "
                        "return immediately")
    p.add_argument("--supervise", action="store_true",
                   help="run under the resilience supervisor: this "
                        "process becomes a light parent that spawns the "
                        "training run, watches its per-epoch heartbeat, "
                        "and on crash/hang restarts it from the newest "
                        "VALID snapshot (exponential backoff, bounded "
                        "retries, no-progress cutoff)")
    p.add_argument("--max-restarts", type=int, default=3, metavar="N",
                   help="supervisor retry budget: give up after N "
                        "restarts (default 3)")
    p.add_argument("--stall-timeout", type=float, default=300.0,
                   metavar="SECONDS",
                   help="supervisor hang detection: kill + restart the "
                        "job when its heartbeat (touched every epoch) "
                        "goes stale this long (default 300; 0 disables)")
    p.add_argument("--snapshot-dir", default=".", metavar="DIR",
                   help="where the supervisor looks for snapshots to "
                        "restart from (default: cwd)")
    p.add_argument("--snapshot-prefix", default="", metavar="PREFIX",
                   help="snapshot filename prefix filter for --supervise "
                        "restarts")
    p.add_argument("--supervise-report", default="", metavar="PATH",
                   help="write the supervisor's JSON exit report "
                        "(attempt log, outcome) to PATH")
    p.add_argument("--mirror", default="", metavar="SPEC",
                   help="snapshot durability mirror: a second directory "
                        "or an http(s):// blob-store URL. Every "
                        "snapshot write is pushed there (sha256-"
                        "verified, idempotent) and --supervise/--cluster "
                        "restarts restore from it when the local "
                        "snapshot dir is missing or corrupt "
                        "(docs/RESILIENCE.md)")
    p.add_argument("--cluster", default="", metavar="HOST:PORT",
                   help="with --supervise: join the cluster control "
                        "plane at HOST:PORT (host 0 binds it) — "
                        "cross-host quorum restarts, gang respawn on a "
                        "coordinated generation counter, dead-host "
                        "declaration for the scheduler")
    p.add_argument("--cluster-hosts", type=int, default=1, metavar="N",
                   help="the cluster's host-count FLOOR (minimum live "
                        "hosts, >= 1): boot hosts use ids 0..N-1, "
                        "joiners grow the membership past it, deaths "
                        "shrink back down to it (below = fail-stop "
                        "exit 84); quorum follows the live membership "
                        "(majority)")
    p.add_argument("--host-id", type=int, default=0, metavar="K",
                   help="this host's index in the --cluster job "
                        "(0 also runs the coordinator; ids >= "
                        "--cluster-hosts need --cluster-join)")
    p.add_argument("--cluster-join", action="store_true",
                   help="join a RUNNING --cluster job mid-run with a "
                        "host id outside the boot membership: the host "
                        "announces itself via the control plane's "
                        "/join endpoint and is admitted at the next "
                        "generation bump (the gang respawn rebuilds "
                        "the job over the grown host set)")
    p.add_argument("--cluster-advertise", default="", metavar="HOST",
                   help="address peers can reach THIS host on if it "
                        "is promoted to coordinator after a "
                        "re-election (default: 127.0.0.1 when the "
                        "--cluster address is loopback, else this "
                        "host's fqdn)")
    p.add_argument("--cluster-beat", type=float, default=1.0,
                   metavar="SECONDS",
                   help="cluster heartbeat interval (default 1.0)")
    p.add_argument("--cluster-dead-after", type=float, default=30.0,
                   metavar="SECONDS",
                   help="declare a host DEAD (stop the run, report it "
                        "to the scheduler) after this long without a "
                        "heartbeat from it (default 30)")
    p.add_argument("--nonfinite-guard", action="store_true",
                   help="abort fused/pipelined training with a distinct "
                        "exit code the moment the loss goes NaN/inf "
                        "(the supervisor then rolls back one snapshot "
                        "before retrying)")
    p.add_argument("--optimize", type=int, default=0, metavar="GENERATIONS",
                   help="genetic hyperparameter search instead of a single "
                        "run: the workflow/config module must define "
                        "TUNABLES = [genetics.Tune(...)]; fitness is the "
                        "best validation error of each spawned run")
    return p


def _daemonize(log_path: str, argv) -> int:
    """Detach by RE-EXEC, not fork: spawn a fresh interpreter on the same
    command line minus `--daemon`, in a new session, stdio → `log_path`,
    and return its pid. A bare fork would inherit this process's runtime
    threads (jax/absl start them at import) with whatever locks they
    hold — re-exec gives the background run a clean process exactly like
    the foreground one."""
    import subprocess

    from veles_tpu.resilience.supervisor import strip_flags

    log_path = os.path.abspath(log_path)
    cmd = [sys.executable, "-m", "veles_tpu"] \
        + strip_flags(argv, {"--daemon": True})
    logfd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    nullfd = os.open(os.devnull, os.O_RDONLY)
    try:
        child = subprocess.Popen(
            cmd, stdin=nullfd, stdout=logfd, stderr=logfd,
            start_new_session=True,           # own session: survives ctty
            cwd=os.getcwd())
    finally:
        os.close(logfd)
        os.close(nullfd)
    return child.pid


#: supervisor-only flags, stripped from the child's command line
#: (flag name -> takes a value). --mirror is NOT here: the child's
#: Snapshotter needs it to push durable copies.
_SUPERVISOR_FLAGS = {"--supervise": False, "--max-restarts": True,
                     "--stall-timeout": True, "--snapshot-dir": True,
                     "--snapshot-prefix": True, "--supervise-report": True,
                     "--cluster": True, "--cluster-hosts": True,
                     "--host-id": True, "--cluster-beat": True,
                     "--cluster-dead-after": True,
                     "--cluster-join": False,
                     "--cluster-advertise": True}


def _supervise(args, argv) -> int:
    """--supervise: become the resilience supervisor. This process stays
    import-light (no jax, no workflow module) — it only spawns/watches
    the real training command (= argv minus the supervisor-only flags)
    and restarts it from snapshots. With --cluster it becomes the
    per-host member of the cross-host control plane instead (host 0
    also runs the coordinator)."""
    if args.serve is not None:
        raise SystemExit("--supervise supervises training runs; it "
                         "conflicts with --serve")
    if args.optimize:
        raise SystemExit("--supervise and --optimize are exclusive "
                         "modes (GA individuals are already independent "
                         "restartable runs)")
    from veles_tpu.resilience.supervisor import Supervisor, strip_flags
    cmd = [sys.executable, "-m", "veles_tpu"] \
        + strip_flags(argv, _SUPERVISOR_FLAGS)
    if args.cluster:
        from veles_tpu.resilience.cluster import (ClusterCoordinator,
                                                  ClusterMember)
        # eager flag validation: a bad floor/id pair must fail HERE,
        # naming both flags, not deep inside member startup
        if args.cluster_hosts < 1:
            raise SystemExit(
                f"--cluster-hosts {args.cluster_hosts} is not a valid "
                f"floor: it is the MINIMUM live host count and must "
                f"be >= 1")
        if args.host_id < 0:
            raise SystemExit(f"--host-id {args.host_id} must be >= 0")
        if args.host_id >= args.cluster_hosts and not args.cluster_join:
            raise SystemExit(
                f"--host-id {args.host_id} is outside the boot "
                f"membership 0..{args.cluster_hosts - 1} implied by "
                f"--cluster-hosts {args.cluster_hosts}: boot hosts "
                f"use ids below the floor; pass --cluster-join to "
                f"join a running cluster with a new id")
        token = os.environ.get("VELES_WEB_TOKEN") or None
        host, _, port = args.cluster.rpartition(":")
        if not port.isdigit():
            raise SystemExit(f"--cluster needs host:port "
                             f"(got {args.cluster!r})")
        if not token and host not in ("127.0.0.1", "localhost", "::1"):
            # same secure-by-default rule as --optimize -l: restart
            # directives on an open port = any peer can roll back or
            # stop the fleet. An EMPTY host is NOT exempt — it makes
            # the coordinator bind 0.0.0.0.
            raise SystemExit(
                "--cluster on a non-loopback address needs a shared "
                "secret: set VELES_WEB_TOKEN on every host (or bind "
                "127.0.0.1:PORT for single-box tests)")
        loopback = host in ("127.0.0.1", "localhost", "::1")
        if args.cluster_advertise:
            advertise = args.cluster_advertise
        elif loopback:
            advertise = "127.0.0.1"
        else:
            import socket
            advertise = socket.getfqdn()
        coordinator = None
        if args.host_id == 0 and not args.cluster_join:
            # a re-placed host 0 REJOINING an elected cluster must not
            # bind a rival control plane: --cluster-join skips the
            # embedded coordinator and re-homes via the mirror record
            coordinator = ClusterCoordinator(
                args.cluster_hosts, host=host or "0.0.0.0",
                port=int(port), token=token,
                dead_after=args.cluster_dead_after,
                max_restarts=args.max_restarts,
                mirror=args.mirror, coord_id="0",
                # the ANNOUNCED endpoint must be an address peers can
                # actually dial — never the bind host (a 0.0.0.0 bind
                # announced verbatim would re-home every member to its
                # own loopback)
                advertise=advertise).start()
        member = ClusterMember(
            [cmd], host_id=str(args.host_id),
            coordinator_addr=f"{host or '127.0.0.1'}:{port}",
            coordinator=coordinator,
            snapshot_dir=args.snapshot_dir,
            snapshot_prefix=args.snapshot_prefix,
            mirror=args.mirror, token=token, beat_s=args.cluster_beat,
            coord_timeout=max(args.cluster_dead_after * 2, 10.0),
            stall_timeout=args.stall_timeout,
            report_path=args.supervise_report,
            floor=args.cluster_hosts,
            dead_after=args.cluster_dead_after,
            max_restarts=args.max_restarts,
            join=args.cluster_join, advertise=advertise)
        return member.run()
    sup = Supervisor(
        [cmd], snapshot_dir=args.snapshot_dir,
        snapshot_prefix=args.snapshot_prefix,
        max_restarts=args.max_restarts,
        stall_timeout=args.stall_timeout,
        report_path=args.supervise_report,
        mirror=args.mirror)
    return sup.run()


def _serve_rollback(url: str) -> int:
    """POST /rollback to a running InferenceServer and print the JSON
    response. Exit 0 on an applied rollback, 1 on refusal (409 — no
    previous generation resident) or transport failure."""
    import urllib.error
    import urllib.request
    url = url.rstrip("/")
    if not url.startswith(("http://", "https://")):
        url = "http://" + url
    req = urllib.request.Request(url + "/rollback", data=b"",
                                 method="POST")
    token = os.environ.get("VELES_WEB_TOKEN")
    if token:
        req.add_header("X-Veles-Token", token)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            payload = json.loads(resp.read())
    except urllib.error.HTTPError as e:
        try:
            payload = json.loads(e.read())
        except ValueError:
            payload = {"error": str(e)}
        print(json.dumps(payload), flush=True)
        return 1
    except (urllib.error.URLError, OSError) as e:
        print(json.dumps({"error": str(e)}), flush=True)
        return 1
    print(json.dumps(payload), flush=True)
    return 0


def _route(args) -> int:
    """Fleet front-door mode (ISSUE 19): stand up a ServingRouter over
    the replica beacons on the given mirror bus and serve until
    interrupted. No workflow import, no jax — a router must run on a
    box that can't build the model (same discipline as
    --serve-rollback)."""
    import time

    from veles_tpu.resilience.mirror import get_mirror
    from veles_tpu.serving_router import ServingRouter
    token = os.environ.get("VELES_WEB_TOKEN")
    router = ServingRouter(get_mirror(args.route, token=token),
                           port=args.route_port or 0,
                           token=token).start()
    print(f"ROUTING http://127.0.0.1:{router.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        router.stop()
    return 0


def main(argv=None) -> int:
    # intermixed parsing: this environment's argparse otherwise refuses
    # trailing `root.a.b=value` overrides once any optional flag
    # separates them from the workflow positional (`wf.py --no-stats
    # root.x=1` errored with "unrecognized arguments")
    args = build_parser().parse_intermixed_args(argv)
    if "=" in args.config:
        # `veles_tpu wf.py root.a.b=1` with config omitted: argparse binds
        # the first override to the config positional — reroute it
        args.overrides.insert(0, args.config)
        args.config = ""
    if args.serve_rollback:
        # client mode: one control-plane POST against a RUNNING server,
        # before any workflow import or backend touch — a rollback must
        # work from a box that can't even build the model
        if args.workflow:
            raise SystemExit("--serve-rollback is a client mode: it "
                             "takes no workflow argument")
        return _serve_rollback(args.serve_rollback)
    if args.route:
        # router mode: beacon discovery + HTTP, before any workflow
        # import or backend touch — the front door must run on a box
        # that can't even build the model
        if args.workflow:
            raise SystemExit("--route is a router mode: it takes no "
                             "workflow argument")
        if args.daemon:
            daemon_pid = _daemonize(
                args.daemon, argv if argv is not None else sys.argv[1:])
            print(daemon_pid, flush=True)
            return 0
        set_verbosity(args.verbose)
        return _route(args)
    if args.route_port is not None:
        raise SystemExit("--route-port configures the fleet router: "
                         "combine with --route")
    if not args.workflow:
        raise SystemExit("workflow module required (or --serve-rollback "
                         "URL / --route SPEC for workflow-less modes)")
    if args.daemon:
        daemon_pid = _daemonize(
            args.daemon, argv if argv is not None else sys.argv[1:])
        print(daemon_pid, flush=True)
        return 0
    set_verbosity(args.verbose)
    if args.cluster and not args.supervise:
        raise SystemExit("--cluster is a supervision mode: combine it "
                         "with --supervise")
    if (args.cluster_join or args.cluster_advertise) and \
            not args.cluster:
        # the --feed-ahead precedent: a cluster-only flag without
        # --cluster would be silently ignored — reject it instead
        raise SystemExit("--cluster-join/--cluster-advertise only "
                         "apply to --cluster runs: add --supervise "
                         "--cluster HOST:PORT")
    if args.supervise:
        return _supervise(args, argv if argv is not None else sys.argv[1:])
    if args.no_plot:
        from veles_tpu.config import root as _root
        _root.common.plotting_disabled = 1
    if args.log_file:
        add_log_file(args.log_file)
    if args.random_seed is not None:
        prng.seed_all(args.random_seed)

    # Import order matters: the workflow module registers its root DEFAULTS
    # at import time, so it must run before the config module and the CLI
    # overrides or it would clobber them (reference §3.1: defaults live with
    # the sample, config.py + trailing args win).
    wf_path = os.path.abspath(args.workflow)
    module = _import_file(wf_path, "veles_workflow")
    if not hasattr(module, "run"):
        raise SystemExit(f"{args.workflow} has no run(load, main) entry")
    if args.config:
        _import_file(args.config, "veles_config")
    apply_overrides(args.overrides)

    if (args.listen or args.master) and not args.optimize \
            and not args.verify_workflow:
        # verify-only runs never touch the backend: joining the SPMD job
        # would block on peers for a static check
        # MUST run before make_device: jax.distributed.initialize rejects
        # any call after the XLA backend is touched (found by live drive;
        # the Launcher's boot_distributed is idempotent and will no-op).
        # --optimize mode does NOT join an SPMD job: individuals are
        # independent runs and -l/-m address the fitness lease queue
        # (run_optimize) instead.
        from veles_tpu.parallel.distributed import initialize_distributed
        initialize_distributed(coordinator=args.listen or args.master,
                               process_id=args.process_id,
                               n_processes=args.n_processes)

    from veles_tpu.backends import make_device
    device = make_device(args.backend)

    launcher = Launcher(
        snapshot=args.snapshot, listen=args.listen, master=args.master,
        process_id=args.process_id, n_processes=args.n_processes,
        device=device, stats=not args.no_stats,
        web_status=args.web_status, web_port=args.web_port,
        profile_dir=args.profile, debug_nans=args.debug_nans,
        fused=args.fused, autotune=args.autotune,
        autotune_budget=args.autotune_budget,
        manhole=args.manhole, pp=args.pp,
        serve=args.serve, serve_ring=args.serve_ring,
        serve_dispatch=args.serve_dispatch,
        serve_quantize=args.serve_quantize,
        serve_mesh=args.serve_mesh, serve_batch=args.serve_batch,
        serve_watch_mirror=args.serve_watch_mirror,
        serve_replicas=args.serve_replicas,
        serve_announce=args.serve_announce,
        accum=args.accum, report=args.report,
        tp=args.tp, sp=args.sp, ep=args.ep,
        nonfinite_guard=args.nonfinite_guard,
        verify_workflow=args.verify_workflow or "",
        mirror=args.mirror, feed_ahead=args.feed_ahead,
        zero_sharding=args.zero_sharding,
        trace=args.trace, profile_window=args.profile_window)
    if args.verify_workflow:
        # takes precedence over every execution mode (incl. --optimize,
        # which otherwise bypasses Launcher.main entirely): the flag
        # promises "exit nonzero on errors WITHOUT training"
        return launcher.run_module(module)
    if args.optimize:
        if args.serve is not None:
            raise SystemExit("--serve and --optimize are exclusive modes")
        if args.report:
            # per-run reports don't exist in GA mode (each individual is
            # its own stats-off run); reject rather than silently ignore
            raise SystemExit("--report applies to a single run; in "
                             "--optimize mode the GA summary JSON is "
                             "printed on stdout")
        return run_optimize(module, args, device)
    return launcher.run_module(module)


def run_optimize(module, args, device) -> int:
    """Reference `--optimize` mode: GA over the module's TUNABLES, each
    individual a full workflow run with the overrides applied to root.

    Cluster mode (reference `veles/genetics/` distributed individuals
    across slaves, SURVEY.md §2.5/§3.5): `-l host:port --optimize N` on
    the coordinator starts a fitness lease queue (task_queue.py) and
    contributes its own compute via a worker thread; `-m host:port
    --optimize N` processes lease individuals, evaluate them locally and
    post results; a worker lost mid-individual misses its lease and the
    coordinator re-issues the work. Shared-secret auth via
    VELES_WEB_TOKEN (optional)."""
    from veles_tpu.config import root
    from veles_tpu.genetics import Population
    from veles_tpu.launcher import Launcher

    tunables = getattr(module, "TUNABLES", None)
    if not tunables:
        raise SystemExit(
            f"--optimize: {args.workflow} defines no TUNABLES list")
    if isinstance(tunables, dict):
        # shorthand form {"root.path": (lo, hi)} (samples/moe.py style)
        from veles_tpu.genetics import Tune
        tunables = [Tune(path, lo, hi)
                    for path, (lo, hi) in tunables.items()]

    def fitness(overrides):
        for path, value in overrides.items():
            root.override(path, value)
        launcher = Launcher(device=device, stats=False)
        launcher.run_module(module)
        dec = getattr(launcher.workflow, "decision", None)
        err = getattr(dec, "best_validation_err", None)
        return float("inf") if err is None else float(err)

    token = os.environ.get("VELES_WEB_TOKEN") or None

    def parse_addr(addr: str, flag: str):
        host, _, port = addr.rpartition(":")
        if not port.isdigit():
            raise SystemExit(
                f"{flag} needs host:port (got {addr!r})")
        return host, int(port)

    if args.master:                       # cluster worker role
        from veles_tpu.task_queue import FitnessQueueWorker
        host, port = parse_addr(args.master, "-m")
        worker = FitnessQueueWorker(host or "127.0.0.1", port,
                                    fitness, token=token)
        try:
            worker.run()
        except PermissionError:
            raise SystemExit(
                "coordinator rejected this worker's token (403): set "
                "the same VELES_WEB_TOKEN on both ends")
        if worker.ended_by == "gave_up" and worker.tasks_done == 0:
            # never reached the coordinator: exiting 0 would report a
            # worker that participated when it evaluated nothing
            raise SystemExit(
                f"no coordinator contact at {args.master} within "
                f"{worker.give_up_s:.0f}s and no individuals evaluated")
        return 0

    srv = None
    if args.listen:                       # cluster coordinator role
        from veles_tpu.task_queue import (FitnessQueueServer,
                                          FitnessQueueWorker)
        host, port = parse_addr(args.listen, "-l")
        if not token and not host.startswith("127."):
            # unauthenticated fitness results on an open port = any
            # network peer can forge the GA's optimization outcome
            # (task ids are predictable). Secure by default: demand the
            # shared secret, or an explicit loopback bind.
            raise SystemExit(
                "--optimize -l on a non-loopback address needs a shared "
                "secret: set VELES_WEB_TOKEN on the coordinator and "
                "every -m worker (or bind -l 127.0.0.1:PORT)")
        srv = FitnessQueueServer(host=host or "0.0.0.0", port=port,
                                 token=token).start()
        # the coordinator contributes compute too (reference master ran
        # individuals itself when idle) — connect to the BOUND address:
        # a non-loopback -l host doesn't listen on 127.0.0.1
        local_host = host if host not in ("", "0.0.0.0") else "127.0.0.1"
        FitnessQueueWorker(local_host, srv.port, fitness,
                           token=token).start_thread()

    pop = Population(tunables, fitness, queue_server=srv)
    try:
        best = pop.evolve(generations=args.optimize)
    finally:
        if srv is not None:
            # drain: answer done=true for a couple of poll cycles so
            # -m workers exit promptly instead of waiting out give_up_s
            srv.stop(drain_s=2.0)
    print(json.dumps({"best_fitness": best.fitness,
                      "best_overrides": best.overrides(tunables)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
