#!/usr/bin/env python
"""Open-loop load generator for the serving tier (ISSUE 15).

Drives POST /predict with POISSON arrivals — the schedule is computed
up front and never waits for completions (open-loop: a server that
falls behind faces the same offered load a real fleet would, instead
of the closed-loop mercy of one-request-per-thread) — and reports
throughput + p50/p99 latency THROUGH THE ONE METRICS REGISTRY
(`veles_loadtest_requests_total{leg,outcome}`,
`veles_loadtest_latency_seconds{leg}`): the record's percentiles are
read BACK from the registry histogram (`metrics.histogram_quantile`),
never from a side-channel list, so every number in the record is
derivable from a /metrics scrape.

Modes:
- default: self-host a synthetic-MLP `InferenceServer` on loopback and
  drive one leg (``--dispatch ring|merge``);
- ``--ab``: the acceptance A/B — drive the SAME poisson schedule
  against the pre-ring merge-per-round core and the
  continuous-batching ring (sharded + AOT), and report the throughput
  speedup and p99 ratio (``--min-speedup`` / ``--max-p99-ratio`` turn
  the SLO into an exit code — the slow-marked test asserts them);
- ``--ramp "R1:S1,R2:S2,..."``: staircase the arrival rate (each phase
  reported separately); ``--duration`` alone is the soak knob;
- ``--url``: drive an EXTERNAL server instead of self-hosting;
- ``--swap``: the hot-swap proof (ISSUE 16) — ONE open-loop window
  across two watcher-applied weight pushes over the mirror bus and one
  HTTP rollback, asserting **zero failed requests** (no errors, no
  sheds) while the serving generation changes live; the record lands
  in SWAP_RECORD.json with every swap event timed and the final
  generation asserted;
- ``--fleet``: the elasticity proof (ISSUE 19) — N ring replicas over
  one shared AOT cache behind the beacon-discovered ``ServingRouter``,
  a single-replica baseline leg, then the ramp against the full fleet
  while one replica is HARD-KILLED (beacon silent) and a fresh one
  joins mid-stream; asserts zero failed (non-shed, non-retried)
  requests and near-linear per-replica throughput. Composes with
  ``--ramp``; the record lands in FLEET_RECORD.json. Clients honor
  Retry-After (one retry, exactly when told — the ``retried``
  outcome);
- ``--smoke``: tiny-budget tier-1 mode (seconds, loopback) asserting
  the record schema and that p50/p99/throughput reached the registry.

The record lands in LOADTEST_RECORD.json (``--swap``:
SWAP_RECORD.json; env ``VELES_LOADTEST_RECORD_PATH``) and the LAST
stdout line is the compact ``LOADTEST {...}`` JSON (one parseable
line, whatever happened).
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

RECORD_ENV = "VELES_LOADTEST_RECORD_PATH"
SCHEMA = "veles-loadtest"
VERSION = 1


def _registry_handles(leg: str):
    """Pre-bound per-leg instruments on the ONE process registry."""
    from veles_tpu.telemetry import metrics as tm
    reg = tm.default_registry()
    req = reg.counter("veles_loadtest_requests_total",
                      "loadtest requests by outcome",
                      labelnames=("leg", "outcome"))
    lat = reg.histogram("veles_loadtest_latency_seconds",
                        "loadtest request latency (client-observed)",
                        labelnames=("leg",),
                        buckets=tm.LATENCY_BUCKETS)
    return {
        "ok": req.labels(leg=leg, outcome="ok"),
        "shed": req.labels(leg=leg, outcome="shed"),
        "error": req.labels(leg=leg, outcome="error"),
        "retried": req.labels(leg=leg, outcome="retried"),
        "latency": lat.labels(leg=leg),
        "lat_family": lat,
    }


class _Client:
    """One persistent keep-alive connection per worker lane
    (http.client, not urllib: urllib's per-request opener + TCP
    connect + server thread spawn measured ~3 ms of pure-python cost —
    it was the generator, not the server, that saturated first)."""

    def __init__(self, host: str, port: int, timeout: float) -> None:
        import http.client
        self._mk = lambda: http.client.HTTPConnection(
            host, port, timeout=timeout)
        self._conn = None
        #: Retry-After seconds from the last 503, or None — the
        #: backpressure contract: a shed tells the client WHEN to
        #: come back, and an honoring client waits exactly that
        self.retry_after: Optional[float] = None

    def post(self, body: bytes) -> int:
        for attempt in (0, 1):      # one reconnect on a dropped conn
            try:
                if self._conn is None:
                    self._conn = self._mk()
                self._conn.request(
                    "POST", "/predict", body,
                    {"Content-Type": "application/json"})
                resp = self._conn.getresponse()
                resp.read()
                ra = resp.getheader("Retry-After")
                try:
                    self.retry_after = float(ra) if ra else None
                except ValueError:
                    self.retry_after = None
                return resp.status
            except OSError:
                try:
                    if self._conn is not None:
                        self._conn.close()
                except OSError:
                    pass
                self._conn = None
                if attempt:
                    return -1
        return -1

    def close(self) -> None:
        try:
            if self._conn is not None:
                self._conn.close()
        except OSError:
            pass


def drive_leg(url: str, leg: str, rate: float, duration: float,
              rows: int, sample_shape, seed: int = 7,
              workers: int = 64, timeout: float = 30.0,
              warmup: int = 4, max_lag: float = 0.25,
              honor_retry_after: bool = False) -> Dict[str, Any]:
    """One open-loop phase: poisson arrivals at `rate`/s for `duration`
    seconds of `rows`-row requests. Returns the phase summary with the
    percentiles READ BACK from the registry.

    `honor_retry_after`: on a 503 the lane waits the server's
    Retry-After (capped — a lane is not a parking lot) and retries
    ONCE; a retry that lands counts as the distinct `retried` outcome,
    never as `ok` (the first-try latency story stays honest) and never
    hammers (exactly one retry, exactly when told)."""
    import numpy as np

    from veles_tpu.telemetry import metrics as tm
    from urllib.parse import urlparse
    u = urlparse(url)
    host, port = u.hostname or "127.0.0.1", u.port or 80
    h = _registry_handles(leg)
    body = json.dumps({"inputs": np.zeros(
        (rows,) + tuple(sample_shape), np.float32).tolist()}).encode()
    warm = _Client(host, port, timeout)
    for _ in range(max(0, warmup)):     # outside the measured window
        warm.post(body)
    warm.close()
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / max(rate, 1e-9), size=(
        max(1, int(rate * duration * 1.5)),))
    arrivals = np.cumsum(gaps)
    arrivals = arrivals[arrivals <= duration]
    q: "queue.Queue[Optional[float]]" = queue.Queue()
    t0 = time.perf_counter()
    counts = {"ok": 0, "shed": 0, "error": 0, "retried": 0,
              "missed": 0}
    lock = threading.Lock()

    def worker() -> None:
        cli = _Client(host, port, timeout)
        try:
            while True:
                item = q.get()
                if item is None:
                    return
                # open-loop: sleep to the SCHEDULED arrival. An arrival
                # the lane pool is already > max_lag late for is
                # counted MISSED and never fired — firing it now would
                # turn the generator into a closed retry loop whose
                # offered rate tracks the server, exactly what
                # open-loop exists to avoid (misses are reported, the
                # no-silent-caps rule).
                delay = item - (time.perf_counter() - t0)
                if delay > 0:
                    time.sleep(delay)
                elif -delay > max_lag:
                    with lock:
                        counts["missed"] += 1
                    continue
                ts = time.perf_counter()
                status = cli.post(body)
                dt = time.perf_counter() - ts
                if status == 200:
                    outcome = "ok"
                elif status == 503 and honor_retry_after:
                    # wait exactly as told (capped), retry exactly once
                    time.sleep(min(cli.retry_after or 1.0, 2.0))
                    status = cli.post(body)
                    outcome = ("retried" if status == 200
                               else "shed" if status == 503
                               else "error")
                elif status == 503:
                    outcome = "shed"
                else:
                    outcome = "error"
                h[outcome].inc()
                if outcome == "ok":
                    h["latency"].observe(dt)
                with lock:
                    counts[outcome] += 1
        finally:
            cli.close()

    n_workers = max(4, min(int(workers), 256))
    threads = [threading.Thread(target=worker, daemon=True,
                                name=f"loadtest-{leg}-{i}")
               for i in range(n_workers)]
    for t in threads:
        t.start()
    for a in arrivals:
        q.put(float(a))
    for _ in threads:
        q.put(None)
    for t in threads:
        t.join(timeout=duration + timeout + 10)
    wall = time.perf_counter() - t0
    total = sum(counts.values()) - counts["missed"]
    # percentiles read BACK from the one registry — the record is
    # always derivable from a /metrics scrape
    p50 = tm.histogram_quantile(h["lat_family"], 0.50, leg=leg)
    p99 = tm.histogram_quantile(h["lat_family"], 0.99, leg=leg)
    return {
        "leg": leg,
        "rate_offered": rate,
        "duration_s": round(wall, 3),
        "requests": total,
        "ok": counts["ok"],
        "shed": counts["shed"],
        "errors": counts["error"],
        "retried": counts["retried"],
        "missed": counts["missed"],
        "rows_per_request": rows,
        "throughput_rps": round(
            (counts["ok"] + counts["retried"]) / wall, 2),
        "throughput_rows_s": round(
            (counts["ok"] + counts["retried"]) * rows / wall, 1),
        "p50_s": p50,
        "p99_s": p99,
    }


def _build_workflow(width: int, sample: int, n_classes: int,
                    depth: int = 1):
    """Self-hosted workload: a depth x width tanh MLP classifier.
    Deep-and-narrow by default for the A/B — compute per row scales
    with depth x width^2 while the JSON/HTTP cost per row scales with
    `sample`, so the measured ratio reflects the serving cores, not
    the wire codec."""
    from veles_tpu import prng
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    prng.seed_all(23)
    loader = SyntheticClassifierLoader(
        n_classes=n_classes, sample_shape=(sample,), n_validation=32,
        n_train=64, minibatch_size=32, noise=0.3)
    layers: List[Dict[str, Any]] = [
        {"type": "all2all_tanh", "output_sample_shape": width,
         "weights_stddev": 0.05} for _ in range(max(1, depth))]
    layers.append({"type": "softmax", "output_sample_shape": n_classes,
                   "weights_stddev": 0.05})
    wf = StandardWorkflow(
        layers=layers,
        loader=loader, loss="softmax", n_classes=n_classes,
        decision_config={"max_epochs": 1, "fail_iterations": 10},
        gd_config={"learning_rate": 0.1}, name="LoadtestWF")
    wf.initialize(device=None)
    return wf


def _serve(wf, dispatch: str, batch: int, ring: Optional[int],
           quantize: str, queue_limit: int):
    from veles_tpu.serving import InferenceServer
    return InferenceServer(
        wf, max_batch=batch, queue_limit=queue_limit,
        dispatch=dispatch, ring_slots=ring, quantize=quantize).start()


def _run_swap(args, record: Dict[str, Any]) -> bool:
    """The hot-swap proof (ISSUE 16): self-host the ring server, point
    a WeightWatcher at a DirMirror, and drive ONE open-loop poisson
    window while a "trainer" thread pushes two perturbed same-geometry
    snapshots over the mirror bus and then POSTs /rollback — asserting
    ZERO failed requests (no errors, no sheds) across all three
    generation changes, >= 2 watcher-applied swaps + 1 rollback, and
    that the final live generation is the rolled-back-to digest. Every
    event is timed into the record; p50/p99 come from the registry like
    every other leg."""
    import tempfile

    import numpy as np

    from veles_tpu.resilience.mirror import DirMirror
    from veles_tpu.serving_watch import WeightWatcher
    from veles_tpu.snapshotter import Snapshotter

    wf = _build_workflow(args.width, args.sample, 4, depth=args.depth)
    srv = _serve(wf, "ring", args.batch, args.ring, args.quantize,
                 args.queue_limit)
    mirror = DirMirror(tempfile.mkdtemp(prefix="veles_swap_mirror_"))
    watcher = WeightWatcher(srv, mirror, prefix="swap",
                            poll_s=args.swap_poll)
    snap_dir = tempfile.mkdtemp(prefix="veles_swap_snaps_")
    url = f"http://127.0.0.1:{srv.port}"
    events: List[Dict[str, Any]] = []

    def _push(tag: str) -> str:
        # the "trainer": nudge every parameter (same geometry, finite,
        # self-consistent — the server's equivalence probe compares the
        # candidate against ITS OWN f32 forward) and publish a
        # digest-addressed snapshot to the mirror bus
        for u in wf.forwards:
            for a in u.param_arrays().values():
                a.mem = np.asarray(a.mem) * np.float32(1.01)
        snap = Snapshotter(workflow=wf, prefix="swap",
                           directory=snap_dir)
        snap.suffix = tag           # distinct, digest-addressed names
        path = snap.export()
        mirror.push(path)
        with open(path + ".sha256") as f:
            return f.read().split()[0]

    def _await_digest(digest: str, timeout: float) -> Optional[float]:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout:
            if srv.generation()["digest"] == digest:
                return round(time.perf_counter() - t0, 3)
            time.sleep(0.02)
        return None

    def _orchestrate(t_start: float, duration: float) -> None:
        # sequential by construction: each push WAITS for its watcher
        # application before the next event fires, so the generation
        # sequence under load is deterministic: boot -> gen1 -> gen2
        # -> rollback(gen1)
        apply_wait = max(5.0, 10.0 * args.swap_poll)
        plan = [(0.20, "push", "gen1"), (0.45, "push", "gen2"),
                (0.70, "rollback", "")]
        for frac, kind, tag in plan:
            delay = t_start + frac * duration - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            ev: Dict[str, Any] = {
                "kind": kind, "tag": tag or None,
                "at_s": round(time.perf_counter() - t_start, 3)}
            try:
                if kind == "push":
                    digest = _push(tag)
                    ev["digest"] = digest
                    ev["applied_after_s"] = _await_digest(
                        digest, apply_wait)
                else:
                    req = urllib.request.Request(
                        url + "/rollback", data=b"", method="POST")
                    with urllib.request.urlopen(req, timeout=15) as r:
                        ev["response"] = json.loads(r.read())
            except Exception as e:  # noqa: BLE001 — a failed event is
                # recorded and judged by the final assertions, never
                # allowed to kill the drive window
                ev["error"] = f"{type(e).__name__}: {e!s:.200}"
            events.append(ev)

    try:
        watcher.start()
        boot = srv.generation()["digest"]
        t_start = time.perf_counter()
        orch = threading.Thread(target=_orchestrate, daemon=True,
                                args=(t_start, args.duration),
                                name="swap-orchestrator")
        orch.start()
        leg = drive_leg(url, "swap", args.rate, args.duration,
                        args.rows, (args.sample,), seed=args.seed,
                        workers=args.workers)
        orch.join(timeout=60)
        final_gen = srv.generation()
        health = srv.health()
        mi = srv.model_info()
        leg["server"] = {k: mi.get(k)
                        for k in ("dispatch", "ring_slots", "sharded",
                                  "quantize", "aot")}
        leg["health"] = {k: health.get(k)
                         for k in ("n_dispatches", "n_rejected",
                                   "round_latency_s")}
        record["legs"]["swap"] = leg
        watcher_status = watcher.status()
    finally:
        watcher.stop()
        srv.stop(drain_s=2)

    pushes = [e for e in events if e["kind"] == "push"]
    applied = [e for e in pushes
               if e.get("applied_after_s") is not None]
    rollbacks = [e for e in events
                 if e["kind"] == "rollback" and "response" in e]
    expected_final = pushes[0].get("digest") if pushes else None
    zero_failed = leg["errors"] == 0 and leg["shed"] == 0
    ok = (zero_failed and len(applied) >= 2 and len(rollbacks) >= 1
          and expected_final is not None
          and final_gen["digest"] == expected_final)
    record["swap"] = {
        "events": events,
        "boot_digest": boot,
        "final_generation": final_gen,
        "expected_final_digest": expected_final,
        "swaps_applied": health["swaps"]["applied"],
        "swaps_refused": health["swaps"]["refused"],
        "watcher": watcher_status,
        "zero_failed_requests": zero_failed,
        "pass": ok,
    }
    return ok


def _run_fleet(args, record: Dict[str, Any]) -> bool:
    """The elasticity proof (ISSUE 19): self-host N ring replicas over
    ONE workflow (shared AOT cache: replicas 2..N start with zero
    compiles) behind a beacon-discovered ServingRouter, measure a
    single-replica baseline leg THROUGH the router, then drive the
    ramp staircase against the full fleet while an orchestrator
    HARD-KILLS one replica (server down, beacon silent — the router
    must degrade via retry + circuit + TTL eviction) and JOINS a fresh
    replica mid-stream. Gates: zero failed (non-shed, non-retried)
    requests across every fleet leg, and fleet throughput per nominal
    replica >= `--min-replica-ratio` x the baseline."""
    import tempfile

    from veles_tpu.resilience.mirror import DirMirror
    from veles_tpu.serving import InferenceServer
    from veles_tpu.serving_router import (ReplicaBeacon, RouterCore,
                                          ServingRouter)

    wf = _build_workflow(args.width, args.sample, 4, depth=args.depth)
    mirror = DirMirror(tempfile.mkdtemp(prefix="veles_fleet_mirror_"))
    n = max(1, args.replicas)
    replicas: Dict[str, Any] = {}     # rid -> (server, beacon)
    events: List[Dict[str, Any]] = []

    def _spawn(rid: str) -> Dict[str, Any]:
        t0 = time.perf_counter()
        srv = InferenceServer(
            wf, max_batch=args.batch, queue_limit=args.queue_limit,
            dispatch="ring", ring_slots=args.ring,
            quantize=args.quantize, replica=rid).start()
        beacon = ReplicaBeacon(
            mirror, rid, f"http://127.0.0.1:{srv.port}",
            health=srv.health, interval_s=0.3).start()
        replicas[rid] = (srv, beacon)
        return {"rid": rid, "port": srv.port,
                "aot": srv.model_info().get("aot"),
                "start_s": round(time.perf_counter() - t0, 3)}

    def _await_routable(router, want: int, timeout: float = 15.0
                        ) -> int:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout:
            if router.health()["routable"] >= want:
                break
            time.sleep(0.05)
        return router.health()["routable"]

    spawns = [_spawn("r0")]
    # short TTL so the killed replica's eviction lands INSIDE the
    # window (production keeps the generous default; the proof needs
    # to witness the sweep, not wait 20s for it)
    router = ServingRouter(mirror, poll_s=0.3,
                           core=RouterCore(beacon_ttl_s=3.0),
                           backoff_base=0.02,
                           backoff_cap=0.1).start()
    url = f"http://127.0.0.1:{router.port}"
    phases = _phases(args)
    if not args.ramp:
        # no explicit staircase: offer the fleet N x the baseline rate
        # (the near-linear claim needs a load only N replicas can take)
        phases = [{"rate": args.rate * n, "duration": args.duration}]
    total_ramp = sum(p["duration"] for p in phases)

    def _orchestrate(t_start: float) -> None:
        # kill at ~40% of the ramp, join at ~65% — both mid-phase so
        # the staircase legs straddle the membership changes
        plan = [(0.40, "kill", "r1"), (0.65, "join", f"r{n}")]
        for frac, kind, rid in plan:
            if kind == "kill" and rid not in replicas:
                continue          # single-replica smoke: nothing to kill
            delay = t_start + frac * total_ramp - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            ev: Dict[str, Any] = {
                "kind": kind, "rid": rid,
                "at_s": round(time.perf_counter() - t_start, 3)}
            try:
                if kind == "kill":
                    srv, beacon = replicas.pop(rid)
                    beacon.silence()    # crash: no 'gone' goodbye
                    srv.stop(drain_s=0)
                else:
                    ev.update(_spawn(rid))
            except Exception as e:  # noqa: BLE001 — a failed chaos
                # event is recorded and judged, never kills the window
                ev["error"] = f"{type(e).__name__}: {e!s:.200}"
            events.append(ev)

    try:
        got = _await_routable(router, 1)
        if got < 1:
            raise RuntimeError("router never discovered r0")
        base = drive_leg(url, "fleet_baseline", args.rate,
                         args.duration, args.rows, (args.sample,),
                         seed=args.seed, workers=args.workers,
                         honor_retry_after=True)
        record["legs"]["fleet_baseline"] = base
        for i in range(1, n):
            spawns.append(_spawn(f"r{i}"))
        _await_routable(router, n)
        t_start = time.perf_counter()
        orch = threading.Thread(target=_orchestrate, daemon=True,
                                args=(t_start,),
                                name="fleet-orchestrator")
        orch.start()
        fleet_legs = []
        for i, ph in enumerate(phases):
            leg = drive_leg(url, f"fleet_ph{i}", ph["rate"],
                            ph["duration"], args.rows, (args.sample,),
                            seed=args.seed + i, workers=args.workers,
                            honor_retry_after=True)
            record["legs"][leg["leg"]] = leg
            fleet_legs.append(leg)
        orch.join(timeout=30)
        fleet_view = router.fleet()
        # per-replica dispatch outcomes from the router's own labeled
        # registry family — the record derives from a /metrics scrape
        from veles_tpu.telemetry import metrics as tm
        fam = tm.default_registry().counter(
            "veles_router_dispatch_total")
        dispatches: Dict[str, Dict[str, float]] = {}
        for labels, child in sorted(getattr(fam, "_children",
                                            {}).items()):
            d = dict(zip(fam.labelnames, labels))
            dispatches.setdefault(d.get("replica", "?"), {})[
                d.get("outcome", "?")] = child.value
    finally:
        router.stop()
        for srv, beacon in list(replicas.values()):
            beacon.stop()
            srv.stop(drain_s=1)

    served = sum(lg["ok"] + lg["retried"] for lg in fleet_legs)
    wall = sum(lg["duration_s"] for lg in fleet_legs)
    errors = sum(lg["errors"] for lg in fleet_legs)
    fleet_rps = served / wall if wall else 0.0
    per_replica = fleet_rps / n
    ratio = (per_replica / base["throughput_rps"]
             if base["throughput_rps"] else 0.0)
    zero_failed = errors == 0 and base["errors"] == 0
    killed = [e for e in events if e["kind"] == "kill"
              and "error" not in e]
    joined = [e for e in events if e["kind"] == "join"
              and "error" not in e]
    ok = (zero_failed and ratio >= args.min_replica_ratio
          and (n < 2 or len(killed) >= 1) and len(joined) >= 1)
    record["fleet"] = {
        "replicas": n,
        "spawns": spawns,
        "events": events,
        "baseline_rps": base["throughput_rps"],
        "fleet_rps": round(fleet_rps, 2),
        "per_replica_rps": round(per_replica, 2),
        "replica_ratio": round(ratio, 3),
        "min_replica_ratio": args.min_replica_ratio,
        "dispatch_by_replica": dispatches,
        "final_fleet": fleet_view,
        "zero_failed_requests": zero_failed,
        "pass": ok,
    }
    return ok


def _phases(args) -> List[Dict[str, float]]:
    if args.ramp:
        out = []
        for part in args.ramp.split(","):
            r, _, s = part.partition(":")
            out.append({"rate": float(r), "duration": float(s or 1.0)})
        return out
    return [{"rate": args.rate, "duration": args.duration}]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", default="",
                    help="drive an external server (skip self-hosting)")
    ap.add_argument("--ab", action="store_true",
                    help="A/B the ring vs the pre-ring merge core on "
                         "the same poisson schedule")
    ap.add_argument("--swap", action="store_true",
                    help="hot-swap proof: drive one window across two "
                         "watcher-applied weight pushes + one rollback "
                         "and assert zero failed requests (record "
                         "defaults to SWAP_RECORD.json)")
    ap.add_argument("--swap-poll", type=float, default=0.3,
                    help="--swap: watcher poll interval, seconds "
                         "(tight so the proof fits one short window; "
                         "production default is 10s)")
    ap.add_argument("--fleet", action="store_true",
                    help="elasticity proof: N beacon-discovered "
                         "replicas behind the ServingRouter, baseline "
                         "leg then the ramp with a hard replica kill + "
                         "a join mid-stream; asserts zero failed "
                         "(non-shed) requests and near-linear "
                         "per-replica throughput (record defaults to "
                         "FLEET_RECORD.json)")
    ap.add_argument("--replicas", type=int, default=3,
                    help="--fleet: replica count (acceptance runs >= 3)")
    ap.add_argument("--min-replica-ratio", type=float, default=0.8,
                    help="--fleet SLO: fleet rps / replicas must reach "
                         "this multiple of the single-replica baseline")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-budget tier-1 mode (loopback, seconds)")
    ap.add_argument("--rate", type=float, default=400.0,
                    help="poisson arrival rate, requests/s")
    ap.add_argument("--duration", type=float, default=8.0,
                    help="measured window per leg, seconds (the soak "
                         "knob)")
    ap.add_argument("--ramp", default="",
                    help="staircase phases 'RATE:SECS,RATE:SECS,...' "
                         "(overrides --rate/--duration)")
    ap.add_argument("--rows", type=int, default=16,
                    help="rows per request")
    ap.add_argument("--batch", type=int, default=64,
                    help="server max_batch (= default ring size)")
    ap.add_argument("--ring", type=int, default=None,
                    help="ring_slots override for the ring leg")
    ap.add_argument("--width", type=int, default=128,
                    help="self-hosted MLP hidden width")
    ap.add_argument("--depth", type=int, default=1,
                    help="self-hosted MLP hidden-layer count (deep + "
                         "narrow keeps the wire codec off the measured "
                         "path)")
    ap.add_argument("--sample", type=int, default=64,
                    help="self-hosted sample feature count")
    ap.add_argument("--queue-limit", type=int, default=256,
                    help="server admission bound")
    ap.add_argument("--dispatch", default="ring",
                    choices=("ring", "merge"),
                    help="single-leg mode: which core to drive")
    ap.add_argument("--quantize", default="f32",
                    choices=("f32", "bf16", "int8"))
    ap.add_argument("--workers", type=int, default=64,
                    help="client thread pool (open-loop firing lanes)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="drive each leg this many times and report the "
                         "BEST run (the autotune `_time_variant` "
                         "convention: a loaded box adds noise, never "
                         "speed — every run still lands in the record "
                         "under its own leg label, no silent caps). "
                         "Non-ramp modes only")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="--ab SLO: exit 1 unless ring throughput >= "
                         "this multiple of merge throughput")
    ap.add_argument("--max-p99-ratio", type=float, default=None,
                    help="--ab SLO: exit 1 unless ring p99 <= this "
                         "multiple of merge p99")
    ap.add_argument("--record", default="",
                    help="record path (default LOADTEST_RECORD.json, "
                         f"env {RECORD_ENV})")
    args = ap.parse_args(argv)
    if args.ab and (args.ramp or args.url):
        # --ab drives its own two-leg schedule; under --ramp/--url the
        # legs would land under other keys and the SLO gates would
        # pass VACUOUSLY — reject instead (the latency-gate rule)
        ap.error("--ab drives the merge/ring pair on one fixed "
                 "schedule: it conflicts with --ramp and --url")
    if args.swap and (args.ab or args.ramp or args.url):
        # --swap self-hosts its own watcher + mirror + rollback plan;
        # mixing schedules would make the zero-failed assertion cover
        # some other leg's traffic
        ap.error("--swap drives its own single-window swap plan: it "
                 "conflicts with --ab, --ramp and --url")
    if args.fleet and (args.ab or args.swap or args.url):
        # --fleet self-hosts the router + replica fleet (it composes
        # with --ramp: the staircase is the fleet's drive schedule)
        ap.error("--fleet self-hosts the routed fleet: it conflicts "
                 "with --ab, --swap and --url")
    if args.smoke:
        # tiny budget: the tier-1 assertion is the record schema + the
        # registry read-back, not a measured claim
        args.rate = min(args.rate, 60.0)
        args.duration = min(args.duration, 1.5)
        args.width = min(args.width, 32)
        args.sample = min(args.sample, 16)
        args.rows = min(args.rows, 4)
        args.batch = min(args.batch, 16)
        args.workers = min(args.workers, 16)
        args.swap_poll = min(args.swap_poll, 0.15)
        if args.swap:
            # the three swap events need room inside the window
            args.duration = max(args.duration, 4.0)
        if args.fleet:
            # the kill + join need room; 2 replicas keep it tiny
            args.replicas = min(args.replicas, 2)
            args.duration = max(args.duration, 3.0)

    record: Dict[str, Any] = {
        "schema": SCHEMA, "version": VERSION,
        "mode": ("ab" if args.ab else
                 "swap" if args.swap else
                 "fleet" if args.fleet else
                 "smoke" if args.smoke else
                 "ramp" if args.ramp else "single"),
        "workload": {"rows": args.rows, "batch": args.batch,
                     "ring": args.ring, "width": args.width,
                     "depth": args.depth, "sample": args.sample,
                     "rate": args.rate, "duration": args.duration,
                     "queue_limit": args.queue_limit,
                     "workers": args.workers, "seed": args.seed},
        "legs": {},
    }
    status = "ok"
    try:
        if args.swap:
            if not _run_swap(args, record):
                status = "swap_failed"
        elif args.fleet:
            if not _run_fleet(args, record):
                status = "fleet_failed"
        elif args.url:
            shape = None  # external server: /info tells us the shape
            with urllib.request.urlopen(args.url + "/info",
                                        timeout=10) as r:
                shape = json.loads(r.read())["input_shape"]
            for i, ph in enumerate(_phases(args)):
                leg = args.dispatch if not args.ramp else \
                    f"{args.dispatch}_ph{i}"
                record["legs"][leg] = drive_leg(
                    args.url, leg, ph["rate"], ph["duration"],
                    args.rows, shape, seed=args.seed,
                    workers=args.workers)
        else:
            wf = _build_workflow(args.width, args.sample, 4,
                                 depth=args.depth)
            shape = (args.sample,)
            legs = (("merge", "ring") if args.ab else (args.dispatch,))
            for legname in legs:
                srv = _serve(wf, legname, args.batch,
                             args.ring if legname == "ring" else None,
                             args.quantize if legname == "ring"
                             else "f32",
                             args.queue_limit)
                try:
                    url = f"http://127.0.0.1:{srv.port}"
                    mi = srv.model_info()
                    server_info = {
                        k: mi.get(k)
                        for k in ("dispatch", "ring_slots",
                                  "sharded", "quantize", "aot")}
                    if args.ramp:
                        runs = [
                            drive_leg(url, f"{legname}_ph{i}",
                                      ph["rate"], ph["duration"],
                                      args.rows, shape, seed=args.seed,
                                      workers=args.workers)
                            for i, ph in enumerate(_phases(args))]
                        best = None
                    else:
                        # best-of-repeats (the _time_variant rule): a
                        # loaded box adds noise, never speed — every
                        # run is recorded, the best one IS the leg
                        n_rep = max(1, args.repeats)
                        runs = [
                            drive_leg(
                                url,
                                (legname if n_rep == 1
                                 else f"{legname}_r{r + 1}"),
                                args.rate, args.duration, args.rows,
                                shape, seed=args.seed,
                                workers=args.workers)
                            for r in range(n_rep)]
                        best = max(runs,
                                   key=lambda r: r["throughput_rps"])
                    h = srv.health()
                    for row in runs:
                        row["server"] = server_info
                        row["health"] = {
                            k: h.get(k)
                            for k in ("n_dispatches", "n_rejected",
                                      "round_latency_s")}
                        record["legs"][row["leg"]] = row
                    if best is not None:
                        record["legs"][legname] = best
                finally:
                    srv.stop(drain_s=2)
        if args.ab and "ring" in record["legs"] \
                and "merge" in record["legs"]:
            ring = record["legs"]["ring"]
            merge = record["legs"]["merge"]
            if merge["throughput_rps"] > 0:
                record["speedup"] = round(
                    ring["throughput_rps"] / merge["throughput_rps"], 3)
            if ring.get("p99_s") and merge.get("p99_s"):
                record["p99_ratio"] = round(
                    ring["p99_s"] / merge["p99_s"], 3)
            if args.min_speedup is not None \
                    and record.get("speedup", 0) < args.min_speedup:
                status = "slo_failed"
            if args.max_p99_ratio is not None and (
                    "p99_ratio" not in record
                    or record["p99_ratio"] > args.max_p99_ratio):
                # a MISSING ratio (a leg with zero ok requests) fails
                # the SLO — a latency gate must never pass vacuously
                status = "slo_failed"
    except Exception as e:  # noqa: BLE001 — the compact line must say
        # failed, never vanish (a failure line must still parse)
        status = "failed"
        record["error"] = f"{type(e).__name__}: {e!s:.300}"
    record["status"] = status
    # the registry's own exposition lines ride the record so every
    # number is visibly derivable from a /metrics scrape (labeled
    # children included — snapshot_flat covers unlabeled only)
    try:
        from veles_tpu.telemetry import metrics as tm
        record["registry"] = [
            ln for ln in tm.default_registry().exposition().splitlines()
            if ln.startswith(("veles_loadtest", "veles_serving",
                              "veles_router"))]
    except Exception:  # noqa: BLE001
        pass
    path = args.record or os.environ.get(RECORD_ENV) \
        or ("SWAP_RECORD.json" if args.swap
            else "FLEET_RECORD.json" if args.fleet
            else "LOADTEST_RECORD.json")
    try:
        with open(path, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    except OSError as e:
        print(f"loadtest: record write failed: {e}", file=sys.stderr)
    compact = {"status": status, "mode": record["mode"],
               "record": path,
               "speedup": record.get("speedup"),
               "p99_ratio": record.get("p99_ratio"),
               "swap": ({"pass": record["swap"]["pass"],
                         "applied": record["swap"]["swaps_applied"],
                         "refused": record["swap"]["swaps_refused"]}
                        if "swap" in record else None),
               "fleet": ({"pass": record["fleet"]["pass"],
                          "replicas": record["fleet"]["replicas"],
                          "ratio": record["fleet"]["replica_ratio"],
                          "zero_failed":
                              record["fleet"]["zero_failed_requests"]}
                         if "fleet" in record else None),
               "legs": {k: {"rps": v.get("throughput_rps"),
                            "p50_s": v.get("p50_s"),
                            "p99_s": v.get("p99_s"),
                            "ok": v.get("ok"), "shed": v.get("shed"),
                            "retried": v.get("retried")}
                        for k, v in record["legs"].items()}}
    print("LOADTEST " + json.dumps(compact, sort_keys=True), flush=True)
    return 0 if status == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
