#!/usr/bin/env python
"""Chaos harness: drive a short CPU training job through every fault
plan the resilience layer claims to survive, and print a pass/fail
recovery matrix.

    python tools/chaos.py [--keep] [--only kill,stall,...]
    python tools/chaos.py --cluster [--only kill_h0,coord_loss,...]
    python tools/chaos.py --swap [--only corrupt_mid_push,...]
    python tools/chaos.py --fleet [--only kill_replica,...]

Each single-host scenario runs `python -m veles_tpu --supervise` on a
tiny synthetic-classifier workflow (6 epochs, snapshots on improvement)
with one VELES_FAULT_PLAN entry injected, then checks that the run
finished with the SAME final epoch count as the uninterrupted baseline
— i.e. recovery was automatic and complete. Exit code: 0 when every
scenario recovers, 1 otherwise.

`--cluster` runs the CROSS-HOST matrix instead: N member processes
(`--supervise --cluster` on loopback, host 0 embedding the control
plane) share a durable snapshot mirror; the coordinator's host is the
snapshot writer, the others rejoin from the mirror. Scenarios: SIGKILL
of either host's children (gang restart from the quorum snapshot), an
emptied local snapshot dir (restore-from-mirror), a corrupted mirror
copy (digest fallback), a transient control-plane partition (rejoin),
plus the ELASTIC matrix — coordinator loss (lowest live host-id
re-elects itself through the mirror record and training resumes from
the quorum snapshot, no rollback), re-elected-coordinator loss (a
THIRD coordinator), join-mid-run (admitted at the next generation
bump), a dead host shrinking the membership (run continues), and a
shrink below the --cluster-hosts floor (clean fail-stop, exit 84 with
machine-readable dead_hosts).

`--swap` runs the HOT-SWAP matrix (ISSUE 16) instead: an in-process
ring `InferenceServer` + DirMirror + `WeightWatcher` per scenario,
proving that live weight pushes apply between rounds under traffic
with zero failed requests, that corrupt/truncated/wrong-geometry
snapshots are REFUSED while the prior generation keeps serving, that
POST /rollback flips to the previous device-resident generation (and
pins it against re-application), and that a dead mirror endpoint costs
bounded per-poll retries and nothing else.

`--fleet` runs the SERVING-FLEET matrix (ISSUE 19) instead: per
scenario an in-process replica group (ring `InferenceServer`s + mirror
presence beacons) behind the real `ServingRouter` front door, with a
live client lane counting outcomes through the router. Scenarios: a
replica crashed to beacon silence mid-load (retries absorb the death,
the corpse is TTL-evicted, zero client-visible errors), a replica
joining mid-load (discovered from the bus, receives traffic, no
config push), a slow replica tripping its circuit breaker open and
being readmitted through the half-open probe once it recovers, and an
unreachable beacon bus (the registry coasts on last-known state —
nothing is amputated — and discovery resumes on restore).

This is the operational twin of tests/test_supervisor.py +
tests/test_cluster.py (+ tests/test_serving_swap.py for --swap,
tests/test_serving_router.py for --fleet): CI asserts a fast subset;
this prints the whole matrix for a human (and is the thing to run
after touching supervisor/cluster/mirror/snapshotter/fault/serving
code).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKFLOW_SRC = '''
from veles_tpu.config import root
from veles_tpu import prng
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.znicz.standard_workflow import StandardWorkflow

root.chaoswf.snapshot_dir = "."

MAX_EPOCHS = 6

def create_workflow():
    prng.seed_all(77)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(10,), n_validation=40, n_train=200,
        minibatch_size=40, noise=0.4)
    return StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.05}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": MAX_EPOCHS,
                         "fail_iterations": 100000},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        snapshot_config={"directory": root.chaoswf.snapshot_dir,
                         "prefix": "chaoswf"},
        name="ChaosWF")

def run(load, main):
    wf, restored = load(create_workflow)
    main()
    print("FINAL", wf.decision.epoch_number, flush=True)
'''

#: cluster-matrix workflow: identical to WORKFLOW_SRC but the snapshot
#: writer role is decided by the harness (non-coordinator hosts run
#: with VELES_SNAPSHOT_DRY_RUN=1 and rejoin from the mirror; a host
#: promoted by a re-election drops the pin on respawn)
CLUSTER_WORKFLOW_SRC = WORKFLOW_SRC.replace("chaoswf", "clwf") \
    .replace("ChaosWF", "ClusterWF")

#: cluster matrix: name -> spec dict. `hosts` boot member processes
#: (ids 0..hosts-1) share a loopback control plane + mirror; `floor`
#: (--cluster-hosts, default = hosts) is the MINIMUM live host count.
#: `plans` maps host id -> VELES_FAULT_PLAN. `lost` hosts are expected
#: to vanish (SIGKILL, nonzero rc); every other host must end rc 0
#: with FINAL 6 — unless `expect_stop` names the clean fail-stop exit
#: code every survivor must end with instead. `joiner_delay` starts an
#: extra `--cluster-join` host (id = hosts) that many seconds in.
#: Optional checks: want_restart (failure restarts consumed — or
#: explicitly zero), want_term (a re-election reached this term),
#: want_resume (the election bump resumed from a quorum snapshot, not
#: scratch — the no-rollback proof), want_members (final membership),
#: want_dead (final dead_hosts list).
CLUSTER_SCENARIOS = {
    "baseline": dict(
        hosts=2, blurb="uninterrupted 2-host run completes"),
    "kill_h0": dict(
        hosts=2, plans={0: "kill@epoch=2"}, want_restart=True,
        blurb="writer host's children SIGKILLed -> gang restart from "
              "quorum snapshot"),
    "kill_h1": dict(
        hosts=2, plans={1: "kill@epoch=2"}, want_restart=True,
        blurb="snapshot-less host's children SIGKILLed -> restart, "
              "rejoin from mirror"),
    "stale_dir": dict(
        hosts=2, plans={0: "kill@epoch=2; stale_local_dir@restart=1"},
        want_restart=True,
        blurb="writer's local snapshot dir emptied at respawn -> "
              "restore from mirror"),
    "mirror_corrupt": dict(
        hosts=2, plans={0: "mirror_corrupt@push=2; kill@epoch=3"},
        want_restart=True,
        blurb="corrupted mirror copy refused by digest at restore -> "
              "blacklisted from future votes, fleet still recovers"),
    "partition": dict(
        hosts=2, plans={1: "partition@beat=3"}, want_restart=False,
        blurb="transient control-plane partition (< dead_after) -> "
              "member rejoins, run completes"),
    "coord_loss": dict(
        hosts=3, floor=2, plans={0: "host_loss@epoch=2"}, lost=(0,),
        want_term=2, want_resume=True,
        blurb="coordinator host vanishes -> lowest live host-id "
              "re-elects itself (term 2), training resumes from the "
              "quorum snapshot with no rollback"),
    "reelect_loss": dict(
        hosts=4, floor=2,
        plans={0: "host_loss@epoch=2", 1: "coord_loss@term=2"},
        lost=(0, 1), want_term=3,
        blurb="the RE-ELECTED coordinator vanishes too -> survivors "
              "elect a third coordinator (term 3) and finish"),
    "join_mid_run": dict(
        hosts=2, joiner_delay=2.0, want_members=["0", "1", "2"],
        blurb="a new host joins mid-run (--cluster-join) -> admitted "
              "at the next generation bump, fleet rebuilds over N+1"),
    "shrink_ok": dict(
        hosts=3, floor=2, plans={2: "host_loss@epoch=2"}, lost=(2,),
        want_dead=["2"],
        blurb="a host above the floor vanishes -> membership (and the "
              "quorum denominator) shrinks, run completes on the "
              "survivors"),
    "shrink_below_floor": dict(
        hosts=2, plans={1: "host_loss@epoch=2"}, lost=(1,),
        expect_stop=84, want_dead=["1"],
        blurb="a host loss that would drop the live set below the "
              "--cluster-hosts floor -> clean fail-stop, exit 84 with "
              "machine-readable dead_hosts"),
}


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_member(tmp: str, wf_py: str, mirror: str, port: int,
                  host: int, floor: int, plan, join: bool = False):
    """One member agent process (+ report path). The coordinator's
    host is the snapshot writer; everyone else runs with
    VELES_SNAPSHOT_DRY_RUN=1 (a member promoted after a re-election
    drops the pin on respawn — the writer role follows the control
    plane)."""
    local = os.path.join(tmp, f"h{host}")
    os.makedirs(local, exist_ok=True)
    report = os.path.join(tmp, f"report_{host}.json")
    env = dict(os.environ)
    for var in ("XLA_FLAGS", "VELES_FAULT_STATE", "VELES_FAULT_PLAN",
                "VELES_SNAPSHOT_DRY_RUN"):
        env.pop(var, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if host != 0:
        env["VELES_SNAPSHOT_DRY_RUN"] = "1"
    if plan:
        env["VELES_FAULT_PLAN"] = plan
    cmd = [sys.executable, "-m", "veles_tpu", wf_py, "--no-stats",
           "-v", "--supervise",
           "--cluster", f"127.0.0.1:{port}",
           "--cluster-hosts", str(floor), "--host-id", str(host),
           "--cluster-beat", "0.5", "--cluster-dead-after", "8",
           "--max-restarts", "3",
           "--snapshot-dir", local, "--snapshot-prefix", "clwf",
           "--mirror", mirror, "--supervise-report", report]
    if join or host >= floor:
        # any id outside 0..floor-1 enters through the join path —
        # whether it boots with the fleet (hosts above the floor) or
        # arrives mid-run
        cmd.append("--cluster-join")
    cmd.append(f"root.clwf.snapshot_dir={local}")
    proc = subprocess.Popen(cmd, env=env, cwd=tmp,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    return proc, report


def run_cluster_scenario(name: str, spec: dict, verbose: bool) -> dict:
    tmp = tempfile.mkdtemp(prefix=f"chaos_cluster_{name}_")
    wf_py = os.path.join(tmp, "clwf.py")
    with open(wf_py, "w") as f:
        f.write(CLUSTER_WORKFLOW_SRC)
    mirror = os.path.join(tmp, "mirror")
    port = _free_port()
    n_hosts = spec["hosts"]
    floor = spec.get("floor", n_hosts)
    plans = spec.get("plans", {})
    lost = {str(h) for h in spec.get("lost", ())}
    procs, reports = {}, {}
    t0 = time.time()
    for host in range(n_hosts):
        procs[str(host)], reports[str(host)] = _spawn_member(
            tmp, wf_py, mirror, port, host, floor, plans.get(host))
        if host == 0:
            time.sleep(1.0)     # let the control plane bind first
    if spec.get("joiner_delay"):
        time.sleep(float(spec["joiner_delay"]))
        procs[str(n_hosts)], reports[str(n_hosts)] = _spawn_member(
            tmp, wf_py, mirror, port, n_hosts, floor,
            plans.get(n_hosts), join=True)
    outs, rcs = {}, {}
    deadline = time.time() + 600
    for host, p in procs.items():
        try:
            out, err = p.communicate(
                timeout=max(5.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
        outs[host] = (out, err)
        rcs[host] = p.returncode
    elapsed = time.time() - t0

    def final_epoch(out):
        lines = [ln for ln in out.splitlines() if ln.startswith("FINAL")]
        return int(lines[-1].split()[1]) if lines else None

    finals = {h: final_epoch(o) for h, (o, _) in outs.items()}
    # the authoritative cluster summary lives in the LAST coordinator's
    # report — after re-elections that is not necessarily host 0: pick
    # the cluster block with the highest (term, generation)
    cluster, top_report = {}, None
    for h, path in sorted(reports.items()):
        if not os.path.exists(path):
            continue            # a lost host never writes its report
        with open(path) as f:
            rep = json.load(f)
        c = rep.get("cluster") or {}
        if c and ((c.get("term") or 0, c.get("generation") or 0)
                  >= (cluster.get("term") or 0,
                      cluster.get("generation") or 0)):
            cluster, top_report = c, rep
    survivors = [h for h in procs if h not in lost]
    problems = []
    stop_rc = spec.get("expect_stop")
    if stop_rc:
        for h in survivors:
            if rcs[h] != stop_rc:
                problems.append(f"host {h} rc {rcs[h]} != {stop_rc}")
        if cluster.get("exit_code") != stop_rc:
            problems.append(
                f"cluster exit_code {cluster.get('exit_code')}")
        if (top_report or {}).get("dead_hosts") != spec.get("want_dead"):
            problems.append("report-level dead_hosts missing")
    else:
        for h in survivors:
            if rcs[h] != 0:
                problems.append(f"host {h} rc {rcs[h]} != 0")
            if finals.get(h) != 6:
                problems.append(f"host {h} FINAL {finals.get(h)} != 6")
        if cluster.get("outcome") != "completed":
            problems.append(f"outcome {cluster.get('outcome')!r}")
    for h in lost:
        if rcs.get(h) == 0:
            problems.append(f"lost host {h} exited 0")
    if spec.get("want_restart") is True and not cluster.get("restarts"):
        problems.append("no failure restart consumed")
    if spec.get("want_restart") is False and cluster.get("restarts"):
        problems.append(f"unexpected restarts {cluster.get('restarts')}")
    if spec.get("want_term") and (cluster.get("term") or 0) \
            < spec["want_term"]:
        problems.append(
            f"term {cluster.get('term')} < {spec['want_term']}")
    if spec.get("want_resume"):
        bumps = [g for g in cluster.get("generations", ())
                 if "re-elected" in str(g.get("reason", ""))]
        if not bumps or not bumps[0].get("snapshot"):
            problems.append("election bump did not resume from a "
                            "quorum snapshot (rollback hazard)")
    if spec.get("want_members") is not None \
            and cluster.get("members") != spec["want_members"]:
        problems.append(f"members {cluster.get('members')} != "
                        f"{spec['want_members']}")
    if spec.get("want_dead") is not None \
            and cluster.get("dead_hosts") != spec["want_dead"]:
        problems.append(f"dead_hosts {cluster.get('dead_hosts')} != "
                        f"{spec['want_dead']}")
    ok = not problems
    if verbose and not ok:
        sys.stderr.write(f"--- {name} problems: {problems} ---\n")
        for h, (out, err) in sorted(outs.items()):
            sys.stderr.write(f"--- host {h} rc={rcs[h]} ---\n"
                             + err[-2500:] + "\n")
    return {"tmp": tmp, "ok": ok, "problems": problems,
            "rc": tuple(rcs[h] for h in sorted(rcs, key=int)),
            "final_epoch": max((f for f in finals.values()
                                if f is not None), default=None),
            "generation": cluster.get("generation"),
            "term": cluster.get("term"),
            "restarts": cluster.get("restarts"),
            "dead_hosts": cluster.get("dead_hosts"),
            "elapsed": elapsed}


# -- the hot-swap matrix (ISSUE 16) ------------------------------------------
#
# In-process (no subprocesses): a ring `InferenceServer` + DirMirror +
# `WeightWatcher` per scenario, each proving one leg of the robustness
# contract — ANY swap failure degrades to "keep serving the current
# generation, record the refusal"; serving never restarts, drains or
# recompiles to recover. Timing-sensitive scenarios drive the
# synchronous `watcher.poll_once()` unit; the under-load pair runs the
# real poll thread with a live request lane.

def _swap_build_wf(width: int = 16, sample: int = 8):
    """The loadtest synthetic-MLP builder (same workload family the
    committed SWAP_RECORD.json was measured on)."""
    tools_dir = os.path.dirname(os.path.abspath(__file__))
    for p in (REPO, tools_dir):
        if p not in sys.path:
            sys.path.insert(0, p)
    import loadtest
    return loadtest._build_workflow(width, sample, 4, depth=1)


class _SwapHarness:
    """One scenario's serving stack: ring server + mirror + watcher +
    an optional background request lane counting outcomes."""

    def __init__(self, poll_s: float = 0.2) -> None:
        if REPO not in sys.path:    # run as `python tools/chaos.py`
            sys.path.insert(0, REPO)
        from veles_tpu.resilience.mirror import DirMirror
        from veles_tpu.serving import InferenceServer
        from veles_tpu.serving_watch import WeightWatcher
        self.tmp = tempfile.mkdtemp(prefix="chaos_swap_")
        self.wf = _swap_build_wf()
        self.sample = 8
        self.srv = InferenceServer(
            self.wf, max_batch=16, queue_limit=128, dispatch="ring",
            ring_slots=16).start()
        self.mirror = DirMirror(os.path.join(self.tmp, "mirror"))
        self.watcher = WeightWatcher(self.srv, self.mirror,
                                     prefix="swapwf", poll_s=poll_s)
        self.url = f"http://127.0.0.1:{self.srv.port}"
        self.counts = {"ok": 0, "shed": 0, "error": 0}
        self._load_stop = threading.Event()
        self._load_thread = None

    # -- snapshot pushes ------------------------------------------------------

    def push(self, tag: str, wf=None):
        """Perturb + export + mirror-push one snapshot generation;
        returns (mirror entry name, sidecar digest)."""
        import numpy as np
        from veles_tpu.snapshotter import Snapshotter
        src = wf if wf is not None else self.wf
        for u in src.forwards:
            for a in u.param_arrays().values():
                a.mem = np.asarray(a.mem) * np.float32(1.01)
        snap = Snapshotter(workflow=src, prefix="swapwf",
                           directory=self.tmp)
        snap.suffix = tag
        path = snap.export()
        self.mirror.push(path)
        with open(path + ".sha256") as f:
            return os.path.basename(path), f.read().split()[0]

    # -- request lane ---------------------------------------------------------

    def predict_ok(self) -> bool:
        body = json.dumps({"inputs": [[0.0] * self.sample] * 2}).encode()
        try:
            req = urllib.request.Request(
                self.url + "/predict", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                return r.status == 200
        except OSError:
            return False

    def load_start(self, interval_s: float = 0.01) -> None:
        body = json.dumps({"inputs": [[0.0] * self.sample] * 2}).encode()

        def lane() -> None:
            while not self._load_stop.wait(interval_s):
                try:
                    req = urllib.request.Request(
                        self.url + "/predict", data=body,
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=10) as r:
                        r.read()
                        self.counts["ok" if r.status == 200
                                    else "error"] += 1
                except urllib.error.HTTPError as e:
                    self.counts["shed" if e.code == 503
                                else "error"] += 1
                except OSError:
                    self.counts["error"] += 1

        self._load_stop.clear()
        self._load_thread = threading.Thread(target=lane, daemon=True,
                                             name="chaos-swap-load")
        self._load_thread.start()

    def load_stop(self) -> None:
        self._load_stop.set()
        if self._load_thread is not None:
            self._load_thread.join(timeout=15)

    # -- waits ----------------------------------------------------------------

    def await_digest(self, digest: str, timeout: float = 10.0) -> bool:
        t0 = time.time()
        while time.time() - t0 < timeout:
            if self.srv.generation()["digest"] == digest:
                return True
            time.sleep(0.02)
        return False

    def await_refused(self, n: int, timeout: float = 10.0) -> bool:
        t0 = time.time()
        while time.time() - t0 < timeout:
            if self.watcher.status()["n_refused"] >= n:
                return True
            time.sleep(0.02)
        return False

    def stop(self) -> None:
        self.load_stop()
        self.watcher.stop()
        self.srv.stop(drain_s=1)


def _swap_under_load(h: "_SwapHarness") -> list:
    problems = []
    h.watcher.start()
    h.load_start()
    _, digest = h.push("gen1")
    if not h.await_digest(digest):
        problems.append("push never applied")
    time.sleep(0.3)             # a few rounds ON the new generation
    h.load_stop()
    if h.counts["error"] or h.counts["shed"]:
        problems.append(f"request failures under swap: {h.counts}")
    if h.srv.health()["swaps"]["applied"] < 1:
        problems.append("swap_applied counter did not move")
    return problems


def _swap_corrupt_mid_push(h: "_SwapHarness") -> list:
    problems = []
    _, d1 = h.push("gen1")
    if h.watcher.poll_once() is None or not h.await_digest(d1, 1.0):
        problems.append("gen1 not applied")
    name2, _ = h.push("gen2")
    h.mirror._corrupt(name2)    # mid-push torn copy: bytes != sidecar
    if h.watcher.poll_once() is not None:
        problems.append("corrupt snapshot was APPLIED")
    last = h.srv.health()["swaps"]["last_refusal"] or {}
    if last.get("reason") != "fetch_failed":
        problems.append(f"refusal reason {last.get('reason')!r} != "
                        "fetch_failed")
    if h.srv.generation()["digest"] != d1:
        problems.append("generation moved off gen1")
    if not h.predict_ok():
        problems.append("serving broken after refusal")
    return problems


def _swap_truncated_sidecar(h: "_SwapHarness") -> list:
    problems = []
    _, d1 = h.push("gen1")
    h.watcher.poll_once()
    if h.srv.generation()["digest"] != d1:
        problems.append("gen1 not applied")
    name2, _ = h.push("gen2")
    side = os.path.join(h.mirror.root, name2 + ".sha256")
    with open(side, "w") as f:          # garbage digest text
        f.write("deadbeef  " + name2 + "\n")
    if h.watcher.poll_once() is not None:
        problems.append("garbage-sidecar snapshot was APPLIED")
    if (h.srv.health()["swaps"]["last_refusal"] or {}).get("reason") \
            != "fetch_failed":
        problems.append("garbage sidecar not refused as fetch_failed")
    with open(side, "w") as f:          # truncated-to-empty sidecar:
        pass                            # the entry becomes invisible
    refused_before = h.watcher.status()["n_refused"]
    if h.watcher.poll_once() is not None:
        problems.append("sidecar-less snapshot was APPLIED")
    if h.watcher.status()["n_refused"] != refused_before:
        problems.append("invisible entry was counted as a refusal")
    if h.srv.generation()["digest"] != d1:
        problems.append("generation moved off gen1")
    if not h.predict_ok():
        problems.append("serving broken after sidecar damage")
    return problems


def _swap_wrong_geometry(h: "_SwapHarness") -> list:
    problems = []
    boot = h.srv.generation()["digest"]
    wide = _swap_build_wf(width=24)     # same family, WRONG geometry
    _, d_bad = h.push("wide", wf=wide)
    if h.watcher.poll_once() is not None:
        problems.append("wrong-geometry snapshot was APPLIED")
    if (h.srv.health()["swaps"]["last_refusal"] or {}).get("reason") \
            != "geometry":
        problems.append("not refused as geometry")
    if d_bad[:12] not in "".join(
            h.watcher.status()["refused_digests"]):
        problems.append("poisoned digest not remembered")
    n = h.watcher.status()["n_refused"]
    h.watcher.poll_once()               # remembered: no refusal churn
    if h.watcher.status()["n_refused"] != n:
        problems.append("remembered digest re-refused on next poll")
    if h.srv.generation()["digest"] != boot:
        problems.append("generation moved")
    if not h.predict_ok():
        problems.append("serving broken after geometry refusal")
    return problems


def _swap_rollback_under_load(h: "_SwapHarness") -> list:
    problems = []
    h.watcher.start()
    h.load_start()
    _, d1 = h.push("gen1")
    if not h.await_digest(d1):
        problems.append("gen1 not applied")
    _, d2 = h.push("gen2")
    if not h.await_digest(d2):
        problems.append("gen2 not applied")
    req = urllib.request.Request(h.url + "/rollback", data=b"",
                                 method="POST")
    with urllib.request.urlopen(req, timeout=15) as r:
        resp = json.loads(r.read())
    gen = resp.get("generation", {})
    if gen.get("digest") != d1 or gen.get("source") != "rollback":
        problems.append(f"rollback landed on {gen}")
    time.sleep(1.0)     # several poll intervals: the rolled-back
    if h.srv.generation()["digest"] != d1:   # digest must stay PINNED
        problems.append("watcher re-applied the rolled-back digest")
    h.load_stop()
    if h.counts["error"] or h.counts["shed"]:
        problems.append(f"request failures under rollback: {h.counts}")
    return problems


def _swap_mirror_unreachable(h: "_SwapHarness") -> list:
    from veles_tpu.resilience.mirror import HttpMirror
    problems = []
    boot = h.srv.generation()["digest"]
    # swap the watcher's bus for a dead endpoint with a retry budget
    # scaled to the chaos poll interval (production: 8s under 10s)
    h.watcher._mirror = HttpMirror(
        f"http://127.0.0.1:{_free_port()}", retries=2,
        retry_base=0.02, retry_cap=0.05, retry_total=0.15)
    h.watcher.start()
    time.sleep(1.2)
    st = h.watcher.status()
    if st["n_polls"] < 3:
        problems.append(f"polls stalled past the retry budget: {st}")
    if st["n_applied"] or st["n_refused"]:
        problems.append(f"phantom swap activity: {st}")
    if h.srv.generation()["digest"] != boot:
        problems.append("generation moved with the mirror down")
    if not h.predict_ok():
        problems.append("serving broken while the mirror is down")
    return problems


#: the hot-swap matrix: name -> (scenario fn, blurb)
SWAP_SCENARIOS = {
    "swap_under_load": (
        _swap_under_load,
        "weight push applied between rounds under live traffic, zero "
        "failed requests"),
    "corrupt_mid_push": (
        _swap_corrupt_mid_push,
        "mirror copy corrupted mid-push -> fetch refused by digest, "
        "prior generation keeps serving"),
    "truncated_sidecar": (
        _swap_truncated_sidecar,
        "garbage sidecar -> fetch refusal; truncated-empty sidecar -> "
        "entry invisible, no churn"),
    "wrong_geometry": (
        _swap_wrong_geometry,
        "snapshot with mismatched layer shapes -> geometry refusal, "
        "poisoned digest remembered (no hot-loop)"),
    "rollback_under_load": (
        _swap_rollback_under_load,
        "POST /rollback flips to the previous device-resident "
        "generation under load; watcher honours the pin"),
    "mirror_unreachable": (
        _swap_mirror_unreachable,
        "mirror endpoint dead -> bounded per-poll retries, serving "
        "untouched, no phantom swaps"),
}


def run_swap_scenario(name: str, verbose: bool) -> dict:
    fn, _blurb = SWAP_SCENARIOS[name]
    t0 = time.time()
    h = None
    try:
        h = _SwapHarness()
        problems = fn(h)
    except Exception as e:  # noqa: BLE001 — a crashed scenario is a
        # FAIL row, not a crashed matrix
        problems = [f"{type(e).__name__}: {e!s:.200}"]
    finally:
        tmp = h.tmp if h is not None else None
        swaps = {}
        try:
            if h is not None:
                swaps = h.srv.health().get("swaps", {})
                h.stop()
        except Exception:  # noqa: BLE001
            pass
    ok = not problems
    if verbose and not ok:
        sys.stderr.write(f"--- {name} problems: {problems} ---\n")
    return {"tmp": tmp or tempfile.mkdtemp(prefix="chaos_swap_empty_"),
            "ok": ok, "problems": problems,
            "applied": swaps.get("applied"),
            "refused": swaps.get("refused"),
            "elapsed": time.time() - t0}


# -- the serving-fleet matrix (ISSUE 19) -------------------------------------
#
# In-process: a replica group (real ring `InferenceServer`s and/or a
# controllable stub) publishes presence beacons on a DirMirror bus;
# the real `ServingRouter` discovers them and fronts a background
# client lane. Every scenario's contract is the fleet one: ANY
# replica-level failure degrades to router-side retry / circuit /
# eviction — the client lane must see ZERO errors and zero sheds.

class _StubReplica:
    """Controllable fake replica (the slow-replica scenario): answers
    POST /predict 200 after `delay_s` seconds — adjustable mid-run, so
    one scenario can trip the router's circuit breaker with timeouts
    and then recover to earn readmission."""

    def __init__(self) -> None:
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        from veles_tpu.http_util import check_shared_token
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_POST(self) -> None:  # noqa: N802
                # same endpoint contract as the real replica: token
                # first (trivially open — chaos runs tokenless on
                # loopback), bounded body before reading
                if not check_shared_token(self, None):
                    return
                n = min(int(self.headers.get("Content-Length", "0")),
                        1 << 20)
                self.rfile.read(n)
                time.sleep(outer.delay_s)
                body = json.dumps({"outputs": [], "stub": True}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args) -> None:
                pass

        class Quiet(ThreadingHTTPServer):
            def handle_error(self, request, client_address) -> None:
                pass        # router timed out and hung up mid-delay

        self.delay_s = 0.0
        self._httpd = Quiet(("127.0.0.1", 0), Handler)
        self.port = self._httpd.server_address[1]
        threading.Thread(
            target=lambda: self._httpd.serve_forever(poll_interval=0.05),
            daemon=True, name="chaos-stub").start()

    def stop(self, drain_s: float = 0) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


class _FleetHarness:
    """One fleet scenario's stack: DirMirror beacon bus + replicas +
    the ServingRouter front door + a background client lane counting
    outcomes THROUGH the router."""

    def __init__(self) -> None:
        if REPO not in sys.path:    # run as `python tools/chaos.py`
            sys.path.insert(0, REPO)
        from veles_tpu.resilience.mirror import DirMirror
        self.tmp = tempfile.mkdtemp(prefix="chaos_fleet_")
        self.mirror = DirMirror(os.path.join(self.tmp, "mirror"))
        self.wf = _swap_build_wf()
        self.sample = 8
        self.reps = {}              # rid -> {"srv", "beacon"}
        self.router = None
        self.url = None
        self.counts = {"ok": 0, "shed": 0, "error": 0}
        self._load_stop = threading.Event()
        self._load_thread = None

    # -- fleet membership -----------------------------------------------------

    def spawn(self, rid: str, capacity=None) -> None:
        """One real ring replica + its presence beacon. `capacity`
        overrides the /healthz-derived hint (to level the field
        against a stub in the circuit scenario)."""
        from veles_tpu.serving import InferenceServer
        from veles_tpu.serving_router import ReplicaBeacon
        srv = InferenceServer(self.wf, max_batch=16, queue_limit=64,
                              dispatch="ring", ring_slots=16,
                              replica=rid).start()
        beacon = ReplicaBeacon(
            self.mirror, rid, f"http://127.0.0.1:{srv.port}",
            health=srv.health, capacity=capacity,
            interval_s=0.3).start()
        self.reps[rid] = {"srv": srv, "beacon": beacon}

    def spawn_stub(self, rid: str, capacity: float) -> _StubReplica:
        from veles_tpu.serving_router import ReplicaBeacon
        stub = _StubReplica()
        beacon = ReplicaBeacon(self.mirror, rid,
                               f"http://127.0.0.1:{stub.port}",
                               capacity=capacity, interval_s=0.3).start()
        self.reps[rid] = {"srv": stub, "beacon": beacon}
        return stub

    def kill(self, rid: str) -> None:
        """Crash `rid`: the beacon goes SILENT (no 'gone' goodbye a
        dead process could not send) and the server hard-stops."""
        rep = self.reps.pop(rid)
        rep["beacon"].silence()
        rep["srv"].stop(drain_s=0)

    def start_router(self, ttl_s: float = 3.0, open_s: float = 1.5,
                     dispatch_timeout_s: float = 5.0,
                     hedge: bool = True) -> None:
        from veles_tpu.serving_router import RouterCore, ServingRouter
        self.router = ServingRouter(
            self.mirror, poll_s=0.2,
            core=RouterCore(open_s=open_s, beacon_ttl_s=ttl_s),
            dispatch_timeout_s=dispatch_timeout_s,
            backoff_base=0.02, backoff_cap=0.1, hedge=hedge).start()
        self.url = f"http://127.0.0.1:{self.router.port}"

    # -- router views ---------------------------------------------------------

    def await_routable(self, n: int, timeout: float = 15.0) -> bool:
        t0 = time.time()
        while time.time() - t0 < timeout:
            if self.router.health()["routable"] == n:
                return True
            time.sleep(0.05)
        return False

    def circuit(self, rid: str):
        for r in self.router.fleet()["replicas"]:
            if r["rid"] == rid:
                return r["circuit"]
        return None

    def await_circuit(self, rid: str, state: str,
                      timeout: float = 10.0) -> bool:
        t0 = time.time()
        while time.time() - t0 < timeout:
            if self.circuit(rid) == state:
                return True
            time.sleep(0.02)
        return False

    def dispatch_n(self, rid: str, outcome: str = "ok") -> float:
        """Router-side per-replica dispatch counter (the telemetry
        registry is process-global, so compare DELTAS)."""
        child = self.router._f_dispatch._children.get((rid, outcome))
        return child.value if child is not None else 0.0

    # -- client lane ----------------------------------------------------------

    def load_start(self, interval_s: float = 0.02) -> None:
        body = json.dumps({"inputs": [[0.0] * self.sample] * 2}).encode()
        # capture the router URL BEFORE the lane thread exists (the
        # lane never reads harness state that the main thread mutates)
        url = self.url + "/predict"

        def lane() -> None:
            while not self._load_stop.wait(interval_s):
                try:
                    req = urllib.request.Request(
                        url, data=body,
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=20) as r:
                        r.read()
                        self.counts["ok" if r.status == 200
                                    else "error"] += 1
                except urllib.error.HTTPError as e:
                    self.counts["shed" if e.code == 503
                                else "error"] += 1
                except OSError:
                    self.counts["error"] += 1

        self._load_stop.clear()
        self._load_thread = threading.Thread(target=lane, daemon=True,
                                             name="chaos-fleet-load")
        self._load_thread.start()

    def load_stop(self) -> None:
        self._load_stop.set()
        if self._load_thread is not None:
            self._load_thread.join(timeout=30)

    def stop(self) -> None:
        self.load_stop()
        if self.router is not None:
            self.router.stop()
        for rep in self.reps.values():
            try:
                rep["beacon"].stop()
                rep["srv"].stop(drain_s=1)
            except Exception:  # noqa: BLE001
                pass


def _fleet_kill_replica(h: "_FleetHarness") -> list:
    problems = []
    h.spawn("r0")
    h.spawn("r1")
    h.start_router(ttl_s=2.0)
    if not h.await_routable(2):
        problems.append("fleet never formed")
    h.load_start()
    time.sleep(0.6)             # traffic on both replicas
    h.kill("r1")                # crash: silence, not a goodbye
    time.sleep(3.0)             # > TTL + poll: eviction must land
    h.load_stop()
    if h.counts["error"] or h.counts["shed"]:
        problems.append(f"client-visible failures: {h.counts}")
    if not h.counts["ok"]:
        problems.append("no traffic served")
    snap = h.router.fleet()
    if any(r["rid"] == "r1" for r in snap["replicas"]):
        problems.append("dead replica never TTL-evicted")
    if snap["routable"] != 1:
        problems.append(f"routable {snap['routable']} != 1")
    return problems


def _fleet_join_mid_load(h: "_FleetHarness") -> list:
    problems = []
    h.spawn("r0")
    h.start_router()
    if not h.await_routable(1):
        problems.append("first replica never registered")
    joined_before = h.dispatch_n("r1")
    h.load_start()
    time.sleep(0.5)
    h.spawn("r1")               # no config push: beacon is the join
    if not h.await_routable(2):
        problems.append("joined replica never discovered")
    time.sleep(1.5)             # traffic must spread onto it
    h.load_stop()
    if h.counts["error"] or h.counts["shed"]:
        problems.append(f"client-visible failures: {h.counts}")
    if h.dispatch_n("r1") <= joined_before:
        problems.append("joined replica received no traffic")
    return problems


def _fleet_slow_circuit(h: "_FleetHarness") -> list:
    problems = []
    h.spawn("r0", capacity=4.0)     # level weights vs the stub
    stub = h.spawn_stub("slow", capacity=4.0)
    h.start_router(open_s=1.5, dispatch_timeout_s=0.4, hedge=False)
    if not h.await_routable(2):
        problems.append("fleet never formed")
    ok_before = h.dispatch_n("slow")
    stub.delay_s = 2.0              # >> dispatch timeout: every
    h.load_start(0.05)              # dispatch there now times out
    if not h.await_circuit("slow", "open"):
        problems.append("slow replica never tripped its circuit")
    stub.delay_s = 0.0              # recovered: the half-open probe
    if not h.await_circuit("slow", "closed"):   # must readmit it
        problems.append("recovered replica never readmitted")
    time.sleep(0.5)                 # a few rounds back in rotation
    h.load_stop()
    if h.counts["error"] or h.counts["shed"]:
        problems.append(f"client-visible failures: {h.counts}")
    if h.dispatch_n("slow") <= ok_before:
        problems.append("no successful dispatch after readmission")
    return problems


def _fleet_mirror_unreachable(h: "_FleetHarness") -> list:
    from veles_tpu.resilience.mirror import HttpMirror
    problems = []
    h.spawn("r0")
    h.spawn("r1")
    h.start_router(ttl_s=10.0)      # generous TTL = coasting window
    if not h.await_routable(2):
        problems.append("fleet never formed")
    h.load_start()
    live_bus = h.router.mirror
    # swap the router's bus for a dead endpoint with a retry budget
    # scaled to the 0.2s poll (production: bounded under poll_s)
    h.router.mirror = HttpMirror(
        f"http://127.0.0.1:{_free_port()}", retries=2,
        retry_base=0.02, retry_cap=0.05, retry_total=0.15)
    time.sleep(1.5)                 # many polls of empty listings
    snap = h.router.fleet()
    if snap["routable"] != 2:
        problems.append("registry amputated during the bus outage")
    if h.counts["error"] or h.counts["shed"]:
        problems.append(f"failures during the outage: {h.counts}")
    h.router.mirror = live_bus      # bus restored: discovery resumes
    h.spawn("r2")
    if not h.await_routable(3):
        problems.append("join not discovered after bus restore")
    h.load_stop()
    if h.counts["error"] or h.counts["shed"]:
        problems.append(f"client-visible failures: {h.counts}")
    return problems


#: the serving-fleet matrix: name -> (scenario fn, blurb)
FLEET_SCENARIOS = {
    "kill_replica": (
        _fleet_kill_replica,
        "replica crashed to beacon silence mid-load -> retries absorb "
        "the death, corpse TTL-evicted, zero client errors"),
    "join_mid_load": (
        _fleet_join_mid_load,
        "replica joins mid-load -> discovered from the beacon bus "
        "(no config push), receives traffic"),
    "slow_circuit": (
        _fleet_slow_circuit,
        "slow replica times out -> circuit trips open; on recovery "
        "the half-open probe readmits it"),
    "mirror_unreachable": (
        _fleet_mirror_unreachable,
        "beacon bus dead -> registry coasts on last-known state, "
        "nothing amputated; discovery resumes on restore"),
}


def run_fleet_scenario(name: str, verbose: bool) -> dict:
    fn, _blurb = FLEET_SCENARIOS[name]
    t0 = time.time()
    h = None
    try:
        h = _FleetHarness()
        problems = fn(h)
    except Exception as e:  # noqa: BLE001 — a crashed scenario is a
        # FAIL row, not a crashed matrix
        problems = [f"{type(e).__name__}: {e!s:.200}"]
    finally:
        tmp = h.tmp if h is not None else None
        counts = dict(h.counts) if h is not None else {}
        try:
            if h is not None:
                h.stop()
        except Exception:  # noqa: BLE001
            pass
    ok = not problems
    if verbose and not ok:
        sys.stderr.write(f"--- {name} problems: {problems} ---\n")
    return {"tmp": tmp or tempfile.mkdtemp(prefix="chaos_fleet_empty_"),
            "ok": ok, "problems": problems,
            "served": counts.get("ok"), "shed": counts.get("shed"),
            "errors": counts.get("error"),
            "elapsed": time.time() - t0}


#: the matrix: name -> (fault plan, extra CLI flags, expectation)
SCENARIOS = {
    "baseline": ("", (), "completes uninterrupted"),
    "kill": ("kill@epoch=2", (), "SIGKILL mid-run -> restart from "
                                 "snapshot"),
    "stall": ("hang@epoch=2", ("--stall-timeout", "10"),
              "hang -> stall detector kills + restarts"),
    "nan": ("nan@step=5", ("--fused", "--nonfinite-guard"),
            "NaN loss -> guard aborts -> rollback restart"),
    "corrupt": ("corrupt_snapshot@write=2; kill@epoch=3", (),
                "torn newest snapshot -> checksum fallback"),
}


def run_scenario(name: str, plan: str, extra, verbose: bool) -> dict:
    tmp = tempfile.mkdtemp(prefix=f"chaos_{name}_")
    wf_py = os.path.join(tmp, "chaoswf.py")
    with open(wf_py, "w") as f:
        f.write(WORKFLOW_SRC)
    report = os.path.join(tmp, "report.json")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("VELES_FAULT_STATE", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if plan:
        env["VELES_FAULT_PLAN"] = plan
    else:
        env.pop("VELES_FAULT_PLAN", None)
    cmd = [sys.executable, "-m", "veles_tpu", wf_py, "--no-stats", "-v",
           "--supervise", "--snapshot-dir", tmp,
           "--snapshot-prefix", "chaoswf", "--max-restarts", "3",
           "--supervise-report", report,
           f"root.chaoswf.snapshot_dir={tmp}", *extra]
    t0 = time.time()
    proc = subprocess.run(cmd, env=env, cwd=tmp, capture_output=True,
                          text=True, timeout=600)
    elapsed = time.time() - t0
    final = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("FINAL")]
    final_epoch = int(final[-1].split()[1]) if final else None
    attempts = None
    if os.path.exists(report):
        with open(report) as f:
            attempts = len(json.load(f)["attempts"])
    ok = proc.returncode == 0 and final_epoch == 6
    if plan:     # a fault scenario that never needed recovery is a FAIL
        ok = ok and (attempts or 0) >= 2
    if verbose and not ok:
        sys.stderr.write(proc.stderr[-3000:] + "\n")
    return {"tmp": tmp, "ok": ok, "rc": proc.returncode,
            "final_epoch": final_epoch, "attempts": attempts,
            "elapsed": elapsed}


def _route_telemetry(rows, cluster: bool, matrix: str = "") -> None:
    """Route the matrix outcome through the ONE telemetry registry
    (telemetry/metrics.py): scenario pass/fail counts and the restarts
    the scenarios actually consumed land in the same
    `veles_restart_total` family the supervisor and the coordinator's
    /metrics expose — and VELES_METRICS_JSONL (if set) mirrors the
    flush next to the matrix output. Guarded: telemetry must never
    flip a recovery verdict."""
    try:
        if REPO not in sys.path:       # run as `python tools/chaos.py`:
            sys.path.insert(0, REPO)   # sys.path[0] is tools/, not the repo
        from veles_tpu.telemetry import metrics as tmetrics
        jsonl = os.environ.get("VELES_METRICS_JSONL")
        if jsonl:
            tmetrics.install_jsonl(jsonl)
        reg = tmetrics.default_registry()
        outcomes = reg.counter(
            "veles_chaos_scenarios_total",
            "chaos scenarios by result", labelnames=("result",))
        restarts = 0
        for _name, _plan, r in rows:
            outcomes.labels(
                result="pass" if r["ok"] else "fail").inc()
            n = r.get("restarts") if cluster else r.get("attempts")
            if isinstance(n, int):
                restarts += max(0, n - (0 if cluster else 1))
        reg.counter("veles_restart_total").inc(restarts)
        tmetrics.flush_installed(extra={
            "source": "chaos",
            "matrix": matrix or ("cluster" if cluster
                                 else "single-host")})
    except Exception:  # noqa: BLE001
        pass


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", default="",
                    help="comma-separated scenario subset "
                         f"(of {', '.join(SCENARIOS)}; with --cluster: "
                         f"{', '.join(CLUSTER_SCENARIOS)}; with "
                         f"--fleet: {', '.join(FLEET_SCENARIOS)})")
    ap.add_argument("--cluster", action="store_true",
                    help="run the CROSS-HOST fault matrix (2 loopback "
                         "member processes + shared mirror) instead of "
                         "the single-host one")
    ap.add_argument("--swap", action="store_true",
                    help="run the HOT-SWAP fault matrix (in-process "
                         "ring server + mirror + weight watcher, "
                         "ISSUE 16) instead of the single-host one")
    ap.add_argument("--fleet", action="store_true",
                    help="run the SERVING-FLEET fault matrix (replica "
                         "group + beacon bus + routing front door, "
                         "ISSUE 19) instead of the single-host one")
    ap.add_argument("--keep", action="store_true",
                    help="keep the per-scenario temp dirs for debugging")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="dump child stderr on failure")
    args = ap.parse_args()
    if sum((args.cluster, args.swap, args.fleet)) > 1:
        ap.error("--cluster / --swap / --fleet are separate matrices: "
                 "pick one")
    catalogue = (CLUSTER_SCENARIOS if args.cluster else
                 SWAP_SCENARIOS if args.swap else
                 FLEET_SCENARIOS if args.fleet else SCENARIOS)
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    unknown = only - set(catalogue)
    if unknown:
        ap.error(f"unknown scenarios: {sorted(unknown)}")

    if args.fleet:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        rows = []
        for name, (_fn, blurb) in FLEET_SCENARIOS.items():
            if only and name not in only:
                continue
            print(f"chaos[fleet]: {name}: {blurb} …", flush=True)
            r = run_fleet_scenario(name, args.verbose)
            rows.append((name, blurb, r))
            if not args.keep:
                import shutil
                shutil.rmtree(r["tmp"], ignore_errors=True)
        print()
        print(f"{'scenario':<19} {'ok':<5} {'served':<7} {'shed':<5} "
              f"{'errors':<7} {'secs':<6} problems")
        failed = 0
        for name, _blurb, r in rows:
            verdict = "PASS" if r["ok"] else "FAIL"
            failed += not r["ok"]
            print(f"{name:<19} {verdict:<5} "
                  f"{str(r['served'] if r['served'] is not None else '-'):<7} "
                  f"{str(r['shed'] if r['shed'] is not None else '-'):<5} "
                  f"{str(r['errors'] if r['errors'] is not None else '-'):<7} "
                  f"{r['elapsed']:<6.1f} "
                  f"{'; '.join(r['problems']) or '—'}")
        print()
        _route_telemetry(rows, cluster=False, matrix="fleet")
        if failed:
            print(f"{failed} fleet scenario(s) did NOT keep serving",
                  file=sys.stderr)
            return 1
        print("all fleet scenarios kept serving")
        return 0

    if args.swap:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        rows = []
        for name, (_fn, blurb) in SWAP_SCENARIOS.items():
            if only and name not in only:
                continue
            print(f"chaos[swap]: {name}: {blurb} …", flush=True)
            r = run_swap_scenario(name, args.verbose)
            rows.append((name, blurb, r))
            if not args.keep:
                import shutil
                shutil.rmtree(r["tmp"], ignore_errors=True)
        print()
        print(f"{'scenario':<19} {'ok':<5} {'applied':<8} "
              f"{'refused':<8} {'secs':<6} problems")
        failed = 0
        for name, _blurb, r in rows:
            verdict = "PASS" if r["ok"] else "FAIL"
            failed += not r["ok"]
            print(f"{name:<19} {verdict:<5} "
                  f"{str(r['applied'] if r['applied'] is not None else '-'):<8} "
                  f"{str(r['refused'] if r['refused'] is not None else '-'):<8} "
                  f"{r['elapsed']:<6.1f} "
                  f"{'; '.join(r['problems']) or '—'}")
        print()
        _route_telemetry(rows, cluster=False, matrix="swap")
        if failed:
            print(f"{failed} swap scenario(s) did NOT keep serving",
                  file=sys.stderr)
            return 1
        print("all swap scenarios kept serving")
        return 0

    if args.cluster:
        rows = []
        for name, spec in CLUSTER_SCENARIOS.items():
            if only and name not in only:
                continue
            print(f"chaos[cluster]: {name}: {spec['blurb']} …",
                  flush=True)
            r = run_cluster_scenario(name, spec, args.verbose)
            plan_str = "; ".join(f"h{h}:{p}" for h, p in
                                 spec.get("plans", {}).items())
            if spec.get("joiner_delay"):
                plan_str = (plan_str + "; " if plan_str else "") + \
                    f"join h{spec['hosts']}@+{spec['joiner_delay']:.0f}s"
            rows.append((name, plan_str or "—", r))
            if not args.keep:
                import shutil
                shutil.rmtree(r["tmp"], ignore_errors=True)
        print()
        print(f"{'scenario':<19} {'fault plan':<44} {'ok':<5} "
              f"{'rc':<18} {'gen':<4} {'term':<5} {'restarts':<9} "
              f"{'dead':<6} {'secs':<6}")
        failed = 0
        for name, plan, r in rows:
            verdict = "PASS" if r["ok"] else "FAIL"
            failed += not r["ok"]
            print(f"{name:<19} {plan:<44} {verdict:<5} "
                  f"{str(r['rc']):<18} "
                  f"{str(r['generation'] or '-'):<4} "
                  f"{str(r['term'] or '-'):<5} "
                  f"{str(r['restarts'] if r['restarts'] is not None else '-'):<9} "
                  f"{','.join(r['dead_hosts'] or []) or '-':<6} "
                  f"{r['elapsed']:<6.1f}")
        print()
        _route_telemetry(rows, cluster=True)
        if failed:
            print(f"{failed} cluster scenario(s) did NOT recover",
                  file=sys.stderr)
            return 1
        print("all cluster scenarios recovered")
        return 0

    rows = []
    for name, (plan, extra, blurb) in SCENARIOS.items():
        if only and name not in only:
            continue
        print(f"chaos: {name}: {blurb} …", flush=True)
        r = run_scenario(name, plan, extra, args.verbose)
        rows.append((name, plan or "—", r))
        if not args.keep:
            import shutil
            shutil.rmtree(r["tmp"], ignore_errors=True)

    print()
    print(f"{'scenario':<10} {'fault plan':<36} {'recovered':<10} "
          f"{'epochs':<7} {'attempts':<9} {'secs':<6}")
    failed = 0
    for name, plan, r in rows:
        verdict = "PASS" if r["ok"] else "FAIL"
        failed += not r["ok"]
        print(f"{name:<10} {plan:<36} {verdict:<10} "
              f"{r['final_epoch'] or '-':<7} {r['attempts'] or '-':<9} "
              f"{r['elapsed']:<6.1f}")
    print()
    _route_telemetry(rows, cluster=False)
    if failed:
        print(f"{failed} scenario(s) did NOT recover", file=sys.stderr)
        return 1
    print("all scenarios recovered")
    return 0


if __name__ == "__main__":
    sys.exit(main())
