"""ISSUE 10: the concurrency & protocol analyzer (analysis passes 4/5).

Every rule proven both ways — a seeded defect it must catch, a clean
build that must produce zero findings — plus the machinery contracts:
the guard-inference model (setup happens-before, flag publication,
lock-context propagation through helpers and the `outer = self` handler
idiom), suppression, the velint-gate integration, a runtime lock-order
WITNESS that cross-validates the static order graph, and the telemetry
tracer ring's thread-safety invariant (slow-marked stress).
"""

import os
import subprocess
import sys
import threading
import time

import pytest

from veles_tpu.analysis import concurrency, lint, protocol

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules(findings):
    return sorted(f.rule for f in findings)


# == shared-write-no-lock =====================================================

_RACY_WORKER = """
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0
        self.results = []
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            self.count += 1
            self.results.append(1)

    def snapshot(self):
        return self.count, len(self.results)

    def stop(self):
        pass
"""


def test_shared_write_no_lock_seeded():
    findings = concurrency.analyze_source(_RACY_WORKER, "w.py")
    assert rules(findings) == ["shared-write-no-lock"] * 2
    attrs = sorted(f.message.split(" is ")[0] for f in findings)
    assert attrs == ["Worker.count", "Worker.results"]
    # the finding names both roots and anchors at the unguarded write
    assert "thread:_loop" in findings[0].message
    assert "main" in findings[0].message


def test_shared_write_no_lock_clean_when_guarded():
    src = _RACY_WORKER.replace(
        "            self.count += 1\n"
        "            self.results.append(1)\n",
        "            with self._lock:\n"
        "                self.count += 1\n"
        "                self.results.append(1)\n").replace(
        "        return self.count, len(self.results)\n",
        "        with self._lock:\n"
        "            return self.count, len(self.results)\n")
    assert concurrency.analyze_source(src, "w.py") == []


def test_shared_write_handler_roots_via_outer_alias():
    """The nested-handler idiom every HTTP plane uses: do_* methods are
    self-concurrent roots of the OUTER class through `outer = self`,
    and container mutation races dict iteration across server threads
    (the exact web_status bug this PR fixed)."""
    src = """
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

class StatusServer:
    def __init__(self):
        self.workers = {}
        self._lock = threading.Lock()
        self._httpd = None
        self._thread = None

    def start(self):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                outer.workers["x"] = 1

            def do_GET(self):
                rows = sorted(outer.workers.items())

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self):
        pass
"""
    findings = concurrency.analyze_source(src, "s.py")
    assert rules(findings) == ["shared-write-no-lock"]
    assert "StatusServer.workers" in findings[0].message
    assert "handler:Handler.do_POST" in findings[0].message
    # guarded twin: a lock alias captured by the closure counts
    clean = src.replace(
        '                outer.workers["x"] = 1',
        '                with lock:\n'
        '                    outer.workers["x"] = 1').replace(
        "                rows = sorted(outer.workers.items())",
        "                with lock:\n"
        "                    rows = sorted(outer.workers.items())").replace(
        "        outer = self",
        "        outer = self\n        lock = self._lock")
    assert concurrency.analyze_source(clean, "s.py") == []


def test_setup_and_prestart_writes_are_exempt():
    """__init__/initialize writes and writes lexically before the
    thread .start() in the spawning method are publication, not races;
    post-start writes from main against a thread reader still flag."""
    src = """
import threading

class Feed:
    def __init__(self):
        self.config = {}

    def initialize(self):
        self.table = [1, 2, 3]

    def start(self):
        self.ready = {"a": 1}
        t = threading.Thread(target=self._loop, daemon=True)
        t.start()
        self.late = {"b": 2}

    def _loop(self):
        return (self.config, self.table, self.ready, self.late)

    def stop(self):
        pass
"""
    findings = concurrency.analyze_source(src, "f.py")
    assert rules(findings) == ["shared-write-no-lock"]
    assert "Feed.late" in findings[0].message


def test_flag_publication_and_safe_types_exempt():
    src = """
import threading
import queue

class Pump:
    def __init__(self):
        self._q = queue.Queue()
        self._stopping = False

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while not self._stopping:
            self._q.put(1)

    def stop(self):
        self._stopping = True
"""
    assert concurrency.analyze_source(src, "p.py") == []


def test_suppression_applies_to_concurrency_findings():
    sup = _RACY_WORKER.replace(
        "            self.count += 1",
        "            # velint: disable=shared-write-no-lock\n"
        "            self.count += 1").replace(
        "            self.results.append(1)",
        "            self.results.append(1)  "
        "# velint: disable=shared-write-no-lock")
    assert concurrency.analyze_source(sup, "w.py") == []


def test_super_call_resolves_into_base_method():
    """PrefetchingLoader.run -> super().run() must reach Loader.run's
    accesses — the analysis flattens single-module hierarchies AND
    follows one super() hop."""
    src = """
import threading

class Base:
    def run(self):
        self.counter += 1

class Derived(Base):
    def __init__(self):
        self.counter = 0

    def run(self):
        super().run()

    def start(self):
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        return self.counter

    def stop(self):
        pass
"""
    findings = concurrency.analyze_source(src, "d.py")
    assert rules(findings) == ["shared-write-no-lock"]
    assert "Derived.counter" in findings[0].message


# == lock-order cycle =========================================================

_ORDERED = """
import threading

class Pair:
    def __init__(self):
        self._lock_a = threading.Lock()
        self._lock_b = threading.Lock()
        self.n = 0

    def start(self):
        t = threading.Thread(target=self._loop, daemon=True)
        t.start()
        return t

    def _loop(self):
        for _ in range(50):
            with self._lock_a:
                with self._lock_b:
                    self.n += 1

    def bump(self):
        for _ in range(50):
            with self._lock_a:
                with self._lock_b:
                    self.n += 1

    def stop(self):
        pass
"""


def test_lock_order_cycle_seeded():
    cyclic = _ORDERED.replace(
        "    def bump(self):\n"
        "        for _ in range(50):\n"
        "            with self._lock_a:\n"
        "                with self._lock_b:",
        "    def bump(self):\n"
        "        for _ in range(50):\n"
        "            with self._lock_b:\n"
        "                with self._lock_a:")
    findings = [f for f in concurrency.analyze_source(cyclic, "c.py")
                if f.rule == "lock-order-cycle"]
    assert len(findings) == 1
    assert "Pair._lock_a" in findings[0].message
    assert "Pair._lock_b" in findings[0].message


def test_lock_order_consistent_is_clean():
    assert [f for f in concurrency.analyze_source(_ORDERED, "c.py")
            if f.rule == "lock-order-cycle"] == []


def test_lock_self_reacquire_flags_lock_but_not_rlock():
    src = """
import threading

class Nest:
    def __init__(self):
        self._lock = threading.Lock()

    def outerm(self):
        with self._lock:
            self.innerm()

    def innerm(self):
        with self._lock:
            pass
"""
    findings = [f for f in concurrency.analyze_source(src, "n.py")
                if f.rule == "lock-order-cycle"]
    assert len(findings) == 1 and "self-deadlock" in findings[0].message
    # the identical shape on an RLock is the blessed reentrant idiom
    assert [f for f in concurrency.analyze_source(
        src.replace("threading.Lock()", "threading.RLock()"), "n.py")
        if f.rule == "lock-order-cycle"] == []


# == wait-holding-lock ========================================================

def test_wait_holding_other_lock_seeded_and_clean():
    src = """
import threading

class Waiter:
    def __init__(self):
        self._lock = threading.Lock()
        self._done = threading.Event()

    def block(self):
        with self._lock:
            self._done.wait()
"""
    findings = concurrency.analyze_source(src, "w.py")
    assert rules(findings) == ["wait-holding-lock"]
    assert "_done" in findings[0].message
    # waiting on the condition you hold is the Condition contract
    clean = """
import threading

class Waiter:
    def __init__(self):
        self._cv = threading.Condition()

    def block(self):
        with self._cv:
            self._cv.wait()
"""
    assert concurrency.analyze_source(clean, "w.py") == []


# == lock-no-with (the folded acquire-release rule) ===========================

def test_lock_no_with_acquire_without_finally_release():
    """ISSUE-10 satellite: .acquire() with no paired `finally:
    .release()` — including the assignment form — is the extended
    lock-no-with; the try/finally idiom is clean."""
    bad = (
        "def f(self):\n"
        "    got = self._lock.acquire(timeout=1)\n"
        "    if got:\n"
        "        work()\n"
        "        self._lock.release()\n"
    )
    findings = lint.lint_source(bad)
    assert [f.rule for f in findings] == ["lock-no-with"]
    good = (
        "def f(self):\n"
        "    self._lock.acquire()\n"
        "    try:\n"
        "        work()\n"
        "    finally:\n"
        "        self._lock.release()\n"
    )
    assert lint.lint_source(good) == []


# == endpoint contracts =======================================================

def test_endpoint_unauthed_seeded_and_clean():
    bad = """
from http.server import BaseHTTPRequestHandler

class Handler(BaseHTTPRequestHandler):
    def do_POST(self):
        n = min(int(self.headers.get("Content-Length", "0")), 4096)
        data = self.rfile.read(n)
        self.send_response(204)
"""
    findings = protocol.analyze_source(bad, "srv.py")
    assert rules(findings) == ["endpoint-unauthed"]
    good = bad.replace(
        "    def do_POST(self):\n",
        "    def do_POST(self):\n"
        "        if not check_shared_token(self, None):\n"
        "            return\n")
    assert protocol.analyze_source(good, "srv.py") == []


def test_endpoint_auth_via_handler_helper_counts():
    """The task_queue idiom: do_POST -> self._auth() ->
    check_shared_token resolves transitively."""
    src = """
from http.server import BaseHTTPRequestHandler

class Handler(BaseHTTPRequestHandler):
    def _auth(self):
        return check_shared_token(self, None)

    def do_POST(self):
        if not self._auth():
            return
        n = min(int(self.headers.get("Content-Length", "0")), 4096)
        data = self.rfile.read(n)
"""
    assert protocol.analyze_source(src, "srv.py") == []


def test_endpoint_unbounded_body_seeded_and_clean():
    bad = """
from http.server import BaseHTTPRequestHandler

class Handler(BaseHTTPRequestHandler):
    def do_POST(self):
        if not check_shared_token(self, None):
            return
        n = int(self.headers.get("Content-Length", "0"))
        body = self.rfile.read(n)

    def do_PUT(self):
        if not check_shared_token(self, None):
            return
        raw = self.rfile.read()
"""
    findings = protocol.analyze_source(bad, "srv.py")
    assert rules(findings) == ["endpoint-unbounded-body"] * 2
    # both blessed idioms: min-clamp and validate-then-read
    good = """
from http.server import BaseHTTPRequestHandler

class Handler(BaseHTTPRequestHandler):
    def do_POST(self):
        if not check_shared_token(self, None):
            return
        n = min(int(self.headers.get("Content-Length", "0")), 4096)
        body = self.rfile.read(n)

    def do_PUT(self):
        if not check_shared_token(self, None):
            return
        length = int(self.headers.get("Content-Length", "0"))
        if length > 65536:
            self.send_response(413)
            return
        raw = self.rfile.read(length)
"""
    assert protocol.analyze_source(good, "srv.py") == []


# == thread-no-stop ===========================================================

def test_thread_no_stop_seeded_and_clean():
    bad = """
import threading
from concurrent.futures import ThreadPoolExecutor

class Owner:
    def start(self):
        self._t = threading.Thread(target=self._loop)
        self._t.start()

    def _loop(self):
        pass

class PoolOwner:
    def fill(self):
        self._pool = ThreadPoolExecutor(max_workers=2)
"""
    findings = protocol.analyze_source(bad, "veles_tpu/svc.py")
    assert rules(findings) == ["thread-no-stop"] * 2
    good = bad.replace(
        "    def _loop(self):\n        pass\n",
        "    def _loop(self):\n        pass\n\n"
        "    def stop(self):\n        self._t.join()\n").replace(
        "        self._pool = ThreadPoolExecutor(max_workers=2)\n",
        "        self._pool = ThreadPoolExecutor(max_workers=2)\n\n"
        "    def stop(self):\n        self._pool.shutdown()\n")
    assert protocol.analyze_source(good, "veles_tpu/svc.py") == []
    # inherited stop() satisfies the contract
    inherited = bad.replace(
        "class Owner:",
        "class BaseSvc:\n    def stop(self):\n        pass\n\n"
        "class Owner(BaseSvc):") + "\n"
    findings = protocol.analyze_source(inherited, "veles_tpu/svc.py")
    assert rules(findings) == ["thread-no-stop"]     # PoolOwner only
    # loader paths belong to velint's loader-thread rule — not this one
    assert protocol.analyze_source(
        bad, "veles_tpu/loader/bad_loader.py") == []


# == the repo itself is clean (tier-1 gate) ===================================

def test_concurrency_and_protocol_repo_clean():
    """Satellite 1: the shipped tree has an EMPTY baseline — every true
    positive the passes surface in resilience/, the loaders, serving,
    task_queue, web_status and telemetry is fixed or suppressed with a
    written justification."""
    paths = [os.path.join(REPO, p) for p in ("veles_tpu", "tools")]
    assert concurrency.analyze_paths(paths, root=REPO) == []
    assert protocol.analyze_paths(paths, root=REPO) == []


def test_velint_gate_runs_concurrency_and_protocol(tmp_path):
    """tools/velint.py runs ALL the passes by default: a seeded race +
    a stop()-less thread owner in an ad-hoc file fail the gate with
    the new rules (the repo-wide --ci smoke in test_analysis.py proves
    the clean direction)."""
    seeded = tmp_path / "svc.py"
    seeded.write_text(_RACY_WORKER.replace(
        "    def stop(self):\n        pass\n", ""))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "velint.py"),
         str(seeded)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "shared-write-no-lock" in out.stdout
    assert "thread-no-stop" in out.stdout


# == runtime lock-order witness ===============================================

class _Witness:
    """Records (held -> acquired) edges as they actually happen."""

    def __init__(self):
        self.edges = set()
        self._tls = threading.local()
        self._elock = threading.Lock()

    def held(self):
        if not hasattr(self._tls, "held"):
            self._tls.held = []
        return self._tls.held


class _WitnessLock:
    def __init__(self, name, witness):
        self._name = name
        self._w = witness
        self._lk = threading.Lock()

    def __enter__(self):
        held = self._w.held()
        with self._w._elock:
            for h in held:
                self._w.edges.add((h, self._name))
        self._lk.acquire()
        held.append(self._name)
        return self

    def __exit__(self, *exc):
        self._w.held().remove(self._name)
        self._lk.release()


def test_runtime_lock_order_witness_matches_static_graph():
    """Tier-1 cross-validation: run the SAME source the static pass
    analyzed, with its locks replaced by recording proxies, on two
    threads — the observed acquisition-order edges must equal the
    static graph, and no observed edge may reverse a static one (the
    deadlock the cycle rule exists to prevent)."""
    static = concurrency.lock_order_edges_source(_ORDERED, "pair.py")
    assert static == {("Pair._lock_a", "Pair._lock_b")}
    ns = {}
    exec(compile(_ORDERED, "pair.py", "exec"), ns)    # the same code
    pair = ns["Pair"]()
    w = _Witness()
    pair._lock_a = _WitnessLock("Pair._lock_a", w)
    pair._lock_b = _WitnessLock("Pair._lock_b", w)
    t = pair.start()
    pair.bump()
    t.join(timeout=30)
    assert not t.is_alive()
    assert pair.n == 100
    assert w.edges == static
    assert not any((b, a) in w.edges for (a, b) in static)


# == the shipped fixes behave =================================================

def test_fitness_worker_stop_decommissions_threaded_loop():
    """The thread-no-stop fix is real teardown, not a stub: stop()
    ends a threaded worker loop mid-backoff (unreachable coordinator)
    instead of leaving it polling until give_up_s."""
    from veles_tpu.task_queue import FitnessQueueWorker
    w = FitnessQueueWorker("127.0.0.1", 1, lambda p: 0.0,
                           poll_s=0.05, give_up_s=60.0)
    t = w.start_thread()
    time.sleep(0.15)
    w.stop()
    t.join(timeout=5)
    assert not t.is_alive()
    assert w.ended_by == "stopped"


def test_web_status_concurrent_beats_and_status_reads():
    """The workers-registry lock fix: hammer beats and status reads
    from concurrent clients — no dropped beat, no iteration crash
    (pre-fix, sorted(workers.items()) mid-insert could raise and 500)."""
    import http.client
    import json as _json
    from types import SimpleNamespace

    from veles_tpu.web_status import WebStatusServer
    wf = SimpleNamespace(name="fixture", stopped=False, units=[])
    srv = WebStatusServer(wf, host="127.0.0.1", port=0)
    srv.start()
    try:
        errors = []

        def beat(pid):
            for i in range(40):
                body = _json.dumps({"process_id": f"p{pid}-{i % 7}",
                                    "host": "h", "local_devices": 1})
                conn = http.client.HTTPConnection("127.0.0.1",
                                                  srv.port, timeout=5)
                try:
                    conn.request("POST", "/heartbeat.json", body,
                                 {"Content-Type": "application/json"})
                    if conn.getresponse().status != 204:
                        errors.append("beat rejected")
                finally:
                    conn.close()

        def read():
            for _ in range(40):
                conn = http.client.HTTPConnection("127.0.0.1",
                                                  srv.port, timeout=5)
                try:
                    conn.request("GET", "/status.json")
                    resp = conn.getresponse()
                    if resp.status != 200:
                        errors.append(f"status {resp.status}")
                    _json.loads(resp.read())
                finally:
                    conn.close()

        threads = [threading.Thread(target=beat, args=(i,))
                   for i in range(2)] + [threading.Thread(target=read)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert len(srv.workers) == 14      # 2 writers x 7 pids
    finally:
        srv.stop()


# == telemetry tracer ring invariant (satellite; slow) ========================

@pytest.mark.slow
def test_tracer_ring_concurrent_appends_no_undercount():
    """The documented thread-safety invariant of the span ring: N
    concurrent appenders lose NOTHING — the recorded-count is exact
    (no lost increments), and overflow drops exactly recorded-capacity
    oldest events, never undercounting `dropped`."""
    from veles_tpu.telemetry.tracer import Tracer
    n_threads, per_thread = 8, 4000
    total = n_threads * per_thread

    def hammer(tr):
        def work():
            for _ in range(per_thread):
                tr.add_span("stress", "t", 0.0, 1e-6)
        threads = [threading.Thread(target=work)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    big = Tracer(capacity=65536)           # no overflow
    hammer(big)
    assert big._n == total
    assert len(big.events()) == total
    assert big.dropped == 0

    small = Tracer(capacity=1024)          # guaranteed overflow
    hammer(small)
    assert small._n == total               # the counter never tears
    assert len(small.events()) == small.capacity
    assert small.dropped == total - small.capacity
