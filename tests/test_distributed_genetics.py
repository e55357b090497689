"""Cluster-distributed genetics/ensemble (VERDICT r4 item 4).

Parity target: reference `veles/genetics/` — the master distributed GA
individuals across slaves and re-issued work lost to dead slaves
(SURVEY.md §2.5, §3.5). Here the coordinator runs a
`task_queue.FitnessQueueServer` lease queue; workers are REAL OS
processes (`tests/dist_ga_worker.py`) plus coordinator-local threads.

Covered:
- individuals demonstrably evaluated on BOTH processes (recorded pids);
- a worker killed mid-individual (leases, then exits without posting)
  has its individual re-queued and finished by a healthy worker;
- full GA evolve() through the queue matches local-mode results;
- ensemble members trained on a worker process come back as
  whole-workflow pickles and serve predictions on the coordinator.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.genetics import Population, Tune
from veles_tpu.task_queue import FitnessQueueServer, FitnessQueueWorker

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "dist_ga_worker.py")


def _spawn(mode: str, port: int, record: str,
           *more: str) -> subprocess.Popen:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(HERE)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen([sys.executable, WORKER, mode, str(port),
                             record, *more], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def test_individuals_run_on_both_processes(tmp_path):
    srv = FitnessQueueServer(host="127.0.0.1", lease_s=30).start()
    sub_record = str(tmp_path / "sub.jsonl")
    local_record = []

    def local_fitness(payload):
        local_record.append(payload)
        time.sleep(0.3)         # let the subprocess win some leases too
        return (payload["x"] - 3.0) ** 2

    # both sides take 0.3 s an individual and poll every 0.05 s, so
    # neither can take all twelve before the other has asked: with the
    # subprocess answering at once and the local worker asleep for its
    # default 0.5 s, the subprocess took the whole round under load
    proc = _spawn("work", srv.port, sub_record, "0.3")
    # wait until the subprocess is past its imports and polling, so both
    # processes genuinely compete for the leases below
    deadline = time.time() + 60
    while not os.path.exists(sub_record + ".ready"):
        assert time.time() < deadline, "worker subprocess never ready"
        assert proc.poll() is None, proc.communicate()
        time.sleep(0.1)
    FitnessQueueWorker("127.0.0.1", srv.port, local_fitness,
                       poll_s=0.05).start_thread()
    try:
        payloads = [{"x": float(i)} for i in range(12)]
        fits = srv.submit(payloads, timeout_s=60)
        assert fits == [(p["x"] - 3.0) ** 2 for p in payloads]
        # both processes demonstrably evaluated individuals
        deadline = time.time() + 20
        sub_lines = []
        while time.time() < deadline:
            if os.path.exists(sub_record):
                sub_lines = open(sub_record).read().splitlines()
                if sub_lines:
                    break
            time.sleep(0.1)
        assert sub_lines, "subprocess worker evaluated no individuals"
        assert local_record, "local worker evaluated no individuals"
        sub_pids = {json.loads(ln)["pid"] for ln in sub_lines}
        assert sub_pids and os.getpid() not in sub_pids
        assert len(sub_lines) + len(local_record) >= len(payloads)
    finally:
        srv.stop()
        proc.terminate()
        proc.wait(timeout=10)


def test_lease_expiry_requeues_within_one_round(tmp_path):
    """Tighter re-queue proof inside ONE submit round: worker A leases
    the only task and dies; worker B (started later) completes it."""
    srv = FitnessQueueServer(host="127.0.0.1", lease_s=1.0).start()
    leased_path = str(tmp_path / "leased.json")
    result = {}

    def submit_thread():
        result["fits"] = srv.submit([{"x": 7.0}], timeout_s=45)

    import threading
    t = threading.Thread(target=submit_thread, daemon=True)
    t.start()
    time.sleep(0.2)                         # task is queued

    evil = _spawn("die", srv.port, leased_path)
    assert evil.wait(timeout=20) == 1       # leased the task, died
    leased = json.load(open(leased_path))
    assert leased["payload"] == {"x": 7.0}

    done = []
    FitnessQueueWorker("127.0.0.1", srv.port,
                       lambda p: done.append(p) or p["x"] * 2,
                       poll_s=0.2).start_thread()
    t.join(timeout=45)
    try:
        assert result.get("fits") == [14.0]
        assert done == [{"x": 7.0}]         # the SAME individual
        assert srv.requeue_count >= 1
    finally:
        srv.stop()


def test_population_evolves_through_queue(tmp_path):
    """Full GA through the cluster queue: same analytic optimum the
    local-mode test uses, individuals evaluated by a subprocess worker
    plus a local thread."""
    srv = FitnessQueueServer(host="127.0.0.1", lease_s=30).start()
    sub_record = str(tmp_path / "sub.jsonl")
    proc = _spawn("work", srv.port, sub_record)

    def local_fitness(payload):
        return (payload["x"] - 3.0) ** 2

    FitnessQueueWorker(
        "127.0.0.1", srv.port,
        lambda p: (p["x"] - 3.0) ** 2).start_thread()

    tun = [Tune("x", 0.0, 10.0)]
    prng.seed_all(5)
    pop = Population(tun, local_fitness, size=8, elite=2,
                     queue_server=srv)
    try:
        best = pop.evolve(generations=4)
        assert abs(best.overrides(tun)["x"] - 3.0) < 1.0, best.values
    finally:
        srv.stop()
        proc.terminate()
        proc.wait(timeout=10)


def test_ensemble_members_trained_on_worker_process(tmp_path):
    """Cluster ensemble: members train in a WORKER process (real
    workflow, real run), come back as pickles, and the coordinator
    serves averaged predictions from them."""
    from veles_tpu.ensemble import Ensemble

    # default max_body: Ensemble.train must auto-raise it for pickles
    srv = FitnessQueueServer(host="127.0.0.1", lease_s=120).start()
    record = str(tmp_path / "members.log")
    proc = _spawn("member", srv.port, record)
    try:
        ens = Ensemble(factory=None, seeds=[21, 22])
        ens.train(queue_server=srv)
        assert len(ens.members) == 2
        # trained on the worker process, not here
        lines = open(record).read().splitlines()
        assert len(lines) == 2
        assert all(f"pid={proc.pid}" in ln for ln in lines)
        # the restored members serve predictions on the coordinator
        x = np.random.RandomState(0).randn(16, 8).astype(np.float32)
        probs = ens.predict(x)
        assert probs.shape == (16, 4)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-4)
    finally:
        srv.stop()
        proc.terminate()
        proc.wait(timeout=10)


def test_token_auth_rejects_unauthenticated():
    srv = FitnessQueueServer(host="127.0.0.1", token="sekrit").start()
    try:
        # a bad token is an ERROR the worker surfaces, not silent
        # no-contact idling (that would exit 0 having evaluated nothing)
        w_bad = FitnessQueueWorker("127.0.0.1", srv.port, lambda p: 0.0)
        with pytest.raises(PermissionError):
            w_bad._request("GET", "/task")
        w_ok = FitnessQueueWorker("127.0.0.1", srv.port, lambda p: 0.0,
                                  token="sekrit")
        got = w_ok._request("GET", "/task")
        assert got == {"done": False, "task": None}
    finally:
        srv.stop()


def test_cli_optimize_cluster_two_process(tmp_path):
    """CLI wiring end-to-end: `--optimize -l` coordinator + `--optimize
    -m` worker as real `python -m veles_tpu` processes. The coordinator
    runs the GA over the lease queue (contributing compute via its local
    worker thread), the worker leases individuals until the server says
    done, both exit 0, and the coordinator prints the best overrides."""
    import socket

    wf_file = tmp_path / "wf.py"
    wf_file.write_text(
        "from veles_tpu.samples.mnist import run  # noqa\n"
        "from veles_tpu.genetics import Tune\n"
        "TUNABLES = [Tune('mnist.gd.learning_rate', 0.01, 0.5, "
        "log=True)]\n")
    overrides = ["root.mnist.decision.max_epochs=1",
                 "root.mnist.loader.n_train=100",
                 "root.mnist.loader.n_validation=50",
                 "root.mnist.loader.minibatch_size=50"]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(HERE) + os.pathsep \
        + env.get("PYTHONPATH", "")
    base = [sys.executable, "-m", "veles_tpu", str(wf_file)] + overrides \
        + ["-b", "numpy", "-r", "5", "--no-stats", "--optimize", "1"]
    master = subprocess.Popen(
        base + ["-l", f"127.0.0.1:{port}"], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    worker = subprocess.Popen(
        base + ["-m", f"127.0.0.1:{port}"], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        m_out, m_err = master.communicate(timeout=300)
        w_out, w_err = worker.communicate(timeout=60)
    except subprocess.TimeoutExpired:
        master.kill()
        worker.kill()
        raise
    assert master.returncode == 0, m_err[-2000:]
    assert worker.returncode == 0, w_err[-2000:]
    best = json.loads(m_out.strip().splitlines()[-1])
    assert 0.01 <= best["best_overrides"]["mnist.gd.learning_rate"] <= 0.5


def test_failed_individual_reports_inf_not_hang():
    """One crashing individual must not kill the worker loop (and with
    it the whole GA): the worker reports worst-possible fitness and
    keeps serving."""
    srv = FitnessQueueServer(host="127.0.0.1", lease_s=30).start()

    def fitness(payload):
        if payload["x"] == 1.0:
            raise RuntimeError("synthetic crash")
        return payload["x"]

    FitnessQueueWorker("127.0.0.1", srv.port, fitness,
                       poll_s=0.1).start_thread()
    try:
        fits = srv.submit([{"x": 1.0}, {"x": 2.0}], timeout_s=30)
        assert fits[0] == float("inf")
        assert fits[1] == 2.0
    finally:
        srv.stop()


def test_lease_renewal_covers_slow_individuals():
    """An individual slower than lease_s must NOT be re-issued while its
    worker is still alive and renewing."""
    srv = FitnessQueueServer(host="127.0.0.1", lease_s=1.0).start()
    calls = []

    def slow_fitness(payload):
        calls.append(payload)
        time.sleep(2.5)                 # 2.5x the lease
        return 42.0

    FitnessQueueWorker("127.0.0.1", srv.port, slow_fitness,
                       poll_s=0.1).start_thread()
    try:
        fits = srv.submit([{"x": 0.0}], timeout_s=30)
        assert fits == [42.0]
        assert len(calls) == 1          # never re-issued
        assert srv.requeue_count == 0
    finally:
        srv.stop()


def test_oversized_result_gets_413_not_truncation():
    srv = FitnessQueueServer(host="127.0.0.1", max_body=1024).start()
    try:
        w = FitnessQueueWorker("127.0.0.1", srv.port, lambda p: 0.0)
        big = {"id": "g1-0", "fitness": 0.0, "artifact": "A" * 4096}
        assert w._request("POST", "/result", big) is None       # 413
    finally:
        srv.stop()


def test_worker_gives_up_and_reports_it():
    """A worker that never reaches a coordinator must not report
    success: run() ends with ended_by='gave_up' and zero tasks (the CLI
    turns that into a nonzero exit)."""
    w = FitnessQueueWorker("127.0.0.1", 1, lambda p: 0.0,
                           poll_s=0.1, give_up_s=1.0)
    assert w.run() == 0
    assert w.ended_by == "gave_up"


def test_worker_poll_backs_off_exponentially_with_jitter(monkeypatch):
    """Unreachable-coordinator polls back off exponentially (jittered,
    capped) instead of hammering at poll_s: a briefly-down coordinator
    must not get a thundering herd from the whole worker fleet the
    moment it comes back."""
    from veles_tpu import task_queue as tq

    w = FitnessQueueWorker("127.0.0.1", 1, lambda p: 0.0,
                           poll_s=0.1, give_up_s=1e9,
                           backoff_max=2.0, backoff_jitter=0.25)
    delays = []

    class FakeTime:
        _now = 0.0

        @classmethod
        def monotonic(cls):
            return cls._now

        @classmethod
        def sleep(cls, d):
            delays.append(d)
            cls._now += d
            if len(delays) >= 8:
                raise KeyboardInterrupt   # enough samples: stop loop

    monkeypatch.setattr(tq, "time", FakeTime)
    monkeypatch.setattr(
        w, "_request",
        lambda *a, **k: (_ for _ in ()).throw(OSError("refused")))
    with pytest.raises(KeyboardInterrupt):
        w.run()
    for i, d in enumerate(delays):
        base = min(0.1 * (2 ** i), 2.0)
        assert base <= d <= base * 1.25 + 1e-9, (i, d)
    # strictly growing until the cap kicks in (jitter < doubling)
    assert delays[0] < delays[1] < delays[2] < delays[3]


def test_bad_token_worker_raises_not_gave_up():
    """PermissionError must escape run() (it subclasses OSError, which
    run() swallows for unreachable-coordinator) so the CLI reports a
    token mismatch, not 'no coordinator contact'."""
    srv = FitnessQueueServer(host="127.0.0.1", token="sekrit").start()
    try:
        w = FitnessQueueWorker("127.0.0.1", srv.port, lambda p: 0.0,
                               poll_s=0.1, give_up_s=5.0)
        with pytest.raises(PermissionError):
            w.run()
    finally:
        srv.stop()
