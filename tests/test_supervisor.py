"""Supervisor end-to-end: real CLI training processes under injected
faults — SIGKILL mid-run, hangs, torn snapshots — recovered without any
manual restart (the acceptance path of the resilience layer).

The fast subset here stays tier-1 (each case is a couple of short CPU
training runs); the full chaos matrix is tools/chaos.py and the
`slow`-marked case below."""

import json
import os
import subprocess
import sys
import time

import pytest

from veles_tpu.resilience import EXIT_GIVEUP
from veles_tpu.snapshotter import Snapshotter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a small supervised run that snapshots on every improvement and prints
#: its final epoch counter; MAX_EPOCHS pins the uninterrupted length.
WORKFLOW_SRC = '''
import numpy as np
from veles_tpu.config import root
from veles_tpu import prng
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.znicz.standard_workflow import StandardWorkflow

root.supwf.snapshot_dir = "."

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
        snapshot_config={"directory": root.supwf.snapshot_dir,
                         "prefix": "supwf"},
        name="SupWF")

def run(load, main):
    wf, restored = load(create_workflow)
    main()
    print("FINAL", wf.decision.epoch_number, flush=True)
'''

#: a workflow whose import always fails — the permanent-crash case
BROKEN_SRC = '''
raise SystemExit("broken on purpose")
'''

#: same training job, but the WORKFLOW deterministically dies at epoch 2
#: on every attempt (a bug that travels with the code, unlike a one-shot
#: injected fault) — the no-progress cutoff's target scenario
CRASH_LOOP_SRC = WORKFLOW_SRC + '''
import sys
from veles_tpu.resilience import hooks as _hooks
_hooks.add_epoch_hook(lambda e: sys.exit(1) if e >= 2 else None)
'''


def _env(tmp_path, fault_plan=""):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("VELES_FAULT_STATE", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if fault_plan:
        env["VELES_FAULT_PLAN"] = fault_plan
    else:
        env.pop("VELES_FAULT_PLAN", None)
    return env


def _run_supervised(tmp_path, fault_plan="", extra=(), timeout=240,
                    workflow_src=WORKFLOW_SRC):
    wf_py = tmp_path / "supwf.py"
    wf_py.write_text(workflow_src)
    report = tmp_path / "supervisor_report.json"
    cmd = [sys.executable, "-m", "veles_tpu", str(wf_py), "--no-stats",
           "-v", "--supervise", "--snapshot-dir", str(tmp_path),
           "--snapshot-prefix", "supwf",
           "--supervise-report", str(report),
           f"root.supwf.snapshot_dir={tmp_path}", *extra]
    out = subprocess.run(cmd, env=_env(tmp_path, fault_plan),
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=timeout)
    report_data = (json.loads(report.read_text())
                   if report.exists() else None)
    return out, report_data


def _final_epoch(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.startswith("FINAL")]
    assert lines, stdout
    return int(lines[-1].split()[1])


def test_supervisor_recovers_from_kill(tmp_path):
    """Acceptance path: kill@epoch=2 SIGKILLs the child mid-run; the
    supervisor restarts it from the newest snapshot and the job reaches
    the SAME final epoch count as an uninterrupted run — no manual
    restart anywhere."""
    out, report = _run_supervised(tmp_path, fault_plan="kill@epoch=2",
                                  extra=("--max-restarts", "3"))
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    # MAX_EPOCHS in the workflow file is the uninterrupted epoch count
    assert _final_epoch(out.stdout) == 6
    assert report["outcome"] == "completed"
    assert len(report["attempts"]) == 2          # initial + 1 restart
    assert report["attempts"][0]["reason"] == "died"
    # the restart resumed from a snapshot, not from scratch
    assert report["attempts"][1]["snapshot"]
    assert report["attempts"][1]["reason"] == "ok"


def test_supervisor_corrupt_snapshot_fallback(tmp_path):
    """Acceptance path: the newest snapshot is torn (fault hook) before
    a kill; the supervisor's restart detects the corruption via the
    sha256 sidecar and resumes from the previous VALID snapshot."""
    out, report = _run_supervised(
        tmp_path,
        fault_plan="corrupt_snapshot@write=2; kill@epoch=3",
        extra=("--max-restarts", "3"))
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert _final_epoch(out.stdout) == 6
    resumed_from = report["attempts"][1]["snapshot"]
    assert resumed_from
    # the torn file is still on disk, newer than the resumed-from one,
    # and fails verification — proving latest() skipped it by checksum
    snaps = sorted((p for p in os.listdir(tmp_path)
                    if p.startswith("supwf") and p.endswith(".gz")),
                   key=lambda p: os.path.getmtime(
                       os.path.join(tmp_path, p)))
    torn = [p for p in snaps
            if not Snapshotter.verify(os.path.join(tmp_path, p))]
    assert torn, snaps
    assert os.path.basename(resumed_from) not in torn
    assert Snapshotter.verify(resumed_from)


def test_supervisor_gives_up_with_exit_report(tmp_path):
    """A permanently-broken job exhausts the retry budget and exits with
    the distinct give-up code plus a machine-readable attempt log."""
    out, report = _run_supervised(tmp_path, extra=("--max-restarts", "1"),
                                  workflow_src=BROKEN_SRC, timeout=120)
    assert out.returncode == EXIT_GIVEUP, (out.returncode,
                                           out.stderr[-2000:])
    assert report["exit_code"] == EXIT_GIVEUP
    assert len(report["attempts"]) == 2          # initial + 1 restart
    assert all(a["reason"] == "died" for a in report["attempts"])
    assert "supervisor:" in out.stderr           # human-readable report


@pytest.mark.slow
def test_supervisor_detects_stall_and_restarts(tmp_path):
    """hang@epoch=2 freezes the child (heartbeats stop); the stall
    detector kills and restarts it from the snapshot, and the run still
    finishes with the uninterrupted epoch count."""
    out, report = _run_supervised(
        tmp_path, fault_plan="hang@epoch=2",
        extra=("--max-restarts", "3", "--stall-timeout", "10"),
        timeout=300)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert _final_epoch(out.stdout) == 6
    assert report["attempts"][0]["reason"] == "stall"
    assert report["attempts"][1]["reason"] == "ok"


def test_supervisor_no_progress_cutoff(tmp_path):
    """A job whose own code dies at the same epoch on every attempt (a
    deterministic bug, not a transient fault) trips the no-progress
    cutoff instead of burning the whole retry budget."""
    out, report = _run_supervised(tmp_path,
                                  extra=("--max-restarts", "10"),
                                  workflow_src=CRASH_LOOP_SRC,
                                  timeout=300)
    assert out.returncode == EXIT_GIVEUP, (out.returncode,
                                           out.stderr[-2000:])
    assert "no epoch progress" in report["outcome"]
    # far fewer attempts than the budget of 10: the cutoff fired
    assert len(report["attempts"]) <= 4
    assert all(a["reason"] == "died" for a in report["attempts"])


def test_supervisor_report_carries_feed_counters(tmp_path):
    """ISSUE 5 observability: a supervised FUSED child publishes its
    device-feed overlap counters through the per-epoch heartbeat, and
    the supervisor's JSON exit report promotes the newest attempt's
    view to the top level (input-pipeline health without instrumenting
    the child)."""
    out, report = _run_supervised(tmp_path, extra=("--fused",))
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    feed = report["feed"]
    assert feed["batches"] > 0 and feed["bytes_h2d"] > 0
    assert "loader_block_s" in feed and "device_sync_s" in feed
    assert report["attempts"][-1]["feed"]["batches"] > 0
