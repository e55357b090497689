"""Failure detection / recovery (SURVEY.md §5.3): the TPU-native story is
"restart from the last snapshot" — here proven end-to-end: a real CLI
training process is SIGKILLed mid-run, and a second process resumes from
`Snapshotter.latest` and finishes, with the epoch counter continuing
from the restored state (not from zero)."""

import os
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKFLOW_SRC = '''
import numpy as np
from veles_tpu.config import root
from veles_tpu import prng
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.znicz.standard_workflow import StandardWorkflow

root.crashwf.snapshot_dir = "."

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
        decision_config={"max_epochs": 4000, "fail_iterations": 100000},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        snapshot_config={"directory": root.crashwf.snapshot_dir,
                         "prefix": "crashwf", "keep_last": 3},
        name="CrashWF")

def run(load, main):
    wf, restored = load(create_workflow)
    if restored:
        # resumed run: finish quickly so the test can assert
        wf.decision.max_epochs = wf.decision.epoch_number + 2
        wf.decision.complete <<= False
    main()
    print("FINAL", wf.decision.epoch_number, flush=True)
'''


def test_kill_and_resume_from_latest_snapshot(tmp_path):
    wf_py = tmp_path / "crashwf.py"
    wf_py.write_text(WORKFLOW_SRC)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    # phase 1: train until at least one snapshot lands, then SIGKILL
    p = subprocess.Popen(
        [sys.executable, "-m", "veles_tpu", str(wf_py), "--no-stats",
         f"root.crashwf.snapshot_dir={tmp_path}"],
        env=env, cwd=tmp_path, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    deadline = time.time() + 120
    snap = None
    try:
        while time.time() < deadline:
            snaps = [f for f in os.listdir(tmp_path)
                     if f.startswith("crashwf") and f.endswith(".gz")]
            if len(snaps) >= 2:      # ensure a COMPLETE one exists
                break
            if p.poll() is not None:
                out, err = p.communicate()
                raise AssertionError(f"train died early: {err[-2000:]}")
            time.sleep(0.3)
        else:
            raise AssertionError("no snapshot appeared in 120s")
    finally:
        if p.poll() is None:
            p.send_signal(signal.SIGKILL)   # simulated hard crash
        p.wait()

    from veles_tpu.snapshotter import Snapshotter
    snap = Snapshotter.latest(str(tmp_path), prefix="crashwf")
    assert snap is not None

    # phase 2: resume from the latest snapshot and run to completion
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu", str(wf_py), "--no-stats",
         "-s", snap, f"root.crashwf.snapshot_dir={tmp_path}"],
        env=env, cwd=tmp_path, capture_output=True, text=True,
        timeout=180)
    assert out.returncode == 0, out.stderr[-2000:]
    final = [ln for ln in out.stdout.splitlines()
             if ln.startswith("FINAL")]
    assert final, out.stdout
    final_epoch = int(final[-1].split()[1])
    # the epoch counter CONTINUED from the snapshot (>2 proves it did
    # not restart at zero: a fresh run reaching FINAL needs exactly 2)
    assert final_epoch > 2, final_epoch


def test_cli_serve_restored_snapshot(tmp_path):
    """Train -> snapshot -> `--serve -s snapshot`: the CLI serves the
    TRAINED model over HTTP (predictions beat chance on the train
    data)."""
    import json
    import urllib.request

    wf_py = tmp_path / "crashwf.py"
    wf_py.write_text(WORKFLOW_SRC)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    # quick training run that drops snapshots (reuses the recovery
    # workflow; kill after the first snapshots land)
    p = subprocess.Popen(
        [sys.executable, "-m", "veles_tpu", str(wf_py), "--no-stats",
         f"root.crashwf.snapshot_dir={tmp_path}"],
        env=env, cwd=tmp_path, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    deadline = time.time() + 120
    while time.time() < deadline:
        if len([f for f in os.listdir(tmp_path)
                if f.startswith("crashwf") and f.endswith(".gz")]) >= 2:
            break
        time.sleep(0.3)
    p.send_signal(signal.SIGKILL)
    p.wait()

    from veles_tpu.snapshotter import Snapshotter
    snap = Snapshotter.latest(str(tmp_path), prefix="crashwf")
    assert snap

    srv = subprocess.Popen(
        [sys.executable, "-m", "veles_tpu", str(wf_py), "--no-stats",
         "-s", snap, "--serve", "0",      # auto-port: no bind clashes
         f"root.crashwf.snapshot_dir={tmp_path}"],
        env=env, cwd=tmp_path, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    try:
        deadline = time.time() + 120
        line = ""
        while time.time() < deadline and srv.poll() is None:
            line = srv.stdout.readline()
            if line.startswith("SERVING"):
                break
        assert line.startswith("SERVING"), (line, srv.poll())
        url = line.split()[1]
        with urllib.request.urlopen(url + "/info", timeout=10) as r:
            info = json.loads(r.read())
        assert info["n_classes"] == 4

        # the served model must hold the SNAPSHOT's trained weights:
        # regenerate the workflow's deterministic dataset and require
        # above-chance accuracy on train rows (fresh init would sit at
        # ~25%; the snapshot had already improved twice)
        from veles_tpu.loader.synthetic import make_classification
        data, labels = make_classification((0, 40, 200), 4, (10,),
                                           noise=0.4)
        x = data[40:40 + 48]
        y = labels[40:40 + 48]
        req = json.dumps({"inputs": x.tolist()}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                url + "/predict", data=req,
                headers={"Content-Type": "application/json"}),
                timeout=30) as r:
            resp = json.loads(r.read())
        probs = np.asarray(resp["outputs"])
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-4)
        acc = (np.asarray(resp["classes"]) == y).mean()
        assert acc >= 0.5, acc
    finally:
        srv.send_signal(signal.SIGKILL)
        srv.wait()
