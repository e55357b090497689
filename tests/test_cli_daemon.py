"""CLI background/daemon mode (SURVEY.md §2.9 CLI row lists the
reference's background/daemon flag): `--daemon LOG` re-execs the same
command line detached in a new session, the launching command returns
immediately printing the background pid, and the detached process trains
to completion with stdio in the logfile."""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKFLOW_SRC = '''
from veles_tpu import prng
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.znicz.standard_workflow import StandardWorkflow

def create_workflow():
    prng.seed_all(5)
    loader = SyntheticClassifierLoader(
        n_classes=3, sample_shape=(8,), n_validation=30, n_train=90,
        minibatch_size=30, noise=0.3)
    return StandardWorkflow(
        layers=[{"type": "softmax", "output_sample_shape": 3,
                 "weights_stddev": 0.05}],
        loader=loader, loss="softmax", n_classes=3,
        decision_config={"max_epochs": 2, "fail_iterations": 99},
        gd_config={"learning_rate": 0.1},
        name="DaemonWF")

def run(load, main):
    wf, _ = load(create_workflow)
    main()
    print("DAEMON_DONE", wf.decision.epoch_number, flush=True)
'''


def _gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    except PermissionError:
        return False
    # still exists — it may be a zombie reparented to init; setsid makes
    # it a session leader so a live state check needs /proc
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().split()[2] == "Z"
    except OSError:
        return True


def test_daemon_detaches_and_finishes(tmp_path):
    wf_py = tmp_path / "daemonwf.py"
    wf_py.write_text(WORKFLOW_SRC)
    log = tmp_path / "daemon.log"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    t0 = time.time()
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu", str(wf_py), "--no-stats",
         "--daemon", str(log)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=60)
    launch_s = time.time() - t0
    assert out.returncode == 0, out.stderr
    pid = int(out.stdout.strip().splitlines()[-1])
    assert pid > 0

    # the launcher returned before training finished (detached), and
    # quickly — it must not have waited on the workflow
    assert launch_s < 30

    deadline = time.time() + 120
    while time.time() < deadline and not _gone(pid):
        time.sleep(0.5)
    assert _gone(pid), f"daemon pid {pid} still running"
    text = log.read_text()
    assert "DAEMON_DONE 2" in text, text[-2000:]
