"""Subprocess body for the distributed fault-tolerance e2e test.

Same two-process loopback DP stack as dist_worker.py, plus the fault
model under test (SURVEY.md §5.3: slave drop -> restart-from-snapshot):
the workflow snapshots on improvement (coordinator-only, the Launcher's
rule), and a run may be handed a snapshot path to RESUME from instead of
building fresh. Prints one DIGEST json line on completion.

Args: role addr process_id snapshot_dir resume_path("-" = fresh) max_epochs
Not a pytest file (no test_ prefix).
"""

import json
import sys

import jax

# the CPU, whatever the environment says: these workers are test doubles
jax.config.update("jax_platforms", "cpu")


def main() -> None:
    role, addr, pid = sys.argv[1], sys.argv[2], int(sys.argv[3])
    snap_dir, resume, max_epochs = (sys.argv[4], sys.argv[5],
                                    int(sys.argv[6]))

    import numpy as np

    from veles_tpu import prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    def factory():
        prng.seed_all(4321)  # same seed everywhere -> same init + data
        loader = SyntheticClassifierLoader(
            n_classes=4, sample_shape=(8,), n_validation=32, n_train=128,
            minibatch_size=32, noise=0.3)
        return StandardWorkflow(
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 16,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.05},
            ],
            loader=loader, loss="softmax", n_classes=4,
            decision_config={"max_epochs": max_epochs,
                             "fail_iterations": 50},
            gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
            snapshot_config={"directory": snap_dir, "prefix": "ftwf",
                             "compression": "gz"},
            name="DistFT")

    launcher = Launcher(
        snapshot="" if resume == "-" else resume,
        listen=addr if role == "coordinator" else "",
        master=addr if role == "worker" else "",
        process_id=pid, n_processes=2, stats=False)
    launcher.load(factory)
    wf = launcher.workflow
    if launcher.snapshot_loaded:
        # restored mid-job: clear the stop gate and keep the SAME epoch
        # budget so the resumed trajectory ends where run A ended
        wf.decision.max_epochs = max_epochs
        wf.decision.complete <<= False
    rc = launcher.main()

    digest = {
        "role": role, "rc": rc, "resumed": launcher.snapshot_loaded,
        "epoch": int(wf.decision.epoch_number),
        "best_validation_err": int(wf.decision.best_validation_err),
        "param_digest": [np.asarray(u.weights.mem).tobytes().hex()[:32]
                         for u in wf.forwards],
    }
    print("DIGEST " + json.dumps(digest), flush=True)


if __name__ == "__main__":
    main()
