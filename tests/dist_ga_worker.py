"""Subprocess body for the cluster-genetics tests: a FitnessQueueWorker
process leasing GA individuals from the test's coordinator.

Modes:
- `work`:  evaluate the analytic fitness, record each evaluated payload
           into `record_path` (proof the individual ran IN THIS PROCESS),
           post results until the server says done. A fourth argument
           is the seconds one evaluation takes (0 without it): a worker
           that answers at once can take a whole round before a slower
           one has polled twice.
- `die`:   lease ONE task and exit(1) WITHOUT posting a result — the
           lost-slave case; the coordinator must re-issue the lease.
- `member`: ensemble-member mode — train a tiny real workflow with the
           leased seed and post the trained-workflow pickle back as the
           artifact.

Not a pytest file (no test_ prefix): launched by
tests/test_distributed_genetics.py.
"""

import json
import os
import sys


def main() -> None:
    mode, port = sys.argv[1], int(sys.argv[2])
    record_path = sys.argv[3] if len(sys.argv) > 3 else ""
    token = os.environ.get("VELES_WEB_TOKEN") or None

    from veles_tpu.task_queue import FitnessQueueWorker

    if mode == "die":
        # lease one task by hand (poll until one is queued), then vanish
        # without posting
        import time
        w = FitnessQueueWorker("127.0.0.1", port, lambda p: 0.0,
                               token=token)
        deadline = time.time() + 15
        got = None
        while time.time() < deadline:
            got = w._request("GET", "/task")
            if got and got.get("task"):
                break
            time.sleep(0.1)
        assert got and got.get("task"), got
        with open(record_path, "w") as f:
            json.dump(got["task"], f)
        os._exit(1)

    if mode == "member":
        # the PRODUCTION worker entry (ensemble.member_worker), fed a
        # factory that also records which process trained each member
        from veles_tpu import prng
        from veles_tpu.ensemble import member_worker
        from veles_tpu.loader.synthetic import SyntheticClassifierLoader
        from veles_tpu.znicz.standard_workflow import StandardWorkflow

        def factory(seed):
            prng.seed_all(seed)
            loader = SyntheticClassifierLoader(
                n_classes=4, sample_shape=(8,), n_validation=32,
                n_train=128, minibatch_size=32, noise=0.3)
            wf = StandardWorkflow(
                layers=[{"type": "all2all_tanh",
                         "output_sample_shape": 16,
                         "weights_stddev": 0.1},
                        {"type": "softmax", "output_sample_shape": 4,
                         "weights_stddev": 0.05}],
                loader=loader, loss="softmax", n_classes=4,
                decision_config={"max_epochs": 2, "fail_iterations": 9},
                gd_config={"learning_rate": 0.1,
                           "gradient_moment": 0.9},
                name=f"Member{seed}")
            wf.initialize(device=None)
            wf.run()
            with open(record_path, "a") as f:
                f.write(f"{seed} pid={os.getpid()}\n")
            return wf

        member_worker("127.0.0.1", port, factory, token=token)
        return

    assert mode == "work"
    eval_s = float(sys.argv[4]) if len(sys.argv) > 4 else 0.0

    def fitness(payload):
        import time
        time.sleep(eval_s)
        with open(record_path, "a") as f:
            f.write(json.dumps({"payload": payload,
                                "pid": os.getpid()}) + "\n")
        return (payload["x"] - 3.0) ** 2

    # signal readiness: imports (jax) take seconds, and the test must
    # not start the submit round until this process can compete for
    # leases
    with open(record_path + ".ready", "w") as f:
        f.write(str(os.getpid()))
    FitnessQueueWorker("127.0.0.1", port, fitness, token=token,
                       poll_s=0.05).run()


if __name__ == "__main__":
    main()
