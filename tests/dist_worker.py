"""Subprocess body for the two-process loopback distributed test.

Plays the reference's master/slave roles (SURVEY.md §3.2) the TPU-native
way: both processes join one JAX job over DCN (loopback here), build the
SAME workflow, and train it data-parallel over the GLOBAL device mesh
through the Launcher's coordinator (-l) / worker (-m) path — gradient
averaging is the in-graph psum, not pickled deltas. Prints one JSON line
with a param digest so the parent test can assert both processes hold
bit-identical trained weights.

Not a pytest file (no test_ prefix): launched by
tests/test_distributed_two_process.py.
"""

import json
import sys

import jax

# the CPU, whatever the environment says: these workers are test doubles
jax.config.update("jax_platforms", "cpu")


def main() -> None:
    role, addr, pid = sys.argv[1], sys.argv[2], int(sys.argv[3])
    tp = int(sys.argv[4]) if len(sys.argv) > 4 else None
    sp = int(sys.argv[5]) if len(sys.argv) > 5 else None
    ep = bool(int(sys.argv[6])) if len(sys.argv) > 6 else False
    pp = (int(sys.argv[7]) or None) if len(sys.argv) > 7 else None
    attn = sys.argv[8] if len(sys.argv) > 8 else "ring"

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
        # 4 layers so --pp runs can place one stage per global device
        return StandardWorkflow(
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 16,
                 "weights_stddev": 0.1},
                {"type": "all2all_tanh", "output_sample_shape": 12,
                 "weights_stddev": 0.1},
                {"type": "all2all_tanh", "output_sample_shape": 12,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.05},
            ],
            loader=loader, loss="softmax", n_classes=4,
            decision_config={"max_epochs": 3, "fail_iterations": 50},
            gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
            name="DistDP")

    def transformer_factory():
        # ring attention with the seq axis SPANNING processes: the
        # long-context path over the DCN analog
        from veles_tpu.config import root
        from veles_tpu.samples.char_transformer import create_workflow
        prng.seed_all(4321)
        root.char_transformer.loader.minibatch_size = 16
        root.char_transformer.loader.seq_len = 16
        root.char_transformer.embed = 16
        root.char_transformer.n_heads = 2
        root.char_transformer.ffn = 24
        root.char_transformer.moe_experts = 0
        root.char_transformer.decision.max_epochs = 2
        root.char_transformer.decision.fail_iterations = 50
        root.char_transformer.parallel_mode = attn
        return create_workflow()

    def moe_factory():
        # expert parallelism across the process boundary: 8 experts over
        # the 8-device data axis (1 expert resident per device)
        import tempfile

        from veles_tpu.config import root
        from veles_tpu.samples.moe import create_workflow
        from veles_tpu.snapshotter import Snapshotter
        prng.seed_all(4321)
        root.moe.loader.minibatch_size = 64
        root.moe.loader.n_train = 256
        root.moe.loader.n_validation = 64
        root.moe.decision.max_epochs = 2
        root.moe.decision.fail_iterations = 50
        wf = create_workflow()
        # snapshotting ON: the improved-epoch write_back gathers the
        # cross-process expert shards — every process must enter that
        # collective (workers get dry_run=True from the Launcher); this
        # exercises the EP/TP + snapshot deadlock regression
        snap = Snapshotter(wf, prefix="ep_dist",
                           directory=tempfile.mkdtemp(prefix="ep_snap_"),
                           keep_last=1)
        snap.link_decision(wf.decision)
        wf.snapshotter = snap
        return wf

    launcher = Launcher(
        listen=addr if role == "coordinator" else "",
        master=addr if role == "worker" else "",
        process_id=pid, n_processes=2, stats=False, tp=tp, sp=sp, ep=ep,
        pp=pp)
    launcher.load(moe_factory if ep
                  else transformer_factory if (sp or 1) > 1 else factory)
    rc = launcher.main()

    wf = launcher.workflow
    # digest EVERY param of every forward (attention units carry
    # wq/wk/wv/wo, not `weights`)
    sums, hexes = [], []
    for u in wf.forwards:
        for pname, arr in sorted(u.param_arrays().items()):
            if not arr:
                continue
            sums.append(float(np.abs(arr.mem).sum()))
            hexes.append(np.asarray(arr.mem).tobytes().hex()[:32])
    snap = getattr(wf, "snapshotter", None)
    digest = {
        "role": role, "rc": rc,
        "n_global_devices": jax.device_count(),
        "n_local_devices": jax.local_device_count(),
        "best_validation_err": int(wf.decision.best_validation_err),
        "param_sums": sums,
        "param_digest": hexes,
        "snapshot": (snap.destination if snap is not None else None),
    }
    print("DIGEST " + json.dumps(digest), flush=True)


if __name__ == "__main__":
    main()
