"""lr_adjust policies, misc units (accumulator/histogram/zero-filler/
image-saver), forge packaging, and the scaling-efficiency harness
(SURVEY.md §2.5, §2.8)."""

import os

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.backends import NumpyDevice
from veles_tpu.forge import Forge
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.znicz.lr_adjust import LearningRateAdjust
from veles_tpu.znicz.misc_units import (Accumulator, ImageSaver,
                                        MultiHistogram, ZeroFiller)
from veles_tpu.znicz.standard_workflow import StandardWorkflow


def build(max_epochs=2, **gd):
    prng.seed_all(1234)
    loader = SyntheticClassifierLoader(
        n_classes=5, sample_shape=(6, 6), n_validation=50, n_train=200,
        minibatch_size=50, noise=0.5)
    return StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16,
                 "weights_stddev": 0.05},
                {"type": "softmax", "output_sample_shape": 5,
                 "weights_stddev": 0.05}],
        loader=loader, loss="softmax", n_classes=5,
        decision_config={"max_epochs": max_epochs, "fail_iterations": 50},
        gd_config={"learning_rate": 0.1, "gradient_moment": 0.9, **gd},
        name="MiscTest")


def test_lr_policies_math():
    from veles_tpu.znicz.lr_adjust import (exp_policy, fixed_policy,
                                           inv_policy, multistep_policy,
                                           poly_policy, step_policy)
    assert step_policy(1.0, 0.5, 10)(25) == 0.25
    assert abs(exp_policy(1.0, 0.9)(2) - 0.81) < 1e-12
    assert abs(inv_policy(1.0, 1.0, 1.0)(3) - 0.25) < 1e-12
    assert fixed_policy(0.3)(12345) == 0.3
    assert abs(poly_policy(1.0, 2.0, 100)(50) - 0.25) < 1e-12
    assert poly_policy(1.0, 2.0, 100)(200) == 0.0     # clamped past max
    ms = multistep_policy(1.0, 0.1, (4, 2))           # unsorted ok
    assert [round(ms(i), 3) for i in range(6)] == \
        [1.0, 1.0, 0.1, 0.1, 0.01, 0.01]


def test_lr_adjust_snapshot_roundtrip_rebuilds_policy():
    import pickle

    u = LearningRateAdjust(policy="poly", base=1.0, power=2.0,
                           max_iter=100)
    u.iteration = 50
    u2 = pickle.loads(pickle.dumps(u))
    assert u2.current_scale == pytest.approx(0.25)


def test_lr_adjust_drives_gd_scale_in_workflow():
    wf = build(max_epochs=2)
    lr = LearningRateAdjust(wf, policy="exp", gamma=0.9)
    lr.link_gds(wf.gds)
    # splice INTO the loop (repeater is an OR-gate: adding a second
    # loop-back edge would double-fire it): ... gds[-1] -> lr -> repeater
    wf.repeater.unlink_from(wf.gds[-1])
    lr.link_from(wf.gds[-1])
    wf.repeater.link_from(lr)
    lr.gate_skip = wf.loader.not_train  # iterations = train minibatches
    wf.initialize(device=NumpyDevice())
    wf.run()
    # 2 epochs x 4 train minibatches, minus the final cycle (end_point
    # stops the pump before the last chain tail drains — same convention
    # as the gd run_count assertions in test_mnist_functional)
    assert lr.iteration == 7
    assert wf.gds[0].lr_scale == pytest.approx(0.9 ** 6)


def test_accumulator_histogram_zerofiller():
    wf = build(max_epochs=1)
    acc = Accumulator(wf)
    acc.link_attrs(wf.evaluator, ("input", "loss"))
    acc.link_from(wf.evaluator)
    hist = MultiHistogram(wf, n_bins=8)
    hist.link_attrs(wf.forwards[0], ("input", "weights"))
    hist.link_from(wf.decision)
    wf.end_point.link_from(acc, hist)
    wf.initialize(device=NumpyDevice())
    wf.run()
    assert len(acc.values) == wf.evaluator.run_count
    assert hist.hist is not None and hist.hist.sum() == 16 * 36

    zf = ZeroFiller()
    zf.weights = wf.forwards[0].weights
    zf.mask = np.zeros((36, 16), bool)
    zf.mask[0, :] = True
    zf.run()
    assert np.all(wf.forwards[0].weights.mem[0] == 0.0)


def test_image_saver_dumps_misclassified(tmp_path):
    wf = build(max_epochs=1)
    saver = ImageSaver(wf, directory=str(tmp_path / "bad"), limit=10)
    saver.link_attrs(wf.loader, ("input", "minibatch_data"),
                     ("labels", "minibatch_labels"))
    saver.link_attrs(wf.forwards[-1], "max_idx")
    saver.link_from(wf.evaluator)
    wf.end_point.link_from(saver)
    wf.initialize(device=NumpyDevice())
    wf.run()
    files = os.listdir(tmp_path / "bad")
    assert 0 < len(files) <= 10
    assert all("_as_" in f for f in files)


def test_forge_publish_list_fetch(tmp_path):
    wf = build(max_epochs=1)
    wf.initialize(device=NumpyDevice())
    wf.run()
    zoo = Forge(str(tmp_path / "zoo"))
    zoo.publish(wf, "misc-test", author="ci",
                description="tiny fc softmax")
    entries = zoo.list()
    assert len(entries) == 1
    assert entries[0]["name"] == "misc-test"
    assert entries[0]["metrics"]["epochs"] == 1
    manifest, restored = zoo.fetch("misc-test")
    assert manifest["workflow_class"] == "StandardWorkflow"
    assert restored.decision.epoch_number == 1


def test_scaling_harness_single_device_honest():
    from veles_tpu.parallel.distributed import scaling_efficiency
    import jax
    wf = build(max_epochs=1)
    wf.initialize(device=None)
    res = scaling_efficiency(wf, mesh_devices=jax.devices()[:1],
                             batch_per_chip=50, warmup=1, steps=3)
    assert res["trivial"] is True
    assert res["scaling_efficiency"] == pytest.approx(1.0)
    assert res["samples_per_sec_per_chip_1"] > 0


def test_scaling_harness_multi_device(eight_devices):
    from veles_tpu.parallel.distributed import scaling_efficiency
    wf = build(max_epochs=1)
    wf.initialize(device=None)
    res = scaling_efficiency(wf, mesh_devices=eight_devices[:4],
                             batch_per_chip=52, warmup=1, steps=3)
    assert res["chips"] == 4
    assert res["trivial"] is False
    assert res["samples_per_sec_per_chip_n"] > 0

def test_wine_sample_trains():
    from veles_tpu.config import root
    from veles_tpu.samples.wine import create_workflow
    prng.seed_all(1234)
    root.wine.decision.max_epochs = 10
    wf = create_workflow()
    wf.initialize(device=NumpyDevice())
    wf.run()
    # 40 validation samples / 3 classes: chance ~27 errors
    assert wf.decision.best_validation_err < 15, \
        wf.decision.best_validation_err


def test_log_file_sink(tmp_path):
    """--log-file duplicates veles logging to a DEBUG-detail file while
    the console keeps its own verbosity (reference Logger file sink)."""
    import logging

    from veles_tpu.logger import (Logger, add_log_file, remove_log_file,
                                  setup_logging)
    prev_level = logging.getLogger("veles").level
    setup_logging(logging.WARNING)
    path = tmp_path / "run.log"
    handler = add_log_file(str(path))
    try:
        class Thing(Logger):
            name = "thing"

        t = Thing()
        t.debug("debug detail %d", 42)
        t.warning("warn %s", "msg")
        for h in logging.getLogger("veles").handlers:
            h.flush()
        text = path.read_text()
        assert "debug detail 42" in text
        assert "warn msg" in text
        # console verbosity stays independently adjustable
        from veles_tpu.logger import set_verbosity
        set_verbosity(2)
        assert logging.getLogger("veles").level == logging.DEBUG
    finally:
        remove_log_file(handler)
        setup_logging(prev_level)   # restores console handler level too
        logging.getLogger("veles").setLevel(prev_level)


def test_inference_server_serves_trained_model():
    """SURVEY §3.4 Python-serving slot: train, stand up the HTTP server,
    POST a batch, get calibrated predictions + argmax classes."""
    import json as _json
    import urllib.error
    import urllib.request

    from veles_tpu import prng
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.serving import InferenceServer
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    prng.seed_all(41)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(10,), n_validation=40, n_train=160,
        minibatch_size=40, noise=0.3)
    wf = StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": 16,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.05}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 5, "fail_iterations": 50},
        gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
        name="ServeWF")
    wf.run_fused()

    srv = InferenceServer(wf, max_batch=16).start()
    try:
        url = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(url + "/info", timeout=10) as r:
            info = _json.loads(r.read())
        assert info["input_shape"] == [10]
        assert info["n_classes"] == 4

        x = loader.data.mem[:8]              # validation rows
        y = loader.labels.mem[:8]
        req = _json.dumps({"inputs": x.tolist()}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                url + "/predict", data=req,
                headers={"Content-Type": "application/json"}),
                timeout=30) as r:
            resp = _json.loads(r.read())
        probs = np.asarray(resp["outputs"])
        assert probs.shape == (8, 4)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-4)
        # the trained model actually predicts (err 0 on this easy set)
        assert (np.asarray(resp["classes"]) == y).mean() >= 0.75

        # malformed request -> 400, not a crash
        bad = urllib.request.Request(url + "/predict", data=b"notjson",
                                     headers={"Content-Type": "x"})
        try:
            urllib.request.urlopen(bad, timeout=10)
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400

        # CONCURRENT requests coalesce into fewer dispatched rounds
        # (continuous batching on the slot ring) and every caller still
        # gets its own correct rows back. Deterministic: stall the
        # ring's in-flight round so the rest queue — they MUST merge
        # into at most one more round.
        import threading as _thr
        base = srv.n_dispatches
        results = {}
        release = _thr.Event()
        orig_fn = srv._fn

        def slow_fn(p, xb):
            release.wait(10)
            return orig_fn(p, xb)

        srv._fn = slow_fn

        def submit(i):
            results[i] = srv._predict_batched(
                np.asarray(x[i:i + 2], np.float32))

        threads = [_thr.Thread(target=submit, args=(i,))
                   for i in range(4)]
        try:
            for t in threads:
                t.start()
            deadline = __import__("time").time() + 2.0
            # wait until round 1 is issued (stalled inside slow_fn) and
            # the remaining requests are queued behind it
            while __import__("time").time() < deadline:
                with srv._cv:
                    n_queued = sum(len(it["x"]) for it in srv._pending)
                if srv.n_dispatches - base >= 1 and n_queued + 2 >= 8:
                    break
                __import__("time").sleep(0.01)
        finally:
            release.set()
            for t in threads:
                t.join(timeout=30)
            srv._fn = orig_fn
        assert srv.n_dispatches - base <= 2, (srv.n_dispatches, base)
        for i in range(4):
            got = np.asarray(results[i]).reshape(2, -1)
            np.testing.assert_allclose(got, probs[i:i + 2], atol=1e-5)
    finally:
        srv.stop()


def test_forge_roundtrip_moe_transformer_family(tmp_path):
    """Forge packaging handles the TPU-era unit families (attention +
    token-MoE): publish a trained workflow, fetch it, predictions
    match."""
    import jax.numpy as jnp

    from veles_tpu import prng
    from veles_tpu.forge import Forge
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    prng.seed_all(61)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(4, 8), n_validation=32, n_train=96,
        minibatch_size=32, noise=0.3)
    wf = StandardWorkflow(
        layers=[{"type": "attention", "n_heads": 2, "residual": True,
                 "weights_stddev": 0.15},
                {"type": "moe", "n_experts": 4, "hidden": 16,
                 "residual": True, "weights_stddev": 0.15},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.05}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 3, "fail_iterations": 50},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        name="ForgeTfMoE")
    wf.run_fused()

    zoo = Forge(str(tmp_path / "zoo"))
    zoo.publish(wf, "tfmoe", author="test")
    _meta, fetched = zoo.fetch("tfmoe")

    x = loader.data.mem[:8]
    def logits(w):
        ps = [{k: jnp.asarray(a.mem) for k, a in u.param_arrays().items()}
              for u in w.forwards]
        out = jnp.asarray(x)
        for u, p in zip(w.forwards, ps):
            out = u.fused_apply(p, out)
        return np.asarray(out)
    np.testing.assert_allclose(logits(fetched), logits(wf),
                               rtol=1e-6, atol=1e-7)


def test_forge_http_server_publish_list_fetch(tmp_path):
    """The zoo's client/server split (reference VelesForge service): an
    HTTP ForgeServer serves a package directory; the SAME Forge client
    verbs work against `http://` zoos — publish uploads, list reads the
    index, fetch restores the trained workflow."""
    from veles_tpu.forge import ForgeServer

    wf = build(max_epochs=1)
    wf.initialize(device=NumpyDevice())
    wf.run()

    srv = ForgeServer(str(tmp_path / "zoo"), port=0).start()
    try:
        zoo = Forge(f"http://127.0.0.1:{srv.port}")
        url = zoo.publish(wf, "http-test", author="ci")
        assert url.endswith("/pkg/http-test.forge.tar.gz")
        entries = zoo.list()
        assert [e["name"] for e in entries] == ["http-test"]
        manifest, restored = zoo.fetch("http-test")
        assert manifest["author"] == "ci"
        assert restored.decision.epoch_number == 1
        # path traversal rejected on both ends
        import pytest as _pytest
        with _pytest.raises(ValueError):
            zoo.fetch("../evil")
        import urllib.error
        import urllib.request
        with _pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/pkg/%2e%2e/x.forge.tar.gz",
                timeout=10)
    finally:
        srv.stop()


def test_compile_cache_guard(tmp_path, monkeypatch):
    """The compile-cache rule, in one place (veles_tpu/caches.py): with
    JAX_COMPILATION_CACHE_DIR set, NO directory is set in code (jax
    reads the variable itself); without it, the cache lands at the one
    fixed in-checkout path — the same on every call, never built from a
    pid, a temporary name or the time. Parity: the reference's on-disk
    kernel-binary cache (SURVEY.md §2.2)."""
    import jax

    from veles_tpu import caches

    orig_cache_dir = jax.config.jax_compilation_cache_dir
    orig_min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        # placed from outside: the helper reports it and leaves
        # jax.config's directory exactly as it found it
        outside = str(tmp_path / "outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert caches.enable_compilation_cache() == outside
        assert jax.config.jax_compilation_cache_dir == "sentinel"

        # not placed: the fixed in-checkout directory, twice the same
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = caches.enable_compilation_cache()
        assert first == caches.enable_compilation_cache()
        assert first == os.path.join(repo, ".veles_cache", "xla")
        assert jax.config.jax_compilation_cache_dir == first
        assert os.path.isdir(first)

        # the sibling caches default beside it; their env overrides stay
        from veles_tpu.ops.autotune import default_cache_path
        from veles_tpu.serving_aot import default_aot_path
        monkeypatch.delenv("VELES_SERVING_AOT_CACHE", raising=False)
        monkeypatch.delenv("VELES_AUTOTUNE_CACHE", raising=False)
        assert os.path.dirname(default_aot_path()) == caches.CACHE_ROOT
        assert os.path.dirname(default_cache_path()) == caches.CACHE_ROOT
        monkeypatch.setenv("VELES_SERVING_AOT_CACHE", "/x/aot.json")
        assert default_aot_path() == "/x/aot.json"

        # the hang-guard flags are gone with the thing they guarded
        from veles_tpu.__main__ import build_parser
        with pytest.raises(SystemExit):
            build_parser().parse_args(["wf.py", "--no-compile-cache"])
    finally:
        jax.config.update("jax_compilation_cache_dir", orig_cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          orig_min_secs)
