"""Plotter units + renderer + results publishing (SURVEY.md §2.5): specs
render off-thread to files, plotting units read through data links, and a
workflow wired with epoch-gated plotters trains unaffected."""

import json
import os

import numpy as np

from veles_tpu import prng
from veles_tpu.backends import NumpyDevice
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.plotter import GraphicsRenderer
from veles_tpu.plotting_units import (AccumulatingPlotter, MatrixPlotter,
                                      Weights2D)
from veles_tpu.publishing import write_results
from veles_tpu.znicz.standard_workflow import StandardWorkflow


def build(tmp_path, max_epochs=2):
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
        gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
        name="PlotTest")


def test_renderer_renders_specs_offthread(tmp_path):
    r = GraphicsRenderer(str(tmp_path))
    r.start()
    r.publish({"name": "curve", "kind": "lines",
               "series": {"train": [3, 2, 1]}})
    r.publish({"name": "mat", "kind": "matrix",
               "data": [[1, 0], [0, 1]]})
    r.publish({"name": "tiles", "kind": "images",
               "data": [np.eye(4).tolist()] * 3})
    r.stop()
    files = sorted(os.listdir(tmp_path))
    assert len(r.rendered) == 3, r.rendered
    assert any(f.startswith("curve") for f in files)
    assert any(f.startswith("mat") for f in files)
    assert any(f.startswith("tiles") for f in files)


def test_workflow_with_plotters_and_results(tmp_path):
    wf = build(tmp_path, max_epochs=3)
    renderer = GraphicsRenderer(str(tmp_path / "plots"))
    renderer.start()

    err_plot = AccumulatingPlotter(wf, plot_name="valid_err",
                                   label="valid", renderer=renderer)
    # read the decision's best validation error each epoch
    err_plot.link_attrs(wf.decision, ("input", "best_validation_err"))
    conf_plot = MatrixPlotter(wf, plot_name="confusion", renderer=renderer)
    conf_plot.link_attrs(wf.evaluator, ("input", "confusion_matrix"))
    w_plot = Weights2D(wf, plot_name="weights", limit=9, renderer=renderer)
    w_plot.link_attrs(wf.forwards[0], ("input", "weights"))

    # fire once per epoch: after the decision, gated on epoch end; also
    # wire them BEFORE end_point so the final epoch's plots render before
    # the pump stops (pulses queued after end_point are dropped)
    for p in (err_plot, conf_plot, w_plot):
        p.link_from(wf.decision)
        p.gate_skip = ~wf.loader.epoch_ended
        wf.end_point.link_from(p)

    wf.initialize(device=NumpyDevice())
    wf.run()
    renderer.stop()
    assert err_plot.run_count == 3      # once per epoch
    assert len(err_plot.values) == 3
    plots = os.listdir(tmp_path / "plots")
    assert any(f.startswith("valid_err") for f in plots)
    assert any(f.startswith("confusion") for f in plots)
    assert any(f.startswith("weights") for f in plots)

    out = write_results(wf, str(tmp_path / "results.json"))
    res = json.load(open(out))
    assert res["epochs"] == 3
    assert res["best_validation_err"] is not None
    assert any(u["name"] == "repeater" for u in res["units"])


def test_standard_workflow_plot_config_granular_and_fused(tmp_path):
    """plot_config wires the reference's standard plot set; error curves
    accumulate one point per epoch in BOTH granular and fused modes."""
    from veles_tpu import prng
    from veles_tpu.backends import XLADevice
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    def build():
        prng.seed_all(31)
        loader = SyntheticClassifierLoader(
            n_classes=4, sample_shape=(8,), n_validation=32, n_train=96,
            minibatch_size=32, noise=0.4)
        return StandardWorkflow(
            layers=[{"type": "all2all_tanh", "output_sample_shape": 12,
                     "weights_stddev": 0.1},
                    {"type": "softmax", "output_sample_shape": 4,
                     "weights_stddev": 0.05}],
            loader=loader, loss="softmax", n_classes=4,
            decision_config={"max_epochs": 3, "fail_iterations": 50},
            gd_config={"learning_rate": 0.1, "gradient_moment": 0.9},
            plot_config={"error_curve": True, "confusion": True,
                         "weights": True},
            name="PlotWF")

    wf = build()
    assert len(wf.plotters) == 4          # 2 curves + confusion + weights
    wf.initialize(device=XLADevice())
    wf.run()
    curves = [p for p in wf.plotters if hasattr(p, "values")]
    assert all(len(p.values) == 3 for p in curves), \
        [(p.label, p.values) for p in curves]
    # validation curve tracks the decision's per-epoch metric
    val = next(p for p in curves if p.label == "validation")
    assert val.values[-1] == wf.decision.epoch_metrics[1]

    # fused mode accumulates the VALIDATION confusion matrix too (via
    # step.confusion): the MatrixPlotter publishes a real heatmap each
    # epoch instead of silently skipping an all-zeros matrix — route the
    # default renderer at a fresh dir to observe the artifact
    from veles_tpu import plotter as plotter_mod
    saved_renderer = plotter_mod._default_renderer
    r2 = GraphicsRenderer(str(tmp_path / "fusedplots"))
    r2.start()
    plotter_mod._default_renderer = r2
    try:
        wf2 = build()
        wf2.run_fused()
        curves2 = [p for p in wf2.plotters if hasattr(p, "values")]
        assert all(len(p.values) == 3 for p in curves2)
    finally:
        r2.stop()
        plotter_mod._default_renderer = saved_renderer
    rendered = os.listdir(tmp_path / "fusedplots")
    assert any(f.startswith("confusion") for f in rendered), rendered


def test_renderer_process_mode(tmp_path):
    """Reference graphics_client isolation: a renderer SUBPROCESS consumes
    pickled specs over a pipe and leaves the artifacts on disk; merged
    line series and clear_series ride the same queue."""
    r = GraphicsRenderer(str(tmp_path), process=True)
    r.start()
    r.publish({"name": "pcurve", "kind": "lines",
               "series": {"train": [3.0, 2.0, 1.0]}})
    r.publish({"name": "pcurve", "kind": "lines",
               "series": {"validation": [4.0, 3.0, 2.0]}})
    r.publish({"name": "pmat", "kind": "matrix",
               "data": np.eye(4)})
    r.stop()
    names = {p.name for p in tmp_path.iterdir()}
    assert any(n.startswith("pcurve.") for n in names), names
    assert any(n.startswith("pmat.") for n in names), names
    # headless path (no matplotlib) writes the MERGED series json; with
    # matplotlib the contract is just the png's existence
    curve = tmp_path / "pcurve.json"
    if curve.exists():
        spec = json.loads(curve.read_text())
        assert set(spec["series"]) == {"train", "validation"}


def test_write_report_html(tmp_path):
    """--report publisher: the HTML report embeds headline metrics, the
    per-unit table, the config snapshot, and rendered plot images."""
    from veles_tpu.plotter import GraphicsRenderer
    from veles_tpu.plotting_units import AccumulatingPlotter
    from veles_tpu.publishing import write_report

    wf = build(tmp_path)
    r = GraphicsRenderer(str(tmp_path / "plots"))
    r.start()
    p = AccumulatingPlotter(wf, plot_name="epoch_err", label="validation",
                            renderer=r)
    p.link_attrs(wf.decision, ("input", "best_validation_err"))
    wf.initialize(device=NumpyDevice())
    wf.run()
    p.run()
    r.stop()
    out = write_report(wf, str(tmp_path / "report.html"),
                       plots_dir=str(tmp_path / "plots"))
    text = open(out).read()
    assert "best_validation_err" in text
    assert "root config snapshot" in text
    assert "PlotTest" in text
    # with matplotlib present a png was rendered and embedded
    import importlib.util
    if importlib.util.find_spec("matplotlib"):
        assert "data:image/png;base64," in text


def test_tensorboard_scalar_sink(tmp_path):
    """SURVEY.md §5.5 TPU-equiv: the plotter API also writes TensorBoard
    scalars. Each 'lines' spec's new points land once (no rewrites on
    re-publish), tagged <plot>/<label>, readable by the TB event loader."""
    import importlib.util

    import pytest
    if importlib.util.find_spec("torch") is None \
            or importlib.util.find_spec("tensorboard") is None:
        pytest.skip("tensorboard sink is optional; torch/tb not installed")
    # import the sink's dependency HERE: its first import takes tens of
    # seconds on a loaded box, and inside the render thread that races
    # stop()'s 30 s join (the writer is then never closed: no event file)
    import torch.utils.tensorboard  # noqa: F401
    # (the root.common.tensorboard_dir -> get_renderer path is covered by
    # the CLI drives; this test exercises the renderer arg directly)
    wf = build(tmp_path, max_epochs=3)
    r = GraphicsRenderer(str(tmp_path / "plots"),
                         tensorboard_dir=str(tmp_path / "tb"))
    r.start()
    p = AccumulatingPlotter(wf, plot_name="err", label="validation",
                            renderer=r)
    p.link_attrs(wf.decision, ("input", "best_validation_err"))
    p.link_from(wf.decision)
    p.gate_skip = ~wf.loader.epoch_ended
    wf.end_point.link_from(p)
    wf.initialize(device=NumpyDevice())
    wf.run()
    r.stop()

    from tensorboard.backend.event_processing.event_file_loader import \
        EventFileLoader
    files = [f for f in (tmp_path / "tb").rglob("*")
             if "tfevents" in f.name]
    assert files, list((tmp_path / "tb").rglob("*"))
    points = {}
    for f in files:
        for ev in EventFileLoader(str(f)).Load():
            for v in getattr(ev.summary, "value", []):
                if v.tag == "err/validation":
                    points[ev.step] = v.simple_value
    assert sorted(points) == [0, 1, 2], points


def test_no_plot_flag_disables_plotters(tmp_path):
    """Reference CLI parity: --no-plot (root.common.plotting_disabled)
    turns plotters into no-ops — no specs, no renderer artifacts."""
    from veles_tpu.config import root

    root.common.plotting_disabled = 1
    try:
        wf = build(tmp_path, max_epochs=2)
        r = GraphicsRenderer(str(tmp_path / "plots"))
        r.start()
        p = AccumulatingPlotter(wf, plot_name="err", label="validation",
                                renderer=r)
        p.link_attrs(wf.decision, ("input", "best_validation_err"))
        p.link_from(wf.decision)
        p.gate_skip = ~wf.loader.epoch_ended
        wf.end_point.link_from(p)
        wf.initialize(device=NumpyDevice())
        wf.run()
        r.stop()
        assert r.rendered == [], r.rendered
        assert not (tmp_path / "plots").exists() \
            or not any((tmp_path / "plots").iterdir())
    finally:
        root.common.plotting_disabled = 0
