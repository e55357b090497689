"""ISSUE 3: the static-analysis subsystem (veles_tpu/analysis/).

Three passes, each proven both ways: a seeded defect every rule must
catch, and a clean build that must produce zero errors.

- graph verifier: dangling/shadowed aliases, AND-gate cycles,
  unreachable units, endpoint reachability, read-before-write flows;
- jaxpr auditor: f64 promotion, host syncs, dropped donation, retrace
  hazards, sharding mismatch — all on CPU via jax.make_jaxpr (no
  compile);
- velint: the AST lint rules + suppression + the ratchet baseline, and
  the repo-wide `tools/velint.py --ci` gate itself (tier-1 CI smoke).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.analysis import lint, verify_workflow
from veles_tpu.analysis.findings import SEV_ERROR
from veles_tpu.analysis.graph import WorkflowVerifyError
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.units import LinkError, TrivialUnit, Unit
from veles_tpu.workflow import Repeater, Workflow
from veles_tpu.znicz.standard_workflow import StandardWorkflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules(findings):
    return sorted({f.rule for f in findings})


def build_standard(minibatch_size=32, layers=None, max_epochs=1):
    prng.seed_all(1234)
    loader = SyntheticClassifierLoader(
        n_classes=10, sample_shape=(6, 6), n_validation=64, n_train=128,
        minibatch_size=minibatch_size)
    return StandardWorkflow(
        layers=layers or [
            {"type": "all2all_tanh", "output_sample_shape": 16,
             "weights_stddev": 0.05},
            {"type": "softmax", "output_sample_shape": 10,
             "weights_stddev": 0.05},
        ],
        loader=loader, loss="softmax", n_classes=10,
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 50},
        gd_config={"learning_rate": 0.1}, name="AnalysisFixture")


# == pass 1: graph verifier ===================================================

def test_clean_standard_workflow_has_zero_findings():
    assert verify_workflow(build_standard()) == []


def test_link_attrs_validates_eagerly_naming_both_units():
    wf = Workflow(name="w")
    a = TrivialUnit(wf, name="alpha")
    b = TrivialUnit(wf, name="beta")
    with pytest.raises(LinkError) as ei:
        b.link_attrs(a, "missing_attr")
    msg = str(ei.value)
    assert "alpha" in msg and "beta" in msg and "missing_attr" in msg
    # LinkError subclasses AttributeError: legacy handlers keep working
    assert isinstance(ei.value, AttributeError)


def test_link_attrs_late_opt_out_and_dangling_alias_finding():
    wf = Workflow(name="w")
    a = TrivialUnit(wf, name="a")
    b = TrivialUnit(wf, name="b")
    b.link_attrs(a, "lazy", late=True)      # opt-out: no raise
    b.link_from(wf.start_point)
    wf.end_point.link_from(b)
    findings = verify_workflow(wf)
    assert rules(findings) == ["dangling-alias"]
    # declared late-bound: pre-initialize verification only warns (the
    # attribute is EXPECTED to appear at the source's initialize());
    # initialize(verify="error") must stay usable with late links
    assert findings[0].severity == "warn"
    wf.initialize(verify="error")
    a.lazy = 1                              # source appears -> clean
    assert verify_workflow(wf) == []
    # the same dangle WITHOUT the late marker is an error
    c = TrivialUnit(wf, name="c")
    c.__dict__["_linked_attrs"]["ghost"] = (a, "ghost")  # bypass eager
    c.link_from(b)
    findings2 = [f for f in verify_workflow(wf)
                 if f.rule == "dangling-alias"]
    assert findings2 and findings2[0].severity == SEV_ERROR


def test_shadowed_alias_warns():
    class Shadowed(TrivialUnit):
        marker = "class-attr"

    wf = Workflow(name="w")
    src = TrivialUnit(wf, name="src")
    src.marker = 7
    u = Shadowed(wf, name="u")
    u.link_attrs(src, "marker")
    found = [f for f in verify_workflow(wf) if f.rule == "shadowed-alias"]
    assert found and found[0].severity == "warn"


def test_and_gate_cycle_is_error_and_repeater_breaks_it():
    wf = Workflow(name="w")
    a = TrivialUnit(wf, name="a")
    b = TrivialUnit(wf, name="b")
    a.link_from(wf.start_point)
    b.link_from(a)
    a.link_from(b)                          # AND-gate loop: deadlock
    wf.end_point.link_from(b)
    assert "control-cycle" in rules(verify_workflow(wf))

    wf2 = Workflow(name="w2")
    r = Repeater(wf2, name="rep")
    c = TrivialUnit(wf2, name="c")
    r.link_from(wf2.start_point)
    c.link_from(r)
    r.link_from(c)                          # same loop through an OR gate
    wf2.end_point.link_from(c)
    assert verify_workflow(wf2) == []


def test_unreachable_and_endpoint_unreachable():
    wf = Workflow(name="w")
    a = TrivialUnit(wf, name="a")
    a.link_from(wf.start_point)             # end_point never linked
    stranded = TrivialUnit(wf, name="stranded")
    feeder = TrivialUnit(wf, name="feeder")
    stranded.link_from(feeder)              # island: no path from start
    findings = verify_workflow(wf)
    got = rules(findings)
    assert "unreachable" in got and "endpoint-unreachable" in got
    names = {f.unit for f in findings if f.rule == "unreachable"}
    assert any("stranded" in n for n in names)


def test_read_before_write_warns_only_without_a_producer_path():
    wf = Workflow(name="w")
    prod = TrivialUnit(wf, name="prod")
    prod.value = 0
    cons = TrivialUnit(wf, name="cons")
    cons.link_attrs(prod, "value")
    cons.link_from(wf.start_point)
    prod.link_from(cons)                    # producer fires AFTER consumer
    wf.end_point.link_from(prod)
    findings = verify_workflow(wf)
    assert rules(findings) == ["read-before-write"]
    assert all(f.severity == "warn" for f in findings)
    # reverse the order: producer upstream -> clean
    wf2 = Workflow(name="w2")
    p2 = TrivialUnit(wf2, name="p2")
    p2.value = 0
    c2 = TrivialUnit(wf2, name="c2")
    c2.link_attrs(p2, "value")
    p2.link_from(wf2.start_point)
    c2.link_from(p2)
    wf2.end_point.link_from(c2)
    assert verify_workflow(wf2) == []


def test_unwired_container_skips_reachability_rules():
    wf = Workflow(name="bare")             # fused-only style container
    TrivialUnit(wf, name="floating")
    assert verify_workflow(wf) == []


def test_initialize_verify_modes():
    wf = Workflow(name="w")
    a = TrivialUnit(wf, name="a")
    b = TrivialUnit(wf, name="b")
    a.link_from(wf.start_point)
    b.link_from(a)
    a.link_from(b)
    wf.end_point.link_from(b)
    with pytest.raises(WorkflowVerifyError) as ei:
        wf.initialize(verify="error")
    assert any(f.rule == "control-cycle" for f in ei.value.findings)
    wf.initialize(verify="warn")            # default policy: log only
    wf.initialize(verify="off")
    with pytest.raises(ValueError):
        wf.initialize(verify="nonsense")


# == pass 2: jaxpr auditor ====================================================

def audit(step, wf, **kw):
    from veles_tpu.analysis.trace import audit_fused_step
    x = wf.loader.minibatch_data.mem
    y = wf.loader.minibatch_labels.mem
    return audit_fused_step(step, x, y, **kw)


@pytest.fixture
def fused_wf():
    wf = build_standard()
    wf.initialize(device=None, verify="off")
    return wf


def test_audit_clean_local_step_zero_findings(fused_wf):
    step = fused_wf.build_fused_step()
    assert audit(step, fused_wf) == []


def test_audit_clean_dp_and_gspmd_steps(fused_wf, eight_devices):
    from veles_tpu.parallel import make_mesh
    for kw in (dict(mesh=make_mesh(eight_devices), mode="dp"),
               dict(mesh=make_mesh(eight_devices, model=2),
                    mode="gspmd")):
        step = fused_wf.build_fused_step(**kw)
        assert audit(step, fused_wf) == [], kw


def test_audit_flags_f64_promotion(fused_wf, monkeypatch):
    from jax import enable_x64
    from veles_tpu.znicz.all2all import All2AllTanh
    orig = All2AllTanh.fused_apply

    def leaky(self, params, x, *, key=None, train=True):
        # np.float64 scalar * array promotes under x64 — the classic
        # weak-type leak the auditor exists to catch pre-compile
        return orig(self, params, x, key=key, train=train) \
            * np.float64(1.0)

    monkeypatch.setattr(All2AllTanh, "fused_apply", leaky)
    step = fused_wf.build_fused_step()
    with enable_x64():
        findings = audit(step, fused_wf)
    assert "f64-promotion" in rules(findings)
    assert any(f.severity == SEV_ERROR for f in findings)


def test_audit_flags_host_sync(fused_wf, monkeypatch):
    from veles_tpu.znicz.all2all import All2AllTanh
    orig = All2AllTanh.fused_apply

    def chatty(self, params, x, *, key=None, train=True):
        jax.debug.print("x sum {}", x.sum())
        return orig(self, params, x, key=key, train=train)

    monkeypatch.setattr(All2AllTanh, "fused_apply", chatty)
    step = fused_wf.build_fused_step()
    assert "host-sync" in rules(audit(step, fused_wf))


def test_audit_flags_dropped_donation(fused_wf, monkeypatch):
    import jax.numpy as jnp

    from veles_tpu.znicz.all2all import All2AllTanh
    orig = All2AllTanh.fused_apply
    u0 = fused_wf.forwards[0]
    captured = jnp.asarray(u0.weights.mem)   # unit reads its own Array

    def const_reader(self, params, x, *, key=None, train=True):
        if self is u0:
            params = dict(params, weights=captured)
        return orig(self, params, x, key=key, train=train)

    monkeypatch.setattr(All2AllTanh, "fused_apply", const_reader)
    step = fused_wf.build_fused_step()
    assert "donation-dropped" in rules(audit(step, fused_wf))


def test_audit_flags_retrace_hazard(fused_wf):
    step = fused_wf.build_fused_step()
    state = step.init_state()
    state["lr_scale"] = 1.0                  # python float in carry
    findings = audit(step, fused_wf, state=state)
    assert "retrace-hazard" in rules(findings)
    assert any("lr_scale" in f.unit for f in findings)


def test_audit_flags_sharding_mismatch(fused_wf, eight_devices):
    from jax.sharding import PartitionSpec as P

    from veles_tpu.parallel import make_mesh
    mesh = make_mesh(eight_devices, model=4)
    step = fused_wf.build_fused_step(mesh=mesh, mode="gspmd")
    plan, flags = step._tp_plan()
    bad = [dict(d) for d in plan]
    bad[1]["weights"] = P(None, "model")     # (16, 10): 10 % 4 != 0
    step._tp_plan = lambda: (tuple(bad), flags)
    findings = audit(step, fused_wf)
    assert rules(findings) == ["sharding-mismatch"]
    assert all(f.severity == SEV_ERROR for f in findings)


def test_audit_fused_pair_geometry_seeded_and_clean():
    """ISSUE 13: the sharding-mismatch pass extends over the fused
    pair's traced step. Clean: a selected lrn_maxpool winner claiming
    an adjacent (norm, pool) pair audits with zero findings. Seeded: a
    post-init reconfiguration of the claimed pass-through pooling unit
    (its declared output Array no longer matches the fused kernel's
    geometry) is flagged as a sharding-mismatch ERROR, and the audit
    stops at the static verdict instead of crashing the trace on the
    downstream shape clash."""
    from veles_tpu.analysis.trace import audit_fused_step
    from veles_tpu.ops import variants as va
    prng.seed_all(7)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(12, 12, 3), n_validation=8,
        n_train=16, minibatch_size=4, noise=0.5)
    wf = StandardWorkflow(
        layers=[{"type": "conv_strictrelu", "n_kernels": 8, "kx": 5,
                 "ky": 5, "stride": (2, 2), "weights_stddev": 0.1},
                {"type": "norm", "n": 5},
                {"type": "max_pooling", "ksize": (2, 2)},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.1}, name="FusedAuditT")
    wf.initialize(device=None, verify="off")
    x = wf.loader.minibatch_data.mem
    y = wf.loader.minibatch_labels.mem
    prev = va.selected("lrn_maxpool")
    try:
        va.select("lrn_maxpool", "fused[rt=2,io=native,fuse=1]")
        with va.pallas_interpret():
            step = wf.build_fused_step()
            assert step.fusion_pairs()          # the claim is live
            assert audit_fused_step(step, x, y) == []
            # seeded drift: ksize edited on the live unit after init —
            # the declared output Array (built for (2, 2)) disagrees
            # with what the fused kernel would now trace
            pool = wf.forwards[2]
            pool.ksize = (4, 4)
            pool.stride = (4, 4)
            findings = audit_fused_step(step, x, y)
            assert rules(findings) == ["sharding-mismatch"]
            assert all(f.severity == SEV_ERROR for f in findings)
            assert any("fused pair" in f.message for f in findings)
    finally:
        if prev is None:
            va.clear_selection("lrn_maxpool")
        else:
            va.select("lrn_maxpool", prev)


def test_audit_nonfinite_guard_warning(fused_wf):
    step = fused_wf.build_fused_step()
    findings = audit(step, fused_wf, nonfinite_guard=False)
    assert rules(findings) == ["nonfinite-guard-off"]
    assert audit(step, fused_wf, nonfinite_guard=True) == []


def test_audit_pipeline_step(fused_wf, eight_devices):
    from veles_tpu.parallel.pipeline import make_stage_mesh
    mesh = make_stage_mesh(eight_devices[:2])
    step = fused_wf.build_pipeline_step(mesh, n_microbatches=2)
    findings = audit(step, fused_wf)
    assert "pre-vma-numerics" not in rules(findings)
    assert not [f for f in findings if f.severity == SEV_ERROR]


def test_environment_findings_parse_child_argv():
    from veles_tpu.analysis.trace import environment_findings
    fs = environment_findings(argv=["wf.py", "--pp", "4"])
    got = rules(fs)
    assert "nonfinite-guard-off" in got
    assert "pre-vma-numerics" not in got
    fs2 = environment_findings(
        argv=["wf.py", "--sp=2", "--tp=2", "--nonfinite-guard"])
    assert "pre-vma-numerics" not in rules(fs2)
    assert "nonfinite-guard-off" not in rules(fs2)
    # --debug-nans counts as a guard for the granular path
    fs3 = environment_findings(argv=["wf.py", "--debug-nans"])
    assert "nonfinite-guard-off" not in rules(fs3)


def test_supervisor_exit_report_embeds_analysis(tmp_path):
    from veles_tpu.resilience.supervisor import Supervisor
    report = tmp_path / "report.json"
    sup = Supervisor(
        [[sys.executable, "-c", "pass", "--pp", "2"]],
        snapshot_dir=str(tmp_path), report_path=str(report),
        max_restarts=0)
    assert sup.run() == 0
    data = json.loads(report.read_text())
    assert "analysis" in data
    got = {f["rule"] for f in data["analysis"]}
    assert "nonfinite-guard-off" in got


# == granular non-finite guard (ROADMAP gap closed) ===========================

def test_granular_nonfinite_guard_raises(monkeypatch):
    from veles_tpu.resilience import NonFiniteLossError
    from veles_tpu.znicz.evaluator import EvaluatorSoftmax
    wf = build_standard(max_epochs=3)
    wf.decision.nonfinite_guard = True
    wf.initialize(device=None)
    orig = EvaluatorSoftmax.xla_run

    def poisoned(self):
        orig(self)
        self.loss = float("nan")

    monkeypatch.setattr(EvaluatorSoftmax, "xla_run", poisoned)
    with pytest.raises(NonFiniteLossError):
        wf.run()


def test_granular_guard_never_rides_into_snapshots():
    import pickle
    wf = build_standard()
    wf.decision.nonfinite_guard = True       # Launcher-armed form
    restored = pickle.loads(pickle.dumps(wf.decision))
    # class attribute default again: a restored run re-opts-in via its
    # own CLI flags, never inherits the snapshot writer's
    assert restored.nonfinite_guard is False
    assert "nonfinite_guard" not in restored.__dict__


def test_granular_guard_off_trains_through(monkeypatch):
    # same poison, guard off: legacy behavior (trains on) is preserved
    from veles_tpu.znicz.evaluator import EvaluatorSoftmax
    wf = build_standard(max_epochs=1)
    wf.initialize(device=None)
    orig = EvaluatorSoftmax.xla_run

    def poisoned(self):
        orig(self)
        self.loss = float("nan")

    monkeypatch.setattr(EvaluatorSoftmax, "xla_run", poisoned)
    wf.run()                                 # completes epoch 1


# == pass 3: velint ===========================================================

def lint_rules(src):
    return sorted({f.rule for f in lint.lint_source(src)})


def test_velint_hot_sync_in_run_and_xla_run():
    src = (
        "import numpy as np\n"
        "import jax\n"
        "class U:\n"
        "    def run(self):\n"
        "        a = np.asarray(self.output.devmem())\n"
        "    def xla_run(self):\n"
        "        b = jax.device_get(self.x)\n"
        "        c = self.loss.item()\n"
    )
    findings = lint.lint_source(src)
    assert [f.rule for f in findings] == ["hot-sync"] * 3
    assert sorted(f.line for f in findings) == [5, 7, 8]


def test_velint_numpy_run_is_exempt_and_module_level_clean():
    src = (
        "import numpy as np\n"
        "class U:\n"
        "    def numpy_run(self):\n"
        "        return np.asarray(self.input.mem)\n"
        "x = np.asarray([1])\n"
    )
    assert lint.lint_source(src) == []


def test_velint_jit_in_loop():
    src = (
        "import jax\n"
        "def build(fns):\n"
        "    out = []\n"
        "    for f in fns:\n"
        "        out.append(jax.jit(f))\n"
        "    return out\n"
        "hoisted = jax.jit(len)\n"
    )
    findings = lint.lint_source(src)
    assert [f.rule for f in findings] == ["jit-in-loop"]
    assert findings[0].line == 5


def test_velint_trace_time_rules():
    src = (
        "import jax, time, random\n"
        "class U:\n"
        "    def fused_apply(self, params, x):\n"
        "        return x * random.random()\n"
        "def outer(self):\n"
        "    def step(s):\n"
        "        return s + time.time()\n"
        "    return jax.jit(step)\n"
        "def host_path():\n"
        "    return time.time()\n"          # untraced: fine
    )
    findings = lint.lint_source(src)
    assert [f.rule for f in findings] == ["trace-time"] * 2
    assert sorted(f.line for f in findings) == [4, 7]


def test_velint_trace_time_in_jitted_lambda_and_while_test():
    src = (
        "import jax, time\n"
        "class U:\n"
        "    def xla_init(self):\n"
        "        self._fn = self.jit(lambda x: x * time.time())\n"
        "def spin(x):\n"
        "    while jax.jit(len)(x) > 0:\n"
        "        x = x[1:]\n"
    )
    findings = lint.lint_source(src)
    assert sorted((f.rule, f.line) for f in findings) == [
        ("jit-in-loop", 6),       # While tests re-run every iteration
        ("trace-time", 4),        # lambda passed to self.jit IS traced
    ]


def test_velint_lock_no_with():
    src = (
        "def bad(self):\n"
        "    self._lock.acquire()\n"
        "    self.n += 1\n"
        "    self._lock.release()\n"
        "def good(self):\n"
        "    with self._lock:\n"
        "        self.n += 1\n"
    )
    findings = lint.lint_source(src)
    assert [f.rule for f in findings] == ["lock-no-with"]
    assert findings[0].line == 2


def test_velint_loader_thread_without_stop():
    """ROADMAP PR-3 open item: a loader that spawns prefetch threads
    must own a stop/join path (Workflow teardown calls every unit's
    stop() — the stop_units contract). Seeded: Thread and executor
    creation in a stop()-less loader class AND at loader module scope
    all fire."""
    src = (
        "import threading\n"
        "from concurrent.futures import ThreadPoolExecutor\n"
        "class LeakyLoader:\n"
        "    def fill(self):\n"
        "        t = threading.Thread(target=self._produce)\n"
        "        self._pool = ThreadPoolExecutor(max_workers=2)\n"
        "worker = threading.Thread(target=print)\n"
    )
    findings = lint.lint_source(src, path="veles_tpu/loader/bad.py")
    assert [f.rule for f in findings] == ["loader-thread"] * 3
    assert sorted(f.line for f in findings) == [5, 6, 7]


def test_velint_loader_thread_clean_cases():
    """Clean: a loader class WITH stop() owns its threads; identical
    code outside loader paths is not the rule's business."""
    src = (
        "import threading\n"
        "class GoodLoader:\n"
        "    def fill(self):\n"
        "        self._t = threading.Thread(target=self._produce)\n"
        "    def stop(self):\n"
        "        self._t.join()\n"
    )
    assert lint.lint_source(src, path="veles_tpu/loader/good.py") == []
    # same leaky source, non-loader path: exempt
    leaky = (
        "import threading\n"
        "class Server:\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._loop).start()\n"
    )
    assert lint.lint_source(leaky, path="veles_tpu/web_status.py") == []


def test_velint_sync_feed_in_step_driver_loop():
    """A loop that dispatches step.train/evaluate is a step-driver loop:
    host-blocking transfers inside it (np.asarray, jax.device_get,
    UNSHARDED jax.device_put) serialize H2D against compute — the
    DeviceFeed exists for exactly this (ISSUE 5)."""
    src = (
        "import numpy as np\n"
        "import jax\n"
        "def drive(step, state, batches):\n"
        "    for x, y in batches:\n"
        "        state, m = step.train(state, x, y)\n"
        "        host = np.asarray(m)\n"
        "        xd = jax.device_put(x)\n"
        "        g = jax.device_get(m)\n"
    )
    findings = lint.lint_source(src)
    assert [f.rule for f in findings] == ["sync-feed"] * 3
    assert sorted(f.line for f in findings) == [6, 7, 8]
    assert "DeviceFeed" in findings[0].message


def test_velint_sync_feed_clean_cases():
    # a loop with no step dispatch is NOT a driver loop
    src = (
        "import numpy as np\n"
        "def gather(rows):\n"
        "    out = []\n"
        "    for r in rows:\n"
        "        out.append(np.asarray(r))\n"
        "    return out\n"
    )
    assert lint.lint_source(src) == []
    # a SHARDED device_put (explicit placement arg) in a driver loop is
    # the feed's own idiom — not flagged; evaluate also marks the loop
    src2 = (
        "import jax\n"
        "def drive(step, state, batches, sh):\n"
        "    while batches:\n"
        "        x = jax.device_put(batches.pop(), sh)\n"
        "        loss, n = step.evaluate(state, x)\n"
    )
    assert lint.lint_source(src2) == []


def test_velint_hot_metric_lookup_in_hot_path():
    """hot-metric (telemetry/metrics.py contract): a per-record
    registry name lookup inside a unit run(), or a chained record on a
    freshly looked-up handle, must pre-bind instead."""
    src = (
        "class U:\n"
        "    def run(self):\n"
        "        self.reg.counter('veles_step_total').inc()\n"
        "        h = metrics.histogram('veles_step_seconds')\n"
    )
    findings = lint.lint_source(src)
    assert [f.rule for f in findings] == ["hot-metric"] * 2
    assert sorted(f.line for f in findings) == [3, 4]


def test_velint_hot_metric_record_inside_traced_fn():
    """Even a PRE-BOUND record inside a traced function is a bug: it
    fires once at trace time and freezes out of the compiled step."""
    src = (
        "import jax\n"
        "class U:\n"
        "    def fused_apply(self, x):\n"
        "        self._m_steps.inc()\n"
        "        self._m_hist.observe(0.5)\n"
        "        return x\n"
        "def build(f):\n"
        "    def traced(x):\n"
        "        m.set_total(3)\n"
        "        return x\n"
        "    return jax.jit(traced)\n"
    )
    findings = lint.lint_source(src)
    assert [f.rule for f in findings] == ["hot-metric"] * 3
    assert sorted(f.line for f in findings) == [4, 5, 9]


def test_velint_hot_metric_clean_cases():
    """Pre-bound records in the DRIVER (not a run()/traced scope) and
    registration at init time are the blessed idioms; np.histogram with
    a non-string first arg never matches the lookup pattern."""
    src = (
        "import numpy as np\n"
        "class W:\n"
        "    def __init__(self, reg):\n"
        "        self._m = reg.counter('veles_step_total')\n"
        "    def _drive(self):\n"
        "        while True:\n"
        "            self._m.inc()\n"
        "class U:\n"
        "    def run(self):\n"
        "        h, e = np.histogram(self.input, 10)\n"
        "        self._m_steps.inc()\n"      # pre-bound in a hot path:
    )                                        # allowed — no lookup
    assert lint.lint_source(src) == []


def test_velint_suppression_same_line_and_line_above():
    src = (
        "import numpy as np\n"
        "class U:\n"
        "    def run(self):\n"
        "        a = np.asarray(self.x)  # velint: disable=hot-sync\n"
        "        # velint: disable=hot-sync\n"
        "        b = np.asarray(self.y)\n"
        "        c = np.asarray(self.z)  # velint: disable=jit-in-loop\n"
    )
    findings = lint.lint_source(src)
    # only the mismatched suppression still fires
    assert len(findings) == 1 and findings[0].line == 7
    src_all = src.replace("disable=jit-in-loop", "disable=all")
    assert lint.lint_source(src_all) == []


def test_velint_baseline_is_ratchet_only():
    src = (
        "import numpy as np\n"
        "class U:\n"
        "    def run(self):\n"
        "        a = np.asarray(self.x)\n"
    )
    old = lint.lint_source(src, path="m.py")
    baseline = lint.baseline_counts(old)
    fresh, over = lint.new_findings(old, baseline)
    assert fresh == [] and over == {}        # same tree: gate passes
    worse = src + "        b = np.asarray(self.y)\n"
    fresh2, over2 = lint.new_findings(
        lint.lint_source(worse, path="m.py"), baseline)
    assert len(fresh2) == 1                  # only the NEW one fails CI
    assert over2 == {"m.py::hot-sync": 1}


def test_lazy_trace_reexports_do_not_recurse():
    # `from veles_tpu.analysis import audit_workflow` goes through the
    # package __getattr__; a from-import inside that hook recursed
    # (caught by the verify drive, not the direct-import tests)
    import veles_tpu.analysis as ana
    assert callable(ana.audit_workflow)
    assert callable(ana.audit_fused_step)
    assert callable(ana.environment_findings)
    assert hasattr(ana.trace, "iter_eqns")
    with pytest.raises(AttributeError):
        ana.no_such_symbol


# == CI gates (tier-1 smoke) ==================================================

def test_velint_pallas_magic_number_seeded():
    """A tile/block int literal assigned inside a kernel function body
    of a pallas file is a frozen tuning axis — exactly the class of
    constant the template config spaces exist to own."""
    src = (
        "def _kern_call(x):\n"
        "    row_tile = 8\n"
        "    blk_q = 512\n"
        "    n_blocks = 4\n"
        "    lanes = 128\n"          # no tile/blk/block in the name
        "    return x\n"
    )
    findings = lint.lint_source(src, path="veles_tpu/ops/pallas_kernels.py")
    assert [f.rule for f in findings] == ["pallas-magic-number"] * 3
    assert sorted(f.line for f in findings) == [2, 3, 4]
    # suppression works like every rule
    sup = src.replace("row_tile = 8",
                      "row_tile = 8  # velint: disable=pallas-magic-number")
    assert len(lint.lint_source(
        sup, path="veles_tpu/ops/pallas_kernels.py")) == 2


def test_velint_pallas_magic_number_clean_cases():
    # module-level constants are the documented space bounds — exempt
    src_mod = "_FLASH_BLK_Q = 512\n_MIN_ROW_TILE = 8\n"
    assert lint.lint_source(
        src_mod, path="veles_tpu/ops/pallas_kernels.py") == []
    # signature defaults (the incumbent seeds) are exempt
    src_sig = ("def f(x, row_tile: int = 8, blk_k=1024):\n"
               "    return x\n")
    assert lint.lint_source(
        src_sig, path="veles_tpu/ops/pallas_kernels.py") == []
    # non-literal assignments (parameters, computed tiles) are exempt
    src_param = ("def f(x, rt):\n"
                 "    row_tile = max(8, int(rt))\n"
                 "    blk_q, blk_k = x.shape\n"
                 "    return x\n")
    assert lint.lint_source(
        src_param, path="veles_tpu/ops/pallas_kernels.py") == []
    # the same magic numbers OUTSIDE a pallas file are not this rule's
    # business
    src = "def f(x):\n    row_tile = 8\n    return x\n"
    assert lint.lint_source(src, path="veles_tpu/ops/xla.py") == []
    # and the REAL kernel file is clean (the refactor parameterized
    # every axis) — the baseline must stay empty
    assert [f for f in lint.lint_file(
        os.path.join(REPO, "veles_tpu", "ops", "pallas_kernels.py"))
        if f.rule == "pallas-magic-number"] == []


def test_velint_ci_runs_clean_on_this_repo():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "velint.py"),
         "--ci"], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr


def test_verify_workflow_cli_clean_sample():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the flag rides AFTER the positional: --verify-workflow now takes
    # an optional {graph,audit} mode, so a following path would bind to
    # it (parse_intermixed_args handles the ordering)
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu",
         os.path.join(REPO, "veles_tpu", "samples", "mnist_simple.py"),
         "--verify-workflow"],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "verify-workflow: 0 error(s)" in out.stdout
    # the ISSUE-10 concurrency section: passes 4/5 run over the
    # installed package and report through the same findings stream
    # (0 on the shipped tree — the empty-baseline contract)
    assert "concurrency pass over the installed package " \
           "(0 finding(s))" in out.stdout


def test_verify_workflow_cli_audit_mode():
    """--verify-workflow=audit additionally traces the fused step with
    the jaxpr auditor (ROADMAP PR-3 open item: `audit_workflow` existed,
    the CLI wiring didn't). The audit branch prints its own traced-step
    marker — a line the graph-only mode can never emit — so this pins
    the wiring, not just behavior both modes share; still exits 0
    (a clean sample has no error findings)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu",
         os.path.join(REPO, "veles_tpu", "samples", "mnist_simple.py"),
         "--verify-workflow=audit"],
        capture_output=True, text=True, timeout=240, cwd=REPO, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "verify-workflow: 0 error(s)" in out.stdout
    # audit-only marker: proof the auditor branch actually traced
    assert "audit traced the fused step" in out.stdout
    # guard-off is emitted ONCE (environment findings), not duplicated
    # by the audit pass
    assert out.stdout.count("nonfinite-guard-off") == 1


def test_verify_workflow_cli_broken_module_exits_nonzero(tmp_path):
    broken = tmp_path / "broken_wf.py"
    broken.write_text(
        "from veles_tpu.units import TrivialUnit\n"
        "from veles_tpu.workflow import Workflow\n\n\n"
        "def create():\n"
        "    wf = Workflow(name='Broken')\n"
        "    a = TrivialUnit(wf, name='a')\n"
        "    b = TrivialUnit(wf, name='b')\n"
        "    a.link_from(wf.start_point)\n"
        "    b.link_from(a)\n"
        "    a.link_from(b)        # AND-gate cycle\n"
        "    wf.end_point.link_from(b)\n"
        "    b.link_attrs(a, 'ghost', late=True)   # dangling alias\n"
        "    return wf\n\n\n"
        "def run(load, main):\n"
        "    load(create)\n"
        "    main()\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu", str(broken),
         "--verify-workflow"],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    assert out.returncode == 1, out.stdout + out.stderr
    assert "dangling-alias" in out.stdout
    assert "control-cycle" in out.stdout
