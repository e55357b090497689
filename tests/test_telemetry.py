"""Unified telemetry plane (veles_tpu/telemetry; docs/OBSERVABILITY.md).

- tracer: ring-buffer bounds, span recording, Chrome-trace schema; the
  GOLDEN overlap test — an 8-device CPU-mesh fused dp run's trace.json
  is Perfetto-loadable, spans nest, and batch k+1's `feed.device_put`
  span overlaps step k's in-flight `step` span (the PR-5 overlap made
  VISIBLE instead of inferred from counters);
- profile windows: --profile-window N:M brackets exactly those driver
  steps; POST-/profile-style request() opens at the next boundary;
- metrics: registry semantics, the Prometheus exposition parsed by a
  STRICT text-format parser (HELP/TYPE per family, counter naming,
  cumulative histogram buckets ending at le="+Inf" == _count, label
  escaping), JSONL sink rotation, feed/mem mirrors;
- endpoints: GET /metrics on web_status (token-guarded), serving and
  the cluster coordinator (fleet-aggregated) all serve parseable
  exposition with the step/feed/mem/restart families present;
  POST /profile is authed + bounded-body (the task_queue precedent);
- web_status cluster table surfaces the feed/mem heartbeat payloads;
- CLI: --trace/--profile-window validation (the --feed-ahead
  precedent) and the trace-producing CLI smoke.
"""

import json
import math
import re
import threading
import time

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.telemetry import metrics, tracer

# -- shared fixtures ----------------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry state is process-global by design (one registry, one
    tracer); every test starts and ends detached."""
    tracer.uninstall()
    tracer.reset_profile_controller()
    metrics.reset_default_registry()
    metrics.uninstall_jsonl()
    yield
    tracer.uninstall()
    tracer.reset_profile_controller()
    metrics.reset_default_registry()
    metrics.uninstall_jsonl()


def make_workflow(max_epochs=3, minibatch=16, n_train=64):
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    prng.seed_all(13)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(6,), n_validation=minibatch,
        n_train=n_train, minibatch_size=minibatch, shuffle_train=False)
    return StandardWorkflow(
        layers=[{"type": "all2all_tanh", "output_sample_shape": 12,
                 "weights_stddev": 0.1},
                {"type": "softmax", "output_sample_shape": 4,
                 "weights_stddev": 0.1}],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 50},
        gd_config={"learning_rate": 0.1}, name="TelemetryWF")


# -- strict Prometheus text-format parser (the exposition contract) -----------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(?:\{{(.*)\}})? (-?(?:[0-9.e+-]+|NaN|\+Inf|-Inf))$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text):
    """Strict parse of text format 0.0.4; raises AssertionError on any
    contract violation. Returns {family: {"type", "help", "samples":
    [(name, labels-dict, value)]}}."""
    families = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_ = rest.partition(" ")
            assert re.fullmatch(_NAME, name), f"{lineno}: bad name"
            families.setdefault(name, {"samples": []})["help"] = help_
        elif line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram"), \
                f"{lineno}: bad type {kind!r}"
            fam = families.setdefault(name, {"samples": []})
            assert "type" not in fam, f"{lineno}: duplicate TYPE {name}"
            assert not fam["samples"], \
                f"{lineno}: TYPE after samples for {name}"
            fam["type"] = kind
        elif line.startswith("#"):
            continue
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"{lineno}: unparseable sample {line!r}"
            sname, rawlabels, rawval = m.groups()
            labels = {}
            if rawlabels:
                parts = []
                for lm in _LABEL_RE.finditer(rawlabels):
                    labels[lm.group(1)] = lm.group(2)
                    parts.append(lm.group(0))
                assert ",".join(parts) == rawlabels.rstrip(","), \
                    f"{lineno}: malformed labels {rawlabels!r}"
            value = float(rawval.replace("+Inf", "inf")
                          .replace("-Inf", "-inf"))
            base = sname
            for suffix in ("_bucket", "_sum", "_count"):
                trimmed = sname[:-len(suffix)] \
                    if sname.endswith(suffix) else None
                if trimmed and families.get(trimmed, {}) \
                        .get("type") == "histogram":
                    base = trimmed
                    break
            assert base in families and "type" in families[base], \
                f"{lineno}: sample {sname} without a TYPE"
            families[base]["samples"].append((sname, labels, value))
    # semantic checks
    for name, fam in families.items():
        kind = fam.get("type")
        assert kind, f"{name}: no TYPE"
        if kind == "counter":
            assert name.endswith("_total"), f"{name}: counter naming"
            for sname, _, v in fam["samples"]:
                assert v >= 0 and math.isfinite(v), \
                    f"{sname}: counter value {v}"
        if kind == "histogram":
            by_labels = {}
            for sname, labels, v in fam["samples"]:
                key = tuple(sorted((k, val) for k, val in
                            labels.items() if k != "le"))
                by_labels.setdefault(key, {"buckets": [], "sum": None,
                                           "count": None})
                slot = by_labels[key]
                if sname.endswith("_bucket"):
                    slot["buckets"].append(
                        (float(labels["le"].replace("+Inf", "inf")),
                         v))
                elif sname.endswith("_sum"):
                    slot["sum"] = v
                elif sname.endswith("_count"):
                    slot["count"] = v
            for key, slot in by_labels.items():
                assert slot["sum"] is not None, f"{name}: no _sum"
                assert slot["count"] is not None, f"{name}: no _count"
                buckets = sorted(slot["buckets"])
                assert buckets, f"{name}: no buckets"
                assert buckets[-1][0] == math.inf, f"{name}: no +Inf"
                assert buckets[-1][1] == slot["count"], \
                    f"{name}: +Inf != _count"
                cum = [v for _, v in buckets]
                assert cum == sorted(cum), \
                    f"{name}: buckets not cumulative"
    return families


# -- tracer core --------------------------------------------------------------


def test_tracer_ring_bounds_and_drop_count():
    tr = tracer.Tracer(capacity=256)
    for i in range(300):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 256
    assert tr.dropped == 44
    # oldest dropped, newest kept
    names = [e[0] for e in tr.events()]
    assert names[0] == "s44" and names[-1] == "s299"


def test_tracer_export_schema(tmp_path):
    tr = tracer.Tracer(512)
    with tr.span("outer", "cat"):
        with tr.span("inner", "cat"):
            pass
    tr.instant("mark")
    path = tr.export(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    assert doc["otherData"]["dropped"] == 0
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    for e in xs:
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid",
                          "tid"}
    inner = next(e for e in xs if e["name"] == "inner")
    outer = next(e for e in xs if e["name"] == "outer")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] \
        + 1e-3
    marks = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert marks and marks[0]["name"] == "mark"
    # thread metadata present (Perfetto track names)
    assert any(e["ph"] == "M" for e in doc["traceEvents"])


def test_tracer_add_span_uses_perf_counter_clock():
    tr = tracer.Tracer(64)
    t0 = time.perf_counter()
    time.sleep(0.01)
    t1 = time.perf_counter()
    tr.add_span("timed", "cat", t0, t1)
    (name, _cat, _ts, dur, _tid, ph, seq) = tr.events()[0]
    assert name == "timed" and ph == "X" and seq is None
    assert dur == pytest.approx((t1 - t0) * 1e6, rel=0.01)


def test_install_is_idempotent_and_uninstall_detaches():
    a = tracer.install()
    b = tracer.install()
    assert a is b and tracer.active() is a
    assert tracer.uninstall() is a
    assert tracer.active() is None


# -- the golden trace: fused dp run on the 8-device CPU mesh ------------------


def test_trace_golden_fused_dp_overlap(tmp_path, eight_devices):
    """The acceptance artifact: a fused dp run on the 8-device CPU mesh
    produces a Perfetto-loadable trace.json in which (a) spans nest
    (feed.device_put inside feed.produce on one thread) and (b) the
    batch-k+1 device_put span OVERLAPS the step-k in-flight span — the
    H2D-under-compute overlap as a picture."""
    import jax

    from veles_tpu.parallel.mesh import make_mesh
    tr = tracer.install()
    wf = make_workflow(max_epochs=3)
    wf.initialize(device=None)
    mesh = make_mesh(jax.devices(), data=8)
    wf.run_fused(mesh=mesh, mode="dp")
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    # Perfetto-loadable: the JSON-object form with a traceEvents array
    # of ph/ts/dur events (the chrome://tracing contract)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert {"feed.next", "feed.produce", "feed.device_put",
            "loader.run", "train.dispatch", "step", "decision",
            "device_sync", "feed.prefetch"} <= names
    # (a) nesting: every device_put lies inside a feed.produce span on
    # the same thread
    produces = [e for e in xs if e["name"] == "feed.produce"]
    puts = [e for e in xs if e["name"] == "feed.device_put"]
    assert puts and produces
    for p in puts:
        assert any(pr["tid"] == p["tid"]
                   and pr["ts"] - 1e-3 <= p["ts"]
                   and p["ts"] + p["dur"]
                   <= pr["ts"] + pr["dur"] + 1e-3
                   for pr in produces), "device_put not nested"
    # (b) overlap: some batch's device_put rides inside an in-flight
    # step window (prefetch after dispatch, before the next dispatch)
    steps = [e for e in xs if e["name"] == "step"]
    assert any(s["ts"] <= p["ts"] < s["ts"] + s["dur"]
               for p in puts for s in steps), \
        "no device_put span overlaps an executing step span"
    # trace flows through the production loop: dispatch spans count
    # matches the driver's step counter in the one registry
    reg = metrics.default_registry()
    n_steps = reg.counter("veles_step_total").value
    assert n_steps == sum(1 for e in xs
                          if e["name"].endswith(".dispatch"))
    assert wf.decision.epoch_number == 3       # training unaffected


# -- profile windows ----------------------------------------------------------


class _FakeProfiler:
    def __init__(self):
        self.calls = []

    def start(self, out_dir):
        self.calls.append(("start", out_dir))

    def stop(self):
        self.calls.append(("stop",))


def test_profile_window_brackets_requested_steps(tmp_path):
    fake = _FakeProfiler()
    ctl = tracer.ProfileController(start_fn=fake.start,
                                   stop_fn=fake.stop)
    ctl.arm(2, 4, str(tmp_path / "pw"))
    for k in range(8):
        ctl.on_step(k)
    ctl.finalize()
    assert fake.calls == [("start", str(tmp_path / "pw")), ("stop",)]
    assert ctl.windows == [{"dir": str(tmp_path / "pw"),
                            "first_step": 2, "last_step": 4,
                            "wall_s": ctl.windows[0]["wall_s"]}]


def test_profile_request_opens_at_next_boundary(tmp_path):
    """The POST /profile path: a live run gets a window of K steps
    starting at the next step boundary."""
    fake = _FakeProfiler()
    ctl = tracer.ProfileController(start_fn=fake.start,
                                   stop_fn=fake.stop)
    ctl.on_step(0)
    armed = ctl.request(3, str(tmp_path / "live"))
    assert armed == {"steps": 3, "dir": str(tmp_path / "live")}
    for k in range(1, 8):
        ctl.on_step(k)
    assert fake.calls == [("start", str(tmp_path / "live")), ("stop",)]
    assert ctl.windows[0]["first_step"] == 1
    assert ctl.windows[0]["last_step"] == 3


def test_profile_window_failed_start_drops_window(tmp_path):
    """A start that failed once (e.g. whole-run -p profiling already
    active) fails every step the same way: the window is dropped after
    ONE error record instead of retrying per step."""
    calls = []

    def bad_start(d):
        calls.append(d)
        raise RuntimeError("profiler already active")

    ctl = tracer.ProfileController(start_fn=bad_start,
                                   stop_fn=lambda: None)
    ctl.arm(2, 100_000, str(tmp_path))
    for k in range(2, 50):
        ctl.on_step(k)
    assert len(calls) == 1
    assert len(ctl.windows) == 1 and "error" in ctl.windows[0]
    assert ctl._window is None and not ctl._hot


def test_profile_window_missed_is_dropped_and_run_end_closes(tmp_path):
    fake = _FakeProfiler()
    ctl = tracer.ProfileController(start_fn=fake.start,
                                   stop_fn=fake.stop)
    ctl.arm(2, 3, str(tmp_path))
    ctl.on_step(10)                      # resumed past the window
    assert fake.calls == []
    ctl.arm(11, 99, str(tmp_path))       # window outlives the run
    ctl.on_step(11)
    ctl.finalize()
    assert fake.calls == [("start", str(tmp_path)), ("stop",)]


def test_profile_window_drives_jax_profiler_through_run(tmp_path):
    """Driver integration: the fused loop calls on_step/finalize — an
    armed window sees exactly the configured step bracket."""
    fake = _FakeProfiler()
    ctl = tracer.profile_controller()
    ctl._start_fn, ctl._stop_fn = fake.start, fake.stop
    ctl.arm(2, 4, str(tmp_path / "w"))
    wf = make_workflow(max_epochs=2)
    wf.run_fused()
    assert [c[0] for c in fake.calls] == ["start", "stop"]
    assert ctl.windows[0]["first_step"] == 2
    assert ctl.windows[0]["last_step"] == 4


# -- metrics registry ---------------------------------------------------------


def test_counter_gauge_histogram_semantics():
    reg = metrics.MetricsRegistry()
    c = reg.counter("x_total", "help")
    c.inc()
    c.inc(2.5)
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5
    c.set_total(10)
    c.set_total(4)              # monotone mirror: never backwards
    assert c.value == 10
    g = reg.gauge("g")
    g.set(-2.5)
    assert g.value == -2.5
    h = reg.histogram("h", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    flat = reg.snapshot_flat()
    assert flat["h_sum"] == pytest.approx(5.55)
    assert flat["h_count"] == 3


def test_registry_rejects_bad_names_and_kind_collisions():
    reg = metrics.MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bad name_total")
    with pytest.raises(ValueError):
        reg.counter("no_suffix")        # counters must end _total
    reg.gauge("thing")
    reg.counter("thing_total")          # ok: different name
    with pytest.raises(ValueError):
        reg.histogram("thing")          # same name, different kind


def test_exposition_is_strictly_parseable_with_labels_and_escapes():
    reg = metrics.MetricsRegistry()
    metrics.register_standard(reg)
    reg.counter("veles_step_total").inc(7)
    reg.histogram("veles_step_seconds").observe(0.004)
    reg.gauge("veles_mem_live_bytes", labelnames=("device",)) \
        .labels(device='weird"dev\\1').set(42)
    reg.counter("veles_serving_requests_total", "with \"quotes\"\n").inc()
    fams = parse_prometheus(reg.exposition())
    assert fams["veles_step_total"]["type"] == "counter"
    assert fams["veles_step_total"]["samples"][0][2] == 7
    hs = fams["veles_step_seconds"]
    assert hs["type"] == "histogram"
    # the labeled gauge round-trips its escaped value
    mem = fams["veles_mem_live_bytes"]["samples"]
    assert any(lb.get("device") == r'weird\"dev\\1' and v == 42
               for _, lb, v in mem)
    # step/feed/mem/restart families all present
    for fam in ("veles_step_total", "veles_feed_h2d_bytes_total",
                "veles_mem_live_bytes_max", "veles_restart_total"):
        assert fam in fams


def test_label_cardinality_is_bounded():
    reg = metrics.MetricsRegistry()
    g = reg.gauge("many", labelnames=("k",))
    for i in range(metrics._MAX_CHILDREN + 50):
        g.labels(k=str(i)).set(i)
    assert len(g._children) <= metrics._MAX_CHILDREN + 1


def test_feed_handles_and_mirror_mem():
    """The feed's counters have one producer, the feed, through handles
    bound once (`feed_handles`); memstats are still mirrored in."""
    reg = metrics.MetricsRegistry()
    metrics.register_standard(reg)
    h = metrics.feed_handles(reg)
    h.bytes_h2d.inc(1024)
    h.device_sync_s.inc(0.25)
    h.put_s.inc(0.5)
    h.h2d_late.inc()
    metrics.loader_handles(reg).waited.inc(2)
    metrics.mirror_mem({"live_bytes": {"0": 100, "1": 200},
                        "live_bytes_max": 200}, reg)
    flat = reg.snapshot_flat()
    assert flat["veles_feed_h2d_bytes_total"] == 1024
    assert flat["veles_feed_device_sync_seconds_total"] == 0.25
    assert flat["veles_feed_put_seconds_total"] == 0.5
    assert flat["veles_feed_h2d_late_total"] == 1
    assert flat["veles_feed_h2d_ready_total"] == 0
    assert flat["veles_loader_lookahead_waited_total"] == 2
    assert flat["veles_mem_live_bytes_max"] == 200
    fams = parse_prometheus(reg.exposition())
    devs = {lb["device"]: v
            for _, lb, v in fams["veles_mem_live_bytes"]["samples"]}
    assert devs == {"0": 100.0, "1": 200.0}


def test_jsonl_sink_rotation(tmp_path):
    path = str(tmp_path / "m.jsonl")
    sink = metrics.JsonlSink(path, max_bytes=4096)
    for i in range(200):
        sink.write({"i": i, "pad": "x" * 64})
    import os
    assert os.path.exists(path) and os.path.exists(path + ".1")
    assert os.path.getsize(path) <= 4096
    assert os.path.getsize(path + ".1") <= 4096 + 128
    # every surviving line is intact JSON and the newest is last
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[-1]["i"] == 199


def test_flush_installed_mirrors_registry(tmp_path):
    path = str(tmp_path / "f.jsonl")
    metrics.install_jsonl(path)
    metrics.default_registry().counter("veles_step_total").inc(3)
    metrics.flush_installed(extra={"source": "test"})
    rows = [json.loads(ln) for ln in open(path)]
    assert rows[0]["source"] == "test"
    assert rows[0]["metrics"]["veles_step_total"] == 3


# -- driver wiring ------------------------------------------------------------


def test_run_fused_populates_one_registry(tmp_path):
    jsonl = str(tmp_path / "drv.jsonl")
    metrics.install_jsonl(jsonl)
    wf = make_workflow(max_epochs=2)
    wf.run_fused()
    flat = metrics.snapshot_flat()
    st = wf.feed_stats
    # the feed mirror IS the feed's counters — one producer
    assert flat["veles_feed_h2d_bytes_total"] == st["bytes_h2d"]
    assert flat["veles_feed_on_demand_total"] == st["on_demand"]
    assert flat["veles_step_total"] == st["batches"]
    assert flat["veles_epoch"] == wf.decision.epoch_number
    assert flat["veles_loss"] > 0
    assert flat["veles_examples_total"] > 0
    # one JSONL row per epoch + the feed-stop mirror never less
    rows = [json.loads(ln) for ln in open(jsonl)]
    assert len([r for r in rows if r.get("source") == "driver"]) == 2


# -- endpoints ----------------------------------------------------------------


def _http(method, port, path, body=None, token=None, host="127.0.0.1"):
    import http.client
    conn = http.client.HTTPConnection(host, port, timeout=5)
    headers = {}
    if token:
        headers["X-Veles-Token"] = token
    if body is not None:
        headers["Content-Type"] = "application/json"
    try:
        conn.request(method, path, body, headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_web_status_metrics_endpoint_and_auth():
    from veles_tpu.web_status import WebStatusServer
    wf = make_workflow(max_epochs=1)
    metrics.default_registry().counter("veles_step_total").inc(5)
    srv = WebStatusServer(wf, port=0, token="sekrit")
    srv.start()
    try:
        status, _ = _http("GET", srv.port, "/metrics")
        assert status == 403                   # token required
        status, body = _http("GET", srv.port, "/metrics",
                             token="sekrit")
        assert status == 200
        fams = parse_prometheus(body.decode())
        for fam in ("veles_step_total", "veles_feed_h2d_bytes_total",
                    "veles_mem_live_bytes_max", "veles_restart_total"):
            assert fam in fams
        assert fams["veles_step_total"]["samples"][0][2] == 5
    finally:
        srv.stop()


def test_web_status_profile_endpoint_auth_and_bounded_body():
    from veles_tpu.web_status import WebStatusServer
    ctl = tracer.ProfileController(start_fn=lambda d: None,
                                   stop_fn=lambda: None)
    srv = WebStatusServer(make_workflow(max_epochs=1), port=0,
                          token="sekrit", profile_controller=ctl)
    srv.start()
    try:
        status, _ = _http("POST", srv.port, "/profile",
                          body=json.dumps({"steps": 5}))
        assert status == 403                   # unauthenticated
        status, _ = _http("POST", srv.port, "/profile",
                          body="x" * 8192, token="sekrit")
        assert status == 413                   # bounded body
        status, _ = _http("POST", srv.port, "/profile",
                          body="not json", token="sekrit")
        assert status == 400
        status, body = _http("POST", srv.port, "/profile",
                             body=json.dumps({"steps": 7}),
                             token="sekrit")
        assert status == 202
        assert json.loads(body)["armed"]["steps"] == 7
        # the controller is armed: the next driver step opens a window
        ctl.on_step(4)
        ctl.finalize()
        assert ctl.windows[0]["first_step"] == 4
    finally:
        srv.stop()


def test_web_status_profile_without_controller_is_409():
    from veles_tpu.web_status import WebStatusServer
    srv = WebStatusServer(make_workflow(max_epochs=1), port=0,
                          profile_controller=None)
    srv.start()
    try:
        status, _ = _http("POST", srv.port, "/profile", body="{}")
        assert status == 409
    finally:
        srv.stop()


def test_web_status_cluster_table_surfaces_feed_and_mem():
    """Satellite: the PR-5/6 heartbeat payload fields become columns
    instead of being dropped on the dashboard floor — and arrive
    sanitized (scalars only, nested rows stripped)."""
    from veles_tpu.web_status import WebStatusServer
    srv = WebStatusServer(make_workflow(max_epochs=1), port=0)
    srv.start()
    try:
        beat = {"process_id": 3, "host": "worker-a", "local_devices": 4,
                "feed": {"bytes_per_batch": 4096, "uint8_wire": True,
                         "loader_block_s": 1.25, "on_demand": 1,
                         "epoch_log": [{"nested": "dropped"}]},
                "mem": {"live_bytes_max": 123456,
                        "n_live_arrays": 17,
                        "live_bytes": {"0": 1}}}
        status, _ = _http("POST", srv.port, "/heartbeat.json",
                          body=json.dumps(beat))
        assert status == 204
        _, body = _http("GET", srv.port, "/status.json")
        w = json.loads(body)["workers"]["3"]
        assert w["feed"]["bytes_per_batch"] == 4096
        assert w["feed"]["uint8_wire"] is True
        assert "epoch_log" not in w["feed"]       # nested: stripped
        assert w["mem"]["live_bytes_max"] == 123456
        assert "live_bytes" not in w["mem"]
        # the page's table carries the new columns
        _, page = _http("GET", srv.port, "/")
        assert b"feed b/batch" in page and b"mem max" in page
        # beats without the optional payloads still register
        status, _ = _http("POST", srv.port, "/heartbeat.json",
                          body=json.dumps({"process_id": 4,
                                           "host": "b",
                                           "local_devices": 1}))
        assert status == 204
    finally:
        srv.stop()


def test_heartbeat_reporter_carries_feed_and_mem():
    from veles_tpu.web_status import HeartbeatReporter, WebStatusServer
    wf = make_workflow(max_epochs=1)
    wf.feed_stats = {"bytes_per_batch": 512, "on_demand": 1,
                     "epoch_log": [{"x": 1}]}
    srv = WebStatusServer(wf, port=0)
    srv.start()
    rep = HeartbeatReporter("127.0.0.1", srv.port, 9, workflow=wf)
    try:
        rep._beat()
        _, body = _http("GET", srv.port, "/status.json")
        w = json.loads(body)["workers"]["9"]
        assert w["feed"]["bytes_per_batch"] == 512
        assert "epoch_log" not in w["feed"]
    finally:
        srv.stop()


def test_serving_metrics_endpoint(tmp_path):
    from veles_tpu.serving import InferenceServer
    wf = make_workflow(max_epochs=1)
    wf.initialize(device=None)
    srv = InferenceServer(wf, max_batch=8, batch_window_ms=0).start()
    try:
        x = np.zeros((2, 6), np.float32)
        srv.predict(x)
        status, body = _http("GET", srv.port, "/metrics")
        assert status == 200
        fams = parse_prometheus(body.decode())
        assert fams["veles_serving_requests_total"]["samples"][0][2] \
            == 1
        assert fams["veles_serving_dispatches_total"]["samples"][0][2] \
            >= 1
        assert fams["veles_serving_latency_seconds"]["type"] \
            == "histogram"
        # the standard families ride every scrape endpoint
        for fam in ("veles_step_total", "veles_feed_h2d_bytes_total",
                    "veles_mem_live_bytes_max", "veles_restart_total"):
            assert fam in fams
    finally:
        srv.stop(drain_s=0)


def test_coordinator_metrics_fleet_aggregation():
    from veles_tpu.resilience.cluster import ClusterCoordinator
    coord = ClusterCoordinator(2, token="tok")
    for hid, steps in (("0", 40.0), ("1", 25.0)):
        coord.handle_beat({
            "host": hid, "generation": 1, "status": "running",
            "epoch": 3, "snapshots": [],
            "feed": {"bytes_h2d": 100},
            "mem": {"live_bytes_max": 1000 * (int(hid) + 1)},
            "metrics": {"veles_step_total": steps,
                        "veles_step_seconds_sum": steps / 100,
                        "veles_step_seconds_count": steps,
                        "veles_loss": 0.5,
                        "veles_feed_h2d_bytes_total": 100.0}})
    fams = parse_prometheus(coord.metrics_exposition())
    # counters SUM across hosts
    assert fams["veles_step_total"]["samples"][0][2] == 65.0
    assert fams["veles_feed_h2d_bytes_total"]["samples"][0][2] == 200.0
    # flattened child histograms fold back into the histogram family
    hs = {s[0]: s[2] for s in fams["veles_step_seconds"]["samples"]
          if not s[1]}
    assert hs["veles_step_seconds_count"] == 65.0
    # gauges label per host
    eps = {lb["host"]: v for _, lb, v in
           fams["veles_cluster_host_epoch"]["samples"]}
    assert eps == {"0": 3.0, "1": 3.0}
    losses = {lb["host"]: v for _, lb, v in
              fams["veles_loss"]["samples"] if lb}
    assert losses == {"0": 0.5, "1": 0.5}
    assert fams["veles_mem_live_bytes_max"]["samples"][0][2] == 2000.0
    # restart family present (and 0 before any restart)
    assert fams["veles_restart_total"]["samples"][0][2] == 0.0


def test_coordinator_metrics_epoch_zero_and_mixed_fleet():
    """Review-pass regressions: a host at epoch 0 shows 0 (not the
    never-reported -1), and in a MIXED fleet (rolling upgrade) a
    pre-telemetry host's raw feed dict still counts toward the fleet
    sums while a telemetry-carrying host is never double-counted."""
    from veles_tpu.resilience.cluster import ClusterCoordinator
    coord = ClusterCoordinator(2)
    coord.handle_beat({          # new child: metrics mirror the feed
        "host": "0", "generation": 1, "status": "running",
        "epoch": 0, "snapshots": [],
        "feed": {"bytes_h2d": 100},
        "metrics": {"veles_feed_h2d_bytes_total": 100.0}})
    coord.handle_beat({          # pre-telemetry child: feed dict only
        "host": "1", "generation": 1, "status": "running",
        "epoch": 0, "snapshots": [],
        "feed": {"bytes_h2d": 40}})
    fams = parse_prometheus(coord.metrics_exposition())
    eps = {lb["host"]: v for _, lb, v in
           fams["veles_cluster_host_epoch"]["samples"]}
    assert eps == {"0": 0.0, "1": 0.0}
    # host 0 via its snapshot (100), host 1 via its feed dict (40) —
    # no double count, no dropped host
    assert fams["veles_feed_h2d_bytes_total"]["samples"][0][2] == 140.0


def test_coordinator_metrics_http_route_authed():
    from veles_tpu.resilience.cluster import ClusterCoordinator
    coord = ClusterCoordinator(1, host="127.0.0.1", token="tok").start()
    try:
        coord.handle_beat({"host": "0", "generation": 1,
                           "status": "running", "epoch": 1,
                           "snapshots": []})
        status, _ = _http("GET", coord.port, "/metrics")
        assert status == 403
        status, body = _http("GET", coord.port, "/metrics",
                             token="tok")
        assert status == 200
        fams = parse_prometheus(body.decode())
        for fam in ("veles_step_total", "veles_feed_h2d_bytes_total",
                    "veles_mem_live_bytes_max", "veles_restart_total",
                    "veles_generation"):
            assert fam in fams
    finally:
        coord.stop()


def test_cluster_member_forwards_child_telemetry(tmp_path):
    """The beat chain: child heartbeat file (feed/mem/metrics written
    by the Launcher's epoch hook) -> member report -> coordinator."""
    from veles_tpu.resilience.cluster import ClusterMember
    from veles_tpu.resilience.supervisor import (read_heartbeat,
                                                 write_heartbeat)
    hb = str(tmp_path / "hb.json")
    write_heartbeat(hb, 4, feed={"bytes_h2d": 77},
                    mem={"live_bytes_max": 5},
                    metrics={"veles_step_total": 12.0})
    assert read_heartbeat(hb)["metrics"] == {"veles_step_total": 12.0}
    member = ClusterMember([["true"]], host_id="1",
                           coordinator_addr="127.0.0.1:1")
    member._hb_paths = [hb]
    payload = member._child_payload()
    assert payload == {"epoch": 4, "feed": {"bytes_h2d": 77},
                       "mem": {"live_bytes_max": 5},
                       "metrics": {"veles_step_total": 12.0}}


# -- CLI ----------------------------------------------------------------------


def test_trace_and_profile_window_flags_require_a_consumer():
    """Satellite: the --feed-ahead validation precedent — flags the run
    mode would silently ignore are rejected."""
    from veles_tpu.launcher import Launcher
    with pytest.raises(SystemExit):
        Launcher(trace="t.json")               # granular: no spans
    with pytest.raises(SystemExit):
        Launcher(profile_window="2:5")
    with pytest.raises(SystemExit):
        Launcher(profile_window="2:5", serve=0)   # no stepped driver
    with pytest.raises(SystemExit):
        Launcher(profile_window="5:2", fused=True)  # N > M
    with pytest.raises(SystemExit):
        Launcher(profile_window="nope", fused=True)
    # consumers accept
    assert Launcher(trace="t.json", fused=True).trace_path == "t.json"
    assert Launcher(trace="t.json", serve=0).trace_path == "t.json"
    assert Launcher(profile_window="2:5", pp=2).profile_window == "2:5"
    assert Launcher(trace="t.json",
                    master="h:1").trace_path == "t.json"


def test_cli_parser_accepts_trace_flags():
    from veles_tpu.__main__ import build_parser
    args = build_parser().parse_args(
        ["wf.py", "--fused", "--trace", "out.json",
         "--profile-window", "3:9"])
    assert args.trace == "out.json"
    assert args.profile_window == "3:9"


_CLI_WF_SRC = '''
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
        name="TraceWF")

def run(load, main):
    wf, _ = load(create_workflow)
    main()
    print("TRACE_DONE", wf.decision.epoch_number, flush=True)
'''


def test_cli_trace_produces_loadable_artifacts(tmp_path):
    """End-to-end CLI smoke: `--fused --trace PATH` writes a
    Perfetto-loadable trace.json at exit plus the metrics JSONL
    sidecar — the acceptance artifact through the real entry point."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    wf_py = tmp_path / "tracewf.py"
    wf_py.write_text(_CLI_WF_SRC)
    out_json = tmp_path / "trace.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "veles_tpu", str(wf_py), "--no-stats",
         "--fused", "--trace", str(out_json)],
        env=env, cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "TRACE_DONE 2" in out.stdout
    doc = json.load(open(out_json))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {"feed.next", "train.dispatch", "step",
            "feed.device_put"} <= {e["name"] for e in xs}
    rows = [json.loads(ln)
            for ln in open(str(out_json) + ".metrics.jsonl")]
    assert rows[-1]["metrics"]["veles_step_total"] > 0
