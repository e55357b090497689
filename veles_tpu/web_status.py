"""Web status dashboard: live workflow progress over HTTP.

Parity: reference `veles/web_status.py` + `web/` (SURVEY.md §2.5) — a
dashboard showing the running workflow, per-unit progress, and (in
distributed mode) cluster membership. The reference used Tornado + a JS
frontend; here a stdlib `http.server` on a daemon thread serves a
self-contained page that polls a JSON endpoint — no extra dependency, same
information.

Cluster view (multi-process runs): the coordinator's server accepts
`POST /heartbeat.json` from worker processes (`HeartbeatReporter`,
started by the Launcher's worker role) and lists every process with its
last-seen age — the analog of the reference master's slave registry,
minus the job bookkeeping that synchronous SPMD made obsolete.
"""

from __future__ import annotations

import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

_PAGE = """<!doctype html>
<html><head><title>veles_tpu status</title><style>
body{font-family:monospace;margin:2em;background:#111;color:#ddd}
table{border-collapse:collapse}td,th{padding:.3em .8em;border:1px solid #444}
th{text-align:left;background:#222}h1{font-size:1.2em}
</style></head><body>
<h1>veles_tpu — workflow status</h1>
<div id="meta"></div>
<div id="cluster"></div>
<svg id="curves" width="640" height="200" style="display:none;
background:#181818;border:1px solid #444;margin:1em 0"></svg>
<div id="legend" style="display:none">
<span style="color:#e66">train</span>
<span style="color:#6ae">valid</span>
<span style="color:#ddd">&nbsp;(errors per epoch)</span></div>
<table id="procs" style="display:none"><thead><tr><th>process</th>
<th>host</th><th>devices</th><th>last seen</th><th>feed b/batch</th>
<th>feed blocked (s)</th><th>on demand</th><th>mem max</th></tr></thead>
<tbody></tbody></table>
<table id="fleet" style="display:none"><thead><tr><th>replica</th>
<th>status</th><th>circuit</th><th>capacity</th><th>inflight</th>
<th>generation</th><th>gen age (s)</th><th>p99 (s)</th></tr></thead>
<tbody></tbody></table>
<table id="units"><thead><tr><th>unit</th><th>runs</th><th>time (s)</th>
</tr></thead><tbody></tbody></table>
<script>
async function tick(){
  const r = await fetch('/status.json'); const s = await r.json();
  document.getElementById('meta').textContent =
    `workflow: ${s.workflow}  stopped: ${s.stopped}  ` +
    (s.epoch != null ? `epoch: ${s.epoch}  best_err: ${s.best_err}` : '');
  const c = s.cluster;
  document.getElementById('cluster').textContent = c ?
    `cluster: process ${c.process_index}/${c.process_count}  ` +
    `global devices: ${c.global_devices}  local: ${c.local_devices}` : '';
  const pt = document.getElementById('procs');
  const ptb = pt.querySelector('tbody'); ptb.innerHTML = '';
  const workers = Object.entries(s.workers || {});
  pt.style.display = workers.length ? '' : 'none';
  for (const [pid, w] of workers){
    const tr = document.createElement('tr');
    const f = w.feed || {}, m = w.mem || {};
    const mb = v => v == null ? '-' : (v / 1048576).toFixed(1) + ' MB';
    const wire = f.uint8_wire ? ' u8' : '';
    tr.innerHTML = `<td>${pid}</td><td>${w.host}</td>` +
      `<td>${w.local_devices}</td><td>${w.age_s.toFixed(1)}s ago</td>` +
      `<td>${f.bytes_per_batch == null ? '-'
            : mb(f.bytes_per_batch) + wire}</td>` +
      `<td>${f.loader_block_s == null ? '-'
            : f.loader_block_s.toFixed(2)}</td>` +
      `<td>${f.on_demand == null ? '-' : f.on_demand}</td>` +
      `<td>${mb(m.live_bytes_max)}</td>`;
    ptb.appendChild(tr);
  }
  const ft = document.getElementById('fleet');
  const ftb = ft.querySelector('tbody'); ftb.innerHTML = '';
  const fleet = (s.fleet && s.fleet.replicas) || [];
  ft.style.display = fleet.length ? '' : 'none';
  for (const r of fleet){
    const tr = document.createElement('tr');
    const dg = r.generation ? r.generation.slice(0, 12) : '-';
    tr.innerHTML = `<td>${r.rid}</td><td>${r.status}</td>` +
      `<td>${r.circuit}</td><td>${r.capacity}</td>` +
      `<td>${r.inflight}</td><td>${dg}</td>` +
      `<td>${r.generation_age_s == null ? '-'
            : r.generation_age_s.toFixed(0)}</td>` +
      `<td>${r.p99_s == null ? '-' : r.p99_s.toFixed(3)}</td>`;
    ftb.appendChild(tr);
  }
  const tb = document.querySelector('#units tbody'); tb.innerHTML = '';
  for (const u of s.units){
    const tr = document.createElement('tr');
    tr.innerHTML = `<td>${u.name}</td><td>${u.runs}</td>` +
                   `<td>${u.time.toFixed(3)}</td>`;
    tb.appendChild(tr);
  }
  drawCurves(s.history || []);
}
function drawCurves(h){
  const svg = document.getElementById('curves');
  const leg = document.getElementById('legend');
  if (h.length < 2){ svg.style.display = 'none';
                     leg.style.display = 'none'; return; }
  svg.style.display = ''; leg.style.display = '';
  const W = 640, H = 200, P = 24;
  const xs = h.map(r => r.epoch);
  const series = [['train_err', '#e66'], ['valid_err', '#6ae']];
  let ymax = 1e-9;
  for (const [k] of series)
    for (const r of h) if (r[k] != null) ymax = Math.max(ymax, r[k]);
  const x = e => P + (W - 2*P) * (e - xs[0]) /
                 Math.max(1, xs[xs.length-1] - xs[0]);
  const y = v => H - P - (H - 2*P) * v / ymax;
  let out = `<text x="4" y="14" fill="#888" font-size="11">` +
            `${ymax.toFixed(0)}</text>` +
            `<text x="4" y="${H-6}" fill="#888" font-size="11">0</text>`;
  for (const [k, color] of series){
    const pts = h.filter(r => r[k] != null)
                 .map(r => `${x(r.epoch).toFixed(1)},` +
                           `${y(r[k]).toFixed(1)}`).join(' ');
    out += `<polyline points="${pts}" fill="none" ` +
           `stroke="${color}" stroke-width="1.5"/>`;
  }
  svg.innerHTML = out;
}
setInterval(tick, 1000); tick();
</script></body></html>"""


def workflow_status(workflow) -> Dict[str, Any]:
    """The JSON the dashboard (and tests) read."""
    status: Dict[str, Any] = {
        "workflow": getattr(workflow, "name", type(workflow).__name__),
        "stopped": bool(getattr(workflow, "stopped", False)),
        "epoch": None,
        "best_err": None,
        "units": [
            {"name": u.name, "runs": u.run_count,
             "time": round(u.run_time, 6)}
            for u in getattr(workflow, "units", [])
        ],
    }
    decision = getattr(workflow, "decision", None)
    if decision is not None:
        status["epoch"] = decision.epoch_number
        status["best_err"] = decision.best_validation_err
        # error curves for the dashboard (bounded: the page only needs
        # the shape, and an unbounded run must not grow the payload)
        status["history"] = list(
            getattr(decision, "history", [])[-1000:])
    try:
        import jax
        if jax.process_count() > 1:
            status["cluster"] = {
                "process_index": jax.process_index(),
                "process_count": jax.process_count(),
                "global_devices": jax.device_count(),
                "local_devices": jax.local_device_count(),
            }
    except Exception:       # backend not initialized yet: no cluster row
        pass
    # hot-swap deploy state (ISSUE 16), read from the one process
    # registry: swaps applied/refused and the live generation's age.
    # Guarded + only shown once serving activity exists — a pure
    # training run keeps its status payload unchanged.
    try:
        from veles_tpu.telemetry import metrics as _m
        reg = _m.default_registry()
        flat = reg.snapshot_flat()
        applied = flat.get("veles_serving_swap_applied_total", 0.0)
        age = flat.get("veles_serving_generation_age_seconds")
        fam = reg.counter("veles_serving_swap_refused_total")
        refused = {(k[0] if k else "total"): ch.value
                   for k, ch in getattr(fam, "_children", {}).items()}
        if applied or refused or age:
            status["serving"] = {
                "swaps_applied": applied,
                "swaps_refused": refused,
                "generation_age_s": age,
            }
    except Exception:       # metrics plane optional for the dashboard
        pass
    return status


class WebStatusServer:
    """Serve `/` (dashboard page) and `/status.json` on a daemon thread.

    The heartbeat endpoint is hardened against untrusted network peers
    (it binds non-loopback in distributed mode): beats are
    field-whitelisted with size caps, the worker registry is bounded
    (`max_workers`), and when `token` is set a beat must carry it in
    `X-Veles-Token` (the Launcher derives a shared token from the
    coordinator address so workers agree without a side channel)."""

    #: accepted beat fields -> (type, max size when str)
    _BEAT_FIELDS = {"host": (str, 256), "local_devices": (int, None)}
    #: OPTIONAL dict payloads a beat may carry (device-feed overlap
    #: counters + memstats snapshot — PR 5/6 heartbeat fields, now
    #: surfaced as cluster-table columns instead of dropped): sanitized
    #: to scalar values, key count and string length capped
    _BEAT_OPTIONAL = ("feed", "mem")
    _BEAT_DICT_KEYS = 32

    def __init__(self, workflow, host: str = "127.0.0.1",
                 port: int = 8090, token: Optional[str] = None,
                 max_workers: int = 256,
                 profile_controller=None,
                 fleet_source: Optional[str] = None) -> None:
        self.workflow = workflow
        self.host = host
        self.port = port
        self.token = token
        self.max_workers = max_workers
        #: serving-fleet router base URL ("http://host:port"). When
        #: set, /status.json carries a "fleet" key (the router's
        #: GET /fleet registry view — per-replica generation digest /
        #: age, capacity hint, circuit state) and the dashboard shows
        #: the fleet table. The fetch reuses this server's token: the
        #: fleet runs under ONE shared-token trust domain (SERVING.md).
        self.fleet_source = fleet_source.rstrip("/") if fleet_source \
            else None
        #: the live run's profile-window controller (telemetry/tracer):
        #: POST /profile arms an on-chip capture window on it
        self.profile_controller = profile_controller
        #: worker heartbeats: process_id -> {host, local_devices, t}
        self.workers: Dict[str, Dict[str, Any]] = {}
        #: guards `workers`: POSTed beats insert from one server thread
        #: while /status.json iterates from another — an unguarded
        #: sorted(workers.items()) mid-insert raises "dictionary changed
        #: size during iteration" (the shared-write-no-lock class the
        #: concurrency pass flags)
        self._workers_lock = threading.Lock()
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    @classmethod
    def _clean_dict(cls, d: Any) -> Optional[Dict[str, Any]]:
        """Scalars-only, size-capped copy of an optional beat dict."""
        if not isinstance(d, dict):
            return None
        out: Dict[str, Any] = {}
        for k, v in d.items():
            if len(out) >= cls._BEAT_DICT_KEYS:
                break
            if isinstance(v, bool) or v is None:
                out[str(k)[:64]] = v
            elif isinstance(v, (int, float)):
                out[str(k)[:64]] = v
            elif isinstance(v, str):
                out[str(k)[:64]] = v[:128]
            # nested structures (epoch_log rows, per-device maps) are
            # dropped: the table shows totals, the child owns detail
        return out

    def _clean_beat(self, beat: Any) -> Optional[Dict[str, Any]]:
        """Whitelisted, size-capped copy of an incoming beat, or None."""
        if not isinstance(beat, dict):
            return None
        out = {}
        for k, (typ, cap) in self._BEAT_FIELDS.items():
            v = beat.get(k)
            if not isinstance(v, typ) or isinstance(v, bool):
                return None
            if cap is not None and len(v) > cap:
                v = v[:cap]
            out[k] = v
        for k in self._BEAT_OPTIONAL:
            v = self._clean_dict(beat.get(k))
            if v:
                out[k] = v
        return out

    def _fetch_fleet(self) -> Optional[Dict[str, Any]]:
        """One GET /fleet against the router; None on any failure (a
        down router must not break the training dashboard)."""
        if self.fleet_source is None:
            return None
        import http.client
        from urllib.parse import urlsplit
        try:
            parts = urlsplit(self.fleet_source)
            conn = http.client.HTTPConnection(
                parts.hostname or "127.0.0.1", parts.port or 80,
                timeout=2)
            try:
                headers = {}
                if self.token:
                    headers["X-Veles-Token"] = self.token
                conn.request("GET", "/fleet", headers=headers)
                resp = conn.getresponse()
                body = resp.read(1 << 20)
                if resp.status != 200:
                    return None
                fleet = json.loads(body)
            finally:
                conn.close()
            return fleet if isinstance(fleet, dict) else None
        except Exception:   # noqa: BLE001 — dashboard survives outages
            return None

    def start(self) -> None:
        wf = self.workflow
        workers = self.workers
        wlock = self._workers_lock
        token = self.token
        max_workers = self.max_workers
        clean = self._clean_beat
        fetch_fleet = self._fetch_fleet

        profile_ctl = self.profile_controller

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self) -> None:  # noqa: N802 (http.server API)
                if self.path.startswith("/metrics"):
                    # Prometheus scrape target (telemetry/metrics.py):
                    # the one process registry, with a scrape-time mem
                    # refresh; token-guarded like the heartbeat POST
                    # (the server binds non-loopback in distributed
                    # mode and an exposition leaks run internals)
                    from veles_tpu.http_util import check_shared_token
                    if not check_shared_token(self, token):
                        return
                    from veles_tpu.telemetry import metrics as tmetrics
                    tmetrics.scrape_mem()
                    reg = tmetrics.default_registry()
                    try:
                        dec = getattr(wf, "decision", None)
                        if dec is not None:
                            reg.gauge("veles_epoch").set(
                                float(dec.epoch_number))
                    except Exception:  # noqa: BLE001 — scrape survives
                        pass
                    body = reg.exposition().encode()
                    ctype = tmetrics.CONTENT_TYPE
                elif self.path.startswith("/status.json"):
                    status = workflow_status(wf)
                    now = time.time()
                    with wlock:     # beats insert from sibling threads
                        snap = sorted((pid, dict(w))
                                      for pid, w in workers.items())
                    status["workers"] = {
                        pid: {**{k: v for k, v in w.items() if k != "t"},
                              "age_s": round(now - w["t"], 3)}
                        for pid, w in snap}
                    fleet = fetch_fleet()
                    if fleet is not None:
                        status["fleet"] = fleet
                    body = json.dumps(status).encode()
                    ctype = "application/json"
                else:
                    body = _PAGE.encode()
                    ctype = "text/html"
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self) -> None:  # noqa: N802
                if self.path.startswith("/profile"):
                    self._do_profile()
                    return
                if not self.path.startswith("/heartbeat.json"):
                    self.send_response(404)
                    self.end_headers()
                    return
                from veles_tpu.http_util import check_shared_token
                if not check_shared_token(self, token):
                    return
                try:
                    n = max(0, min(
                        int(self.headers.get("Content-Length", "0")),
                        64 * 1024))
                    raw = json.loads(self.rfile.read(n) or b"{}")
                    pid = str(raw.pop("process_id"))[:64]
                    beat = clean(raw)
                    if beat is None:
                        raise ValueError(raw)
                except (ValueError, KeyError, AttributeError, TypeError):
                    self.send_response(400)   # malformed beat != crash
                    self.end_headers()
                    return
                beat["t"] = time.time()
                with wlock:
                    full = (pid not in workers
                            and len(workers) >= max_workers)
                    if not full:
                        workers[pid] = beat
                if full:
                    self.send_response(429)   # registry full: no growth
                    self.end_headers()
                    return
                self.send_response(204)
                self.end_headers()

            def _do_profile(self) -> None:
                """POST /profile {"steps": K[, "dir": PATH]} — arm a
                jax.profiler window of K steps at the live run's next
                step boundary. Auth + bounded body like the heartbeat endpoint
                (task_queue hardening precedent): arming the profiler
                on an open port is a writable control surface."""
                from veles_tpu.http_util import check_shared_token
                if not check_shared_token(self, token):
                    return
                try:
                    length = int(self.headers.get("Content-Length",
                                                  "0"))
                except ValueError:
                    length = -1
                if not 0 <= length <= 4096:
                    self.send_response(413 if length > 4096 else 400)
                    self.end_headers()
                    return
                if profile_ctl is None:
                    body = json.dumps({"error": "no stepped driver in "
                                       "this process"}).encode()
                    self.send_response(409)
                else:
                    try:
                        req = json.loads(self.rfile.read(length)
                                         or b"{}")
                        steps = int(req.get("steps", 20))
                        out_dir = str(req.get("dir", ""))[:512]
                        if steps < 1:
                            raise ValueError(steps)
                    except (ValueError, TypeError, AttributeError):
                        self.send_response(400)
                        self.end_headers()
                        return
                    armed = profile_ctl.request(steps, out_dir)
                    body = json.dumps({"armed": armed}).encode()
                    self.send_response(202)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args: Any) -> None:
                pass  # keep the training log clean

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]  # resolve port 0
        self._thread = threading.Thread(
            target=lambda: self._httpd.serve_forever(poll_interval=0.05),
            daemon=True, name="web-status")
        self._thread.start()

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


class HeartbeatReporter:
    """Worker-side: POST a liveness beat to the coordinator's web status
    every `interval` seconds on a daemon thread (the Launcher starts one
    per worker process when web status is enabled)."""

    def __init__(self, coordinator_host: str, port: int,
                 process_id: int, interval: float = 5.0,
                 token: Optional[str] = None, workflow=None) -> None:
        self.url_host = coordinator_host
        self.port = port
        self.process_id = process_id
        self.interval = interval
        self.token = token
        #: when given, beats carry the run's feed/mem telemetry so the
        #: coordinator's cluster table shows input-pipeline health and
        #: memory footprint per process, not just last-seen ages
        self.workflow = workflow
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _beat(self) -> None:
        import http.client
        try:
            import jax
            n_local = jax.local_device_count()
        except Exception:
            n_local = 0
        payload: Dict[str, Any] = {
            "process_id": self.process_id,
            "host": socket.gethostname(),
            "local_devices": n_local,
        }
        feed = getattr(self.workflow, "feed_stats", None)
        if feed:
            payload["feed"] = {k: v for k, v in feed.items()
                               if k != "epoch_log"}
        try:
            from veles_tpu.parallel.memstats import device_memory_stats
            mem = device_memory_stats()
            if mem:
                # totals only: the beat whitelist drops nested maps
                payload["mem"] = {
                    "live_bytes_max": mem.get("live_bytes_max", 0),
                    "n_live_arrays": mem.get("n_live_arrays", 0),
                    "peak_bytes_max": mem.get("peak_bytes_max")}
        except Exception:   # noqa: BLE001 — stats never kill a beat
            pass
        body = json.dumps(payload)
        conn = http.client.HTTPConnection(self.url_host, self.port,
                                          timeout=3)
        headers = {"Content-Type": "application/json"}
        if self.token:
            headers["X-Veles-Token"] = self.token
        try:
            conn.request("POST", "/heartbeat.json", body, headers)
            conn.getresponse().read()
        finally:
            conn.close()

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self._beat()
            except Exception:   # noqa: BLE001 — liveness thread must
                pass            # outlive ANY transport hiccup (refused,
                                # BadStatusLine, ...), not just OSError
            self._stop.wait(self.interval)

    def start(self) -> "HeartbeatReporter":
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="heartbeat")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
