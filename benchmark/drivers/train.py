"""The `train` driver: one fused training step, fed one of two ways, on one
chip or on a data mesh. Every cell of it differs only in data files.

Traffic parameters: `source` (`resident`: one batch made on the device
from the seed; `feed`: uint8 images packed from the seed, through
`MemmapImageLoader` and `DeviceFeed`), `mesh` (`none`, or `data`:
`make_mesh()` over the cell's chips, global batch = chips x batch per
chip), `warmup_steps`, `steps_in_flight`, `span_steps`, `trace_steps`,
`check_steps`, `host_tracer_level`, and `rate_metric`, the
name under which the cell's rate is reported (a fed cell's rate spreads
twenty times wider than a resident one's, so it is a metric of its own
with its own bound).

Set-up builds ONE object, the compiled step with its state, drives it
from the seed through its first steps by the window's own call and feed,
and hands that same object to the window. The loop is `next -> train ->
prefetch`, then `block_until_ready` on the loss of the step before: the
device always has the next step queued and every step has a completion
stamp (`steps_in_flight`: how many are queued behind the running one, 1
unless the traffic says otherwise). The window opens at the completion of
the last warm-up step and closes at the first completion past `--seconds`.
"""

from __future__ import annotations

import gc
import os
import shutil
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import ops_count, reference, seeded, trace_reduce

#: the first steps the reference follows, where the traffic names no other
#: number (`check_steps`: two where three would outlast the window)
CHECK_STEPS = 3


class CompileClock:
    """Seconds XLA spent compiling, from jax.monitoring's own events
    (copied from chip_smoke.py)."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.n = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw: Any) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += secs
            self.n += 1


def program_layers(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The configuration's layer list in the program's layer-table
    vocabulary (JSON lists become the tuples the units expect)."""
    out = []
    for spec in config["layers"]:
        spec = {k: tuple(v) if isinstance(v, list) else v
                for k, v in spec.items()}
        out.append(spec)
    return out


def device_peak_bytes(stats: Dict[str, Any]) -> int:
    """The chip's memory peak: the allocator's peak of live buffers plus
    the peak of what it reserved for the compiled programs' temporaries.
    The TPU client counts the two apart (`peak_bytes_in_use` held 1.18 GB
    for AlexNet at batch 1024 while `bytes_reserved` held the step's
    4.08 GB of temporaries, equal to the compiler's `temp_size_in_bytes`;
    chip runs of PR 23)."""
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


def host_rss_gb() -> float:
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def span_means(stamps: List[float], span: int) -> List[float]:
    """Mean step interval over every run of `span` consecutive steps: a
    host-clock reading spans several steps, not one."""
    return [(stamps[i + span] - stamps[i]) / span
            for i in range(len(stamps) - span)]


def build_loader(cell: Dict[str, Any], batch: int, seed: int, work_dir: str):
    """The loader the traffic asks for, and the pack it reads (or None)."""
    cfg, tr = cell["config_data"], cell["traffic_data"]
    if tr["source"] == "feed":
        from veles_tpu.loader.memmap import MemmapImageLoader, pack_arrays
        n = int(tr["pack_samples"])
        data, labels = seeded.make_pack(cfg, n, seed)
        pack_dir = os.path.join(work_dir, "pack")
        shutil.rmtree(pack_dir, ignore_errors=True)
        pack_arrays(pack_dir, data, labels, [0, 0, n], shard_mb=256.0)
        loader = MemmapImageLoader(data_path=pack_dir, minibatch_size=batch,
                                   **tr["loader"])
        return loader, (data, labels)
    if tr["source"] != "resident":
        raise ValueError(f"unknown source {tr['source']!r}")
    from veles_tpu.loader.fullbatch import FullBatchLoader

    class ShapeOnlyLoader(FullBatchLoader):
        """Gives the workflow its input shape; the resident batch never
        passes through it."""

        def load_data(self) -> None:
            self.bind_arrays(
                np.zeros((batch,) + tuple(cfg["input_shape"]), np.float32),
                np.zeros(batch, np.int64), 0, 0, batch)

    return ShapeOnlyLoader(minibatch_size=batch, on_device=False), None


class TrainSession:
    """The compiled step with its state, its inputs and the loop that
    drives them: built once by set-up, driven through the first steps and
    handed, the same object, to the window."""

    def __init__(self, cell: Dict[str, Any], seed: int, t_start: float,
                 say: Callable[[str], None],
                 sabotage: Optional[Callable] = None) -> None:
        import jax

        from veles_tpu import prng
        from veles_tpu.caches import cache_path
        from veles_tpu.znicz.standard_workflow import StandardWorkflow

        self.cell, self.seed, self.say, self.t_start = cell, seed, say, t_start
        cfg, tr = cell["config_data"], cell["traffic_data"]
        self.cfg, self.tr = cfg, tr
        self.marks = {"import": time.perf_counter() - t_start}
        self.devices = jax.devices()[:cell["chips"]]
        if tr["mesh"] not in ("none", "data"):
            raise ValueError(f"unknown mesh {tr['mesh']!r}")
        on_mesh = tr["mesh"] == "data"
        self.n_shards = len(self.devices) if on_mesh else 1
        self.batch = cfg["batch_per_chip"] * self.n_shards
        self.work_dir = cache_path("benchmark", cell["name"])
        os.makedirs(self.work_dir, exist_ok=True)

        # -- the program: workflow, step, state -----------------------------
        prng.seed_all(seeded.host_seed(seed))
        self.loader, self.pack = build_loader(cell, self.batch, seed,
                                              self.work_dir)
        self._mark("data")
        self.wf = StandardWorkflow(
            layers=program_layers(cfg), loader=self.loader, loss="softmax",
            n_classes=cfg["n_classes"],
            decision_config={"max_epochs": 10 ** 9,
                             "fail_iterations": 10 ** 9},
            gd_config=dict(cfg["optimizer"]), name="bench_" + cfg["name"])
        self.wf.initialize(device=None)
        self._mark("initialize")
        mesh = None
        if on_mesh:
            from jax.sharding import NamedSharding, PartitionSpec

            from veles_tpu.parallel.mesh import make_mesh
            mesh = make_mesh(self.devices)
            whole = NamedSharding(mesh, PartitionSpec())   # on every chip
        else:
            whole = jax.sharding.SingleDeviceSharding(self.devices[0])
        wire = self.wf._wire_spec() if tr["source"] == "feed" else None
        step = self.wf.build_fused_step(
            mesh=mesh, compute_dtype=cfg["compute_dtype"],
            input_normalize=wire["normalize"] if wire else None)
        self.step = sabotage(step) if sabotage is not None else step
        self.state = self.step.init_state()
        self.wkey = seeded.stream_key(seed, "weights")
        want = jax.eval_shape(lambda k: seeded.make_params(cfg, k), self.wkey)
        have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            self.state["params"])
        if jax.tree.structure(want) != jax.tree.structure(have) or \
                jax.tree.leaves(want) != jax.tree.leaves(have):
            raise RuntimeError("the program's parameters are not the "
                               f"configuration's: {have} != {want}")
        if ops_count.n_params(cfg) != cfg["n_params"]:
            raise RuntimeError("n_params of the configuration file is wrong")
        self.state["params"] = jax.jit(
            lambda k: seeded.make_params(cfg, k),
            out_shardings=whole)(self.wkey)
        self.state["key"] = jax.device_put(
            seeded.stream_key(seed, "dropout"), whole)

        # -- the inputs --------------------------------------------------------
        self.feed = self.resident = None
        if tr["source"] == "feed":
            from veles_tpu.loader.device_feed import DeviceFeed
            if wire is not None and hasattr(self.loader, "set_emit"):
                self.loader.set_emit(wire["emit"])
            self.loader.on_device = False
            norm = wire["normalize"] if wire else None
            stated = tr.get("normalize")
            if norm is None or norm.get("mean") is not None \
                    or stated is None \
                    or abs(norm["scale"] - stated["scale"]) > 1e-12 \
                    or norm["offset"] != stated["offset"]:
                raise RuntimeError(f"the loader's wire {norm} is not the "
                                   f"traffic's {stated}")
            self.feed = DeviceFeed.for_step(self.loader, self.step,
                                            ahead=tr["feed_ahead"])
        else:
            if mesh is not None:
                specs = self.step.input_put_specs()
                xsh, ysh = (NamedSharding(mesh, sp) for sp in specs[:2])
            else:
                xsh = ysh = whole
            self.resident = jax.jit(
                lambda kx, ky: seeded.make_resident_batch(
                    cfg, self.batch, kx, ky),
                out_shardings=(xsh, ysh))(
                    seeded.stream_key(seed, "inputs"),
                    seeded.stream_key(seed, "labels"))
            jax.block_until_ready(self.resident)
        self._mark("state")
        self.check_steps = int(tr.get("check_steps", CHECK_STEPS))
        self.k = 0
        self.feed_block_ms: List[float] = []
        self.pending: deque = deque()

    def _mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter() - self.t_start

    # -- the loop ----------------------------------------------------------------

    def dispatch(self):
        """One pass of the loop: next -> train -> prefetch. Returns the
        loss (not waited for) and the batch as it was fed."""
        import jax
        ann = jax.profiler.TraceAnnotation
        with jax.profiler.StepTraceAnnotation("bench.step", step_num=self.k):
            if self.feed is not None:
                with ann("bench.feed_next"):
                    b = self.feed.next()
                x, y, w = b.x, b.y, b.w
                self.feed_block_ms.append(1e3 * b.loader_block_s)
            else:
                (x, y), w = self.resident, None
            with ann("bench.dispatch"):
                self.state, (loss, _n_err) = self.step.train(
                    self.state, x, y, w)
            if self.feed is not None:
                with ann("bench.prefetch"):
                    self.feed.prefetch()
        self.k += 1
        return loss, (x, y, w)

    def sync_oldest(self):
        """Wait for the oldest step in flight; its completion stamp."""
        import jax
        loss = self.pending.popleft()
        with jax.profiler.TraceAnnotation("bench.sync"):
            loss.block_until_ready()
        return time.perf_counter(), loss

    # -- the first steps, which the reference follows -----------------------------

    def first_steps(self) -> Dict[str, Any]:
        """Drive the step from the seed through CHECK_STEPS steps by the
        window's own call and feed. Returns each loss and the per-leaf
        norm of the parameters' change; the velocity after the first step
        is kept (`first_grad`)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg

        def norms(tree) -> Dict[str, Any]:
            return {f"{i}.{name}": jnp.sqrt(jnp.sum(jnp.square(a)))
                    for i, layer in enumerate(tree)
                    for name, a in layer.items()}

        @jax.jit
        def dparam_norms(params, k):
            return norms(jax.tree.map(jnp.subtract, params,
                                      seeded.make_params(cfg, k)))

        prog: Dict[str, Any] = {"loss": []}
        self.fed = []
        for i in range(self.check_steps):
            loss, (x, y, w) = self.dispatch()
            if i == 0:
                # kept on the host until the reference has its own
                self.vel1 = jax.device_get(self.state["vel"])
            if self.feed is not None:
                self.fed.append((np.asarray(x), np.asarray(y),
                                 np.asarray(w)))
            prog["loss"].append(float(loss))
        prog["dparam_norm"] = dparam_norms(self.state["params"], self.wkey)
        prog["dparam_norm"] = {n: float(v)
                               for n, v in prog["dparam_norm"].items()}
        self._mark("first_steps")
        return prog

    # -- after the window ----------------------------------------------------------

    def free_program(self) -> None:
        """The program's state and feed go, so that the reference has the
        chip to itself and the memory peak stays the program's."""
        import jax
        if self.feed is not None:
            self.feed.stop()
        for a in jax.tree.leaves(self.state):
            a.delete()
        self.state = None

    def first_grad(self):
        """The program's first gradient as its optimizer got it, from the
        velocity after one step from rest: v1 = -rate (g + wd w0)."""
        import jax
        cfg, opt = self.cfg, self.cfg["optimizer"]

        def unwind(vel, k):
            p0 = seeded.make_params(cfg, k)
            return tuple(
                {name: -vl[name].reshape(-1)[:p.size].reshape(p.shape)
                 / (opt["learning_rate"] * (opt["learning_rate_bias"]
                                            if p.ndim == 1 else 1.0))
                 - opt["weights_decay"] * p for name, p in pl.items()}
                for vl, pl in zip(vel, p0))
        return jax.jit(unwind)(self.vel1, self.wkey)

    def check_against_reference(self, prog: Dict[str, Any]):
        """(program's readings completed, the reference's): run after
        `free_program`, shared by a run and by `read_limits.py`."""
        g_prog = self.first_grad()
        prog["grad_norm"] = reference.leaf_norms(g_prog)
        return prog, self.reference(first_grad_of_program=g_prog)

    def reference(self, precision: str = "float32", **kw: Any
                  ) -> Dict[str, Any]:
        """The plain reference over the same first steps, on one device."""
        import jax
        cfg = self.cfg
        if self.feed is not None:
            batches = self.fed
        else:
            x, y = (jax.device_put(a, self.devices[0])
                    for a in self.resident)
            batches = [(x, y, None)] * self.check_steps
        params0 = jax.jit(lambda k: seeded.make_params(cfg, k))(self.wkey)
        return reference.reference_steps(
            cfg, params0, seeded.stream_key(self.seed, "dropout"), batches,
            n_shards=self.n_shards,
            block_rows=int(cfg["reference_block_rows"]),
            normalize=self.tr.get("normalize"), precision=precision, **kw)

    def fed_rows_wrong(self) -> Optional[Dict[str, Any]]:
        """Every row the feed delivered in the first steps, against the
        pack made from the seed: bytes and label."""
        if self.feed is None:
            return None
        data, labels = self.pack
        tags = {int(t): i for i, t in enumerate(seeded.row_tags(data))}
        wrong = 0
        for x, y, w in self.fed:
            for r, t in enumerate(seeded.row_tags(x)):
                i = tags.get(int(t), -1)
                if w[r] > 0 and (i < 0 or y[r] != labels[i]
                                 or not np.array_equal(x[r], data[i])):
                    wrong += 1
        return {"name": "fed_rows_wrong", "value": wrong, "limit": 0,
                "ok": wrong == 0, "at": f"{len(self.fed)} batches"}


def run(cell: Dict[str, Any], manifest, *, seed: int, seconds: float,
        trace: bool, t_start: float, say: Callable[[str], None],
        sabotage: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of one cell. `sabotage(step)` may wrap the timed step
    (tests break the timed path underneath and see `correct` go false)."""
    import jax

    from veles_tpu.caches import enable_compilation_cache

    clock = CompileClock()
    say(f"compile cache: {enable_compilation_cache()}")
    ses = TrainSession(cell, seed, t_start, say, sabotage)
    tr, cfg, devices = ses.tr, ses.cfg, ses.devices
    prog = ses.first_steps()

    # -- warm-up, then the window, in one unbroken loop -----------------------
    # what set-up built (units, host buffers) is out of the collector's way,
    # so that a full collection inside the window has little to walk
    gc.collect()
    gc.freeze()
    lag = int(tr.get("steps_in_flight", 1))
    for _ in range(max(2, lag, int(tr["warmup_steps"]))):
        ses.pending.append(ses.dispatch()[0])
        if len(ses.pending) > lag:
            ses.sync_oldest()
    compile_s, n_compiled = clock.seconds, clock.n
    say(f"host: peak rss {host_rss_gb():.2f} GB when the window opens")
    feed0 = ses.feed.stats() if ses.feed is not None else None
    ses.pending.append(ses.dispatch()[0])   # queued behind the last warm-up
    n_block0 = len(ses.feed_block_ms)
    t_open, _ = ses.sync_oldest()
    setup_s = t_open - t_start
    stamps, losses = [t_open], []
    tracing, traced_from, trace_dir = False, None, None
    while True:
        if trace and traced_from is None and len(stamps) > 4:
            trace_dir = os.path.join(ses.work_dir, "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            # the loop's own spans are TraceAnnotations; Python's call
            # tracer would only slow the host and swell the trace
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = int(tr.get("host_tracer_level", 2))
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing, traced_from = True, ses.k
            say(f"trace: started at step {ses.k}")
        ses.pending.append(ses.dispatch()[0])
        t, loss = ses.sync_oldest()
        stamps.append(t)
        losses.append(loss)
        if tracing and ses.k - traced_from >= int(tr["trace_steps"]) + 2:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            tracing = False
            say(f"trace: stopped at step {ses.k}, which took "
                f"{time.perf_counter() - t_stop:.1f} s; host peak rss "
                f"{host_rss_gb():.2f} GB")
        if t - t_open >= seconds and not tracing:
            break
    window_s = stamps[-1] - t_open
    say(f"host: peak rss {host_rss_gb():.2f} GB when the window closes")
    compiled_in_window = clock.n - n_compiled
    while ses.pending:                      # the steps still in flight
        ses.sync_oldest()
    feed1 = ses.feed.stats() if ses.feed is not None else None
    losses = [float(v) for v in losses]
    attempted = len(losses)
    failed = sum(not np.isfinite(v) for v in losses)

    # -- what the run read ------------------------------------------------------
    # (the CPU backend of the tests reports no memory statistics)
    mem = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(device_peak_bytes(m) for m in mem)
    say(f"memory: {mem[0]}")
    counters: Dict[str, Any] = {
        "compile_s": compile_s, "n_compiled": n_compiled,
        "window_s": window_s, "steps": attempted, "batch": ses.batch,
        "chips": len(devices), "peak_bytes": peak_bytes,
        "feed_block_ms": ses.feed_block_ms[n_block0:],
        "flops_per_step": ops_count.train_flops_per_sample(cfg)
        * cfg["batch_per_chip"],
    }
    if ses.feed is not None:
        counters["feed_wait_s"] = sum(
            feed1[n] - feed0[n] for n in ("loader_block_s", "put_block_s"))
        say("feed: " + ", ".join(
            f"{n}={feed1[n]}" for n in ("batches", "bytes_per_batch",
                                        "uint8_wire", "loader_block_s",
                                        "put_block_s", "on_demand")))
    prev = 0.0
    for name in ("import", "data", "initialize", "state", "first_steps"):
        say(f"setup: {name} {ses.marks[name] - prev:.2f} s")
        prev = ses.marks[name]
    say(f"setup: warm-up {setup_s - prev:.2f} s; XLA compiled "
        f"{n_compiled} programs in {compile_s:.2f} s during set-up")
    if hasattr(ses.step, "variant_table"):
        say(f"variants: {ses.step.variant_table()}")
    acct = getattr(ses.step, "collective_accounting", lambda: None)()
    if acct:
        say(f"collective byte model (a count, no metric): {acct}")

    # -- the program's state goes, then the reference runs ---------------------
    ses.free_program()
    t_ref = time.perf_counter()
    prog, ref = ses.check_against_reference(prog)
    checks = reference.compare(prog, ref, cell["limits"])
    rows = ses.fed_rows_wrong()
    if rows is not None:
        checks.append(rows)
    checks.append({"name": "compiled_in_window", "value": compiled_in_window,
                   "limit": 0, "ok": compiled_in_window == 0, "at": "-"})
    for row in checks:
        say(f"check: {row['name']} = {row['value']:.6g} (limit "
            f"{row['limit']:.6g}) at {row['at']}: "
            f"{'ok' if row['ok'] else 'NOT OK'}")
    say(f"check: reference took {time.perf_counter() - t_ref:.1f} s; program "
        f"losses {prog['loss']}, reference {ref['loss']}")
    correct = all(row["ok"] for row in checks) and failed == 0

    spans = span_means(stamps, int(tr["span_steps"]))
    p95 = 1e3 * percentile(spans, 95) if spans else None
    end_to_end = {
        tr.get("rate_metric", "train_samples_per_s_per_chip"):
            attempted * ses.batch / window_s / len(devices),
        "step_ms_p95": p95,
        "setup_s": setup_s,
    }
    counters["step_ms_p95"] = p95
    say(f"window: {attempted} steps in {window_s:.3f} s, median span "
        f"{1e3 * percentile(spans, 50) if spans else float('nan'):.3f} ms "
        f"over {len(spans)} spans of {tr['span_steps']} steps")
    reduced = None
    if trace_dir is not None:
        reduced = trace_reduce.reduce_dir(trace_dir, n_devices=len(devices))
    return {"correct": bool(correct), "attempted": attempted,
            "failed": int(failed), "end_to_end": end_to_end,
            "counters": counters, "trace": reduced, "checks": checks,
            "peak_bytes": peak_bytes, "devices": devices}
