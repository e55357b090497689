"""Benchmark harness: AlexNet training throughput, samples/sec/chip.

Protocol (BASELINE.md): full Krizhevsky geometry (227x227x3, batch 128),
fused train step (forward+backward+update in ONE donated XLA computation),
bf16 compute with f32 master weights, synthetic device-resident batch.
Warmup steps first (compile + cache), then timed windows; the FULL record
(throughput + MFU chain, per-layer FLOPs, scaling prediction, attached
evidence) goes to BENCH_RECORD.json and the LAST stdout line is ONE
compact JSON summary — value, MFU, the lowering-variant table that
produced the number (ops.variants), and the record path. The r4/r5 full
records outgrew the driver's capture window (`parsed: null` two rounds
running); the compact line cannot.

Robustness (a backend can HANG, not just error, and the caller's own
timeout is shorter than a generous retry budget): the top-level process
is a supervisor that never touches jax (a chip belongs to one process
at a time) and runs the measurement in a child subprocess under a TOTAL
deadline (default 540s, env-overridable). After every failed attempt it
immediately prints a flushed, parseable JSON error record (last line
wins — replaced by the success record if a retry lands), and a
SIGTERM/SIGINT handler emits the record even when an outer `timeout`
kills us first. The exit code is NON-ZERO whenever no measurement
landed: a failure record is a failure, and it carries no earlier run's
numbers.

vs_baseline: the reference's published numbers are unrecoverable (empty
mount, BASELINE.json "published": {}); the denominator is this repo's own
round-1 measured floor so later rounds show progress against it.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

# Round-1 measured floor (samples/sec/chip, single v5e chip), measured
# 2026-07-29 on TPU v5 lite via this harness. Later rounds report
# vs_baseline against it so progress/regressions are visible.
ROUND1_FLOOR = 8622.0

METRIC = "alexnet_train_samples_per_sec_per_chip"
UNIT = "samples/s/chip"

# batch-sweep result (r3, TPU v5 lite): 128 -> 6456, 256 -> 8951,
# 512 -> 9620, 1024 -> 9907, 2048 -> 10043 samples/s/chip; 1024 is the
# knee — 2048 adds 1.4% for 2x the compile/input footprint
BATCH = int(os.environ.get("BENCH_BATCH") or "1024")
WINDOWS = int(os.environ.get("BENCH_WINDOWS", "3"))
STEPS_PER_WINDOW = int(os.environ.get("BENCH_STEPS", "20"))

ATTEMPTS = int(os.environ.get("BENCH_ATTEMPTS", "2"))
BACKOFF_S = float(os.environ.get("BENCH_BACKOFF_S", "5"))
# the first XLA compile takes tens of seconds; give the child room — but
# the whole run must fit the caller's window, so the child budget is
# also clipped against TOTAL_DEADLINE_S at each attempt.
CHILD_TIMEOUT_S = float(os.environ.get("BENCH_CHILD_TIMEOUT_S", "420"))
TOTAL_DEADLINE_S = float(os.environ.get("BENCH_TOTAL_DEADLINE_S", "540"))
#: don't start a retry with less than this much budget left
MIN_ATTEMPT_S = 45.0

# peak dense bf16 TFLOP/s per chip for MFU (known device kinds; MFU is
# null on anything unrecognized rather than guessed)
PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,   # v5e: 197 TFLOP/s bf16
    "TPU v5e": 197.0,
    "TPU v4": 275.0,
    "TPU v6 lite": 918.0,   # v6e/Trillium
}


def _mem_record():
    """Per-device memory snapshot (parallel/memstats.py) embedded next
    to the measured number: live-array bytes per device everywhere, the
    allocator's peak where the backend reports one (TPU). Guarded like
    _audit_record — accounting must never cost the measured value."""
    try:
        from veles_tpu.parallel.memstats import device_memory_stats
        return device_memory_stats()
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)[:200]}


def _memory_record(step, x, y, w=None):
    """Predicted-vs-measured per-device memory (analysis pass 6,
    ISSUE 14), embedded next to the memstats snapshot: the static
    resident/high-water prediction for the step that was measured, and
    the measured live/peak maxima to hold it against. trace=False — the
    STATIC model only; the accounting must never cost the measured
    value a make_jaxpr walk. Guarded like _mem_record."""
    try:
        from veles_tpu.analysis.resources import step_resource_report
        rep = step_resource_report(step, x, y, w, trace=False)
        meas = _mem_record() or {}
        return {
            "predicted_per_device": {
                "resident": rep["resident_per_device"],
                "highwater": rep["highwater_per_device"],
                "static_only": rep.get("static_only"),
                "components": rep["components"],
            },
            "measured": {
                "live_bytes_max": meas.get("live_bytes_max"),
                "peak_bytes_max": meas.get("peak_bytes_max"),
            },
        }
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)[:200]}


def _telemetry_overhead(step_time_s: float) -> dict:
    """Measured tracing-on vs tracing-off A/B: the record proves what
    --trace costs relative to THIS run's measured step time. `on` times
    real `with ring.span(...)` blocks into a live ring buffer; `off`
    times what the program runs when nothing records: `tracer.span()`
    returning its shared no-op (no ring installed, no profiler session
    open). The driver loop emits at most 8 spans per training step
    (feed.next, dispatch, the in-flight window, decision, prefetch + the
    produce trio), so overhead_frac = 8 x (on - off) / step_time — the
    <1% tracing budget, asserted by a slow-marker test. Guarded like the
    other accounting: telemetry must never cost the measured value."""
    try:
        from veles_tpu.telemetry import tracer
        n = 2000
        ring = tracer.Tracer(capacity=4096)
        t0 = time.perf_counter()
        for _ in range(n):
            with ring.span("bench.overhead", "bench"):
                pass
        on_s = (time.perf_counter() - t0) / n
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.span("bench.overhead", "bench"):
                pass
        off_s = (time.perf_counter() - t0) / n
        spans_per_step = 8
        per_step_s = spans_per_step * max(0.0, on_s - off_s)
        return {
            "span_pair_us": round(on_s * 1e6, 3),
            "disabled_guard_us": round(off_s * 1e6, 4),
            "spans_per_step": spans_per_step,
            "overhead_frac": (round(per_step_s / step_time_s, 6)
                              if step_time_s > 0 else None),
        }
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)[:200]}


def _mirror_bench_metrics(n_steps: int, step_time_s: float,
                          n_examples: float) -> None:
    """Route the bench child's measured numbers through the ONE
    telemetry registry and mirror the flush to the JSONL sink next to
    the record file — the same producer every /metrics endpoint
    scrapes, so 'the bench number' and 'the scraped number' cannot
    diverge. Guarded: accounting never costs the measured value."""
    try:
        from veles_tpu.telemetry import metrics as tmetrics
        reg = tmetrics.default_registry()
        reg.counter("veles_step_total").inc(n_steps)
        hist = reg.histogram("veles_step_seconds")
        for _ in range(min(n_steps, 256)):  # bounded mirror of the
            hist.observe(step_time_s)       # measured per-step time
        reg.counter("veles_examples_total").inc(n_examples)
        if step_time_s > 0:
            reg.gauge("veles_examples_per_second").set(
                n_examples / (n_steps * step_time_s))
        tmetrics.install_jsonl(RECORD_PATH + ".telemetry.jsonl")
        tmetrics.flush_installed(extra={"source": "bench"})
    except Exception:  # noqa: BLE001
        pass


def _audit_record(step, x_shape, y_shape=None, state=None) -> dict:
    """Jaxpr-audit summary (analysis/trace.py) embedded in the record
    next to `variants`: the measured number ships with the auditor's
    verdict on the step that produced it (dtype leaks, host syncs,
    dropped donation, sharding drift). Host-side trace only — values are
    zeros, no device transfer — and guarded: analysis must never cost
    the measured value."""
    try:
        from veles_tpu.analysis.findings import summarize
        from veles_tpu.analysis.trace import audit_fused_step
        x = np.zeros(x_shape, np.float32)
        y = np.zeros(y_shape or (x_shape[0],), np.int32)
        return summarize(audit_fused_step(step, x, y, state=state))
    except Exception as e:  # noqa: BLE001
        return {"error": str(e)[:200]}


def analytic_flops_per_sample(step) -> tuple:
    """(train_flops, per-layer forward GFLOPs) from the fused step's
    forward units. Counts MXU work (conv + matmul MACs) over EVERY
    matmul-bearing param the unit exposes (so attention wq/wk/wv/wo,
    SeqFFN w1/w2, LSTM gate matrices and MoE expert tensors all count,
    not just params literally named "weights"); elementwise ops are
    bandwidth-bound and excluded. Training = 3x forward (grad wrt input
    + grad wrt weights each cost ~one forward)."""
    fwd_flops = 0.0
    per_layer = {}
    for i, u in enumerate(step.forwards):
        layer_macs = 0.0
        out = u.output.shape if getattr(u, "output", None) else ()
        inp = (u.input.shape if getattr(u, "input", None) else ())
        # Matmuls apply once per TOKEN: (N, S, C) outputs carry S tokens
        # per sample; flattened (N*T, H) outputs (LSTM scan, SeqSoftmax)
        # reveal T as the row blow-up over the (N, ...) input.
        if len(out) == 3:
            tokens = out[1]
        elif (len(out) == 2 and inp and out[0] >= inp[0]
              and out[0] % inp[0] == 0):
            tokens = out[0] // inp[0]
        else:
            tokens = 1
        # EXACT bias/table names across the unit zoo ("bias", LSTM gate
        # "b", MoE expert-stacked "b1"/"b2" (E,H), positional tables) —
        # an exact set, not a startswith, so a future matmul param named
        # e.g. "beta" is counted, not silently dropped
        non_matmul = {"bias", "b", "b1", "b2"}
        # MoE routing fan-out: each token visits top_k experts (today's
        # units route top-1 and carry no attribute; derived, not assumed)
        top_k = int(getattr(u, "top_k", 1))
        for pname, arr in u.param_arrays().items():
            if not arr or pname in non_matmul or "pos" in pname:
                continue
            ws = arr.shape
            if len(ws) == 4:        # conv HWIO: (kh, kw, cin, cout)
                layer_macs += (out[1] * out[2]
                               * ws[0] * ws[1] * ws[2] * ws[3])
            elif len(ws) == 2:      # any (in, out) matmul
                layer_macs += tokens * ws[0] * ws[1]
            elif len(ws) == 3:      # MoE expert stack (E, in, out)
                layer_macs += top_k * tokens * ws[1] * ws[2]
        if layer_macs:
            fwd_flops += 2.0 * layer_macs
            per_layer[f"{i}:{type(u).__name__}"] = round(
                2.0 * layer_macs / 1e9, 3)
    return 3.0 * fwd_flops, per_layer


def apply_ab_overrides() -> None:
    """A/B-winner overrides for EVERY measuring child (device-only and
    e2e alike — a merged record must measure ONE configuration), applied
    as lowering-variant registry selections (ops.variants):
    BENCH_LRN = recompute | cached | pallas; BENCH_POOL = slices;
    BENCH_AUTOTUNE=1 additionally loads the persisted autotune-cache
    winners (both children — a merged record must measure ONE
    configuration), with explicit env pins WINNING over cache hits
    (callers re-invoke this after apply_cached): an on-chip A/B re-runs
    the bench with the measured winner via these BEFORE any source
    default flips."""
    from veles_tpu.ops import variants
    lrn_mode = os.environ.get("BENCH_LRN", "")
    if lrn_mode:
        table = {"recompute": "banded_matmul", "cached": "cached_residual",
                 "pallas": "pallas_one_pass"}
        if lrn_mode not in table:
            # fail LOUDLY: a typo silently measuring the default config
            # would be recorded as the "winner applied" headline
            raise SystemExit(f"unknown BENCH_LRN {lrn_mode!r} "
                             "(want recompute|cached|pallas)")
        variants.select("lrn", table[lrn_mode])
    if os.environ.get("BENCH_POOL") == "slices":
        variants.select("maxpool", "slices")


def _apply_cached_winners(wf) -> None:
    """BENCH_AUTOTUNE=1: inherit a tuning session's persisted winners
    (cache hits only, zero timing — the deadline stays for measuring),
    then RE-apply the env pins so an explicit BENCH_LRN/BENCH_POOL wins
    over the cache (the watcher's 'measure THIS variant' contract).
    Runs in BOTH children: a merged record must measure ONE config."""
    if os.environ.get("BENCH_AUTOTUNE") != "1":
        return
    from veles_tpu.ops.autotune import apply_cached
    applied = apply_cached(wf, compute_dtype="bfloat16")
    sys.stderr.write(f"bench: autotune cache applied {applied or 'nothing'}"
                     " (misses keep defaults)\n")
    apply_ab_overrides()


def child_main() -> None:
    import jax

    from veles_tpu.caches import enable_compilation_cache
    enable_compilation_cache()

    from veles_tpu import prng
    from veles_tpu.samples.alexnet import create_workflow

    apply_ab_overrides()
    prng.seed_all(1234)
    # On a multi-chip host, shard the data axis over every local chip so
    # the per-chip division below matches where the work actually ran; a
    # single chip uses the local fast path (same scanned hot loop).
    n_chips = jax.local_device_count()
    mesh = None
    batch = BATCH
    if n_chips > 1:
        from veles_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(jax.devices(), data=n_chips)
        batch = BATCH * n_chips
    # width/resolution knobs for CPU smoke runs of the harness itself
    # (full geometry takes minutes to compile on XLA:CPU); the TPU
    # protocol always runs width 1.0 at 227²
    width = float(os.environ.get("BENCH_WIDTH", "1.0"))
    kw = {}
    if width != 1.0:
        kw = dict(width_mult=width, fc_width=int(4096 * width) or 64,
                  input_hw=int(os.environ.get("BENCH_HW", "67")))
    wf = create_workflow(minibatch_size=batch, n_train=2 * batch,
                         n_validation=batch, **kw)
    wf.initialize(device=None)
    _apply_cached_winners(wf)
    step = wf.build_fused_step(mesh=mesh, compute_dtype="bfloat16")
    state = step.init_state()
    train_flops, layer_gflops = analytic_flops_per_sample(step)

    # Synthesize the batch ON DEVICE: a jitted PRNG program transfers
    # nothing (a batch-1024 f32 image tensor is ~630 MB of H2D) and
    # leaves the batch resident — this mode measures the device only.
    import jax.numpy as jnp
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    in_shape = (batch,) + tuple(wf.loader.minibatch_data.shape[1:])
    x = jax.jit(lambda k: jax.random.normal(k, in_shape, jnp.float32))(k1)
    y = jax.jit(lambda k: jax.random.randint(k, (batch,), 0, 64))(k2)

    sync = jax.block_until_ready

    # One dispatch per window via the scanned repeat trainer (real
    # per-minibatch updates; removes host->device dispatch latency from
    # the measurement). train_repeat keeps ONE batch resident
    # (train_many's (K, batch, ...) stack is 12+ GB at batch 1024).
    state, _ = step.train_repeat(state, x, y, STEPS_PER_WINDOW)  # warmup
    sync(state)

    rates = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        state, _ = step.train_repeat(state, x, y, STEPS_PER_WINDOW)
        sync(state)
        dt = time.perf_counter() - t0
        rates.append(batch * STEPS_PER_WINDOW / dt)

    value = float(np.median(rates))
    per_chip = value / n_chips
    step_time_s = batch / value
    _mirror_bench_metrics(WINDOWS * STEPS_PER_WINDOW, step_time_s,
                          float(batch) * WINDOWS * STEPS_PER_WINDOW)
    tflops = per_chip * train_flops / 1e12
    kind = jax.devices()[0].device_kind
    peak = PEAK_TFLOPS.get(kind)
    # the falsifiable v5e-64 weak-scaling prediction from THIS run's
    # measured step time (ROOFLINE.md r5; inputs echoed in the record).
    # Guarded: an exception here must never cost the measured value the
    # supervisor's whole design exists to protect.
    try:
        from veles_tpu.parallel.scaling_model import predict_dp_scaling
        n_params = sum(int(v.size) for layer in state["params"]
                       for v in layer.values())
        pred = predict_dp_scaling(grad_bytes=4 * n_params,
                                  step_time_s=BATCH / per_chip,
                                  batch_per_chip=BATCH, mesh_shape=(8, 8))
        scaling_rec = {
            "predicted_efficiency": round(
                pred["predicted_efficiency"], 4),
            "batch_per_chip_at_90pct": round(
                pred["batch_per_chip_at_target"], 1),
            "allreduce_ms": round(1e3 * pred["allreduce_time_s"], 3),
            "inputs": pred["inputs"],
        }
    except Exception as e:  # noqa: BLE001
        scaling_rec = {"error": str(e)[:200]}
    # the planner's predicted block (analysis pass 7) next to the
    # measured number: every bench run doubles as a calibration point
    # for the whole-system model. pred_err = predicted/measured - 1
    # per-chip rate, surfaced on the compact line; None when the
    # device kind has no committed MFU sweep (docs/PLANNER.md).
    predicted_rec, pred_err = None, None
    try:
        from veles_tpu.analysis import planner as _planner
        _n_params = sum(int(v.size) for layer in state["params"]
                        for v in layer.values())
        _prof = step.resource_profile() \
            if hasattr(step, "resource_profile") else {}
        _vt = step.variant_table()
        predicted_rec = _planner.predict_for_bench(
            n_params=_n_params,
            train_flops_per_sample=train_flops,
            device_kind=kind, n_chips=n_chips, batch_per_chip=BATCH,
            zero_active=bool(_prof.get("zero_active")),
            wire=_vt.get("grad_reduce") or "f32",
            fused=bool(getattr(step, "fusion_pairs", lambda: ())()),
            input_hw=int(x.shape[1]))
        if predicted_rec.get("calibrated"):
            pred_err = round(
                predicted_rec["samples_per_sec_per_chip"] / per_chip
                - 1.0, 4)
    except Exception as e:  # noqa: BLE001 - must never cost the number
        predicted_rec = {"error": str(e)[:200]}
    print(json.dumps({
        "metric": METRIC,
        "value": round(per_chip, 2),
        "unit": UNIT,
        "vs_baseline": round(per_chip / ROUND1_FLOOR, 3),
        "tflops_per_chip": round(tflops, 2),
        "mfu": round(tflops / peak, 4) if peak else None,
        "device_kind": kind,
        "n_chips": n_chips,
        "batch_per_chip": BATCH,
        # the lowerings that produced this number (ops.variants): the
        # driver finally sees WHICH variant table was measured
        "variants": step.variant_table(),
        # ZeRO collective byte attribution (ISSUE 12): the modeled
        # per-device grad_reduce/all-gather egress this step moves per
        # train step, by link leg — None off the registry-scatter path
        "collectives": (step.collective_accounting()
                        if hasattr(step, "collective_accounting")
                        else None),
        # the jaxpr auditor's verdict on the step that was measured
        # (analysis pass 2; docs/ANALYSIS.md)
        "analysis": _audit_record(step, in_shape, state=state),
        # per-device memory under the measured config (memstats): the
        # ZeRO optimizer-state delta is a recorded number, not a claim
        "device_memory": _mem_record(),
        # predicted-vs-measured per-device memory (analysis pass 6):
        # the static HBM model for the measured step, held against the
        # memstats maxima right next to it
        "memory": _memory_record(step, x, y),
        # the measured price of --trace relative to THIS step time
        # (the <1% tracing budget, A/B on/off)
        "telemetry": _telemetry_overhead(step_time_s),
        "train_gflops_per_sample": round(train_flops / 1e9, 3),
        "fwd_layer_gflops_per_sample": layer_gflops,
        "scaling_prediction_v5e64": scaling_rec,
        # analysis pass 7: the whole-system model's prediction for
        # THIS measured config (step time, comms bytes, HBM
        # high-water) — the planner's standing calibration loop
        "predicted": predicted_rec,
        "pred_err": pred_err,
    }))


def e2e_child_main() -> None:
    """BENCH_MODE=e2e: END-TO-END throughput — the north-star metric's
    full definition (BASELINE.md:18 includes the host input pipeline).

    Path measured: packed uint8 memmap dataset on disk -> MemmapImageLoader
    (RAM-preloaded shards, background-thread gather, raw uint8 leaves the
    host) -> the SHARED DeviceFeed (loader/device_feed.py: async
    device_put one batch ahead — batch k+1 transfers while step k
    computes) -> fused AlexNet train step with a leading input_normalize
    layer (float conversion + scaling on device, where it fuses into
    conv1's HBM read). This is the exact implementation the production
    loop (_run_with_step) trains through — no bespoke bench loop.

    Reports e2e samples/s plus the device-only rate measured in the same
    process, so overlap efficiency = e2e / device_only is explicit."""
    import jax

    from veles_tpu.caches import enable_compilation_cache
    enable_compilation_cache()

    from veles_tpu import prng
    from veles_tpu.loader.device_feed import DeviceFeed
    from veles_tpu.loader.memmap import MemmapImageLoader, pack_arrays
    from veles_tpu.samples.alexnet import alexnet_layers
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    batch = BATCH
    hw = 227
    n = int(os.environ.get("BENCH_E2E_SAMPLES", str(4 * batch)))
    n_workers = int(os.environ.get("BENCH_E2E_WORKERS", "4"))
    width = float(os.environ.get("BENCH_E2E_WIDTH", "1.0"))  # CPU smoke
    pack_dir = f"/tmp/veles_e2e_{hw}_{n}"
    if not os.path.exists(os.path.join(pack_dir, "manifest.json")):
        rng = np.random.RandomState(7)
        data = rng.randint(0, 256, (n, hw, hw, 3), dtype=np.uint8)
        pack_arrays(pack_dir, data, rng.randint(0, 64, n).astype(np.int64),
                    [0, 0, n], shard_mb=256.0)

    apply_ab_overrides()
    prng.seed_all(1234)
    loader = MemmapImageLoader(
        data_path=pack_dir, minibatch_size=batch, emit="uint8",
        preload=True, mean_normalize=False, n_workers=n_workers,
        prefetch=3)
    wf = StandardWorkflow(
        layers=[{"type": "input_normalize"}]
        + alexnet_layers(64, width, int(4096 * width) or 64),
        loader=loader, loss="softmax", n_classes=64,
        decision_config={"max_epochs": 999, "fail_iterations": 999},
        gd_config={"learning_rate": 0.01, "gradient_moment": 0.9},
        name="AlexNetE2E")
    wf.initialize(device=None)
    loader.on_device = False   # the feed does the (async) device_put
    _apply_cached_winners(wf)
    step = wf.build_fused_step(compute_dtype="bfloat16")
    state = step.init_state()
    feed = DeviceFeed.for_step(loader, step, ahead=1)

    sync = jax.block_until_ready

    # -- device-only rate, SAME per-step dispatch protocol on one
    # resident batch (not train_repeat: lax.scan bodies lose intra-op
    # parallelism on XLA:CPU, which would corrupt smoke-run ratios; on
    # TPU the two protocols agree to a few %) --
    warm = feed.next()
    state, _ = step.train(state, warm.x, warm.y, warm.w)  # compile + warm
    sync(state)
    dev_rates = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(STEPS_PER_WINDOW):
            state, _ = step.train(state, warm.x, warm.y, warm.w)
        sync(state)
        dev_rates.append(batch * STEPS_PER_WINDOW
                         / (time.perf_counter() - t0))
    device_only = float(np.median(dev_rates))

    # -- loader-only rate: the host half of the decomposition (gather +
    # page-in, no device work). Enough batches to amortize the already-
    # filled prefetch window (prefetch=3 near-free pops would otherwise
    # inflate the rate) --
    from veles_tpu.loader.memmap import loader_throughput
    loader_rate = loader_throughput(
        loader, n_batches=max(32, 2 * STEPS_PER_WINDOW))["samples_per_sec"]

    # -- end-to-end: loader -> shared DeviceFeed -> per-step dispatch
    # (prefetch AFTER dispatch: batch k+1's put rides under step k) --
    for _ in range(4):                                   # warm per-step path
        b = feed.next()
        state, _ = step.train(state, b.x, b.y, b.w)
        feed.prefetch()
    sync(state)
    rates = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(STEPS_PER_WINDOW):
            b = feed.next()
            state, _ = step.train(state, b.x, b.y, b.w)
            feed.prefetch()
        sync(state)
        rates.append(batch * STEPS_PER_WINDOW / (time.perf_counter() - t0))
    value = float(np.median(rates))
    feed_stats = feed.stats()
    feed.stop()   # also stops the loader's produce threads
    _mirror_bench_metrics(WINDOWS * STEPS_PER_WINDOW, batch / value,
                          float(batch) * WINDOWS * STEPS_PER_WINDOW)
    rec = {
        "metric": "alexnet_e2e_samples_per_sec_per_chip",
        "value": round(value, 2),
        "unit": UNIT,
        # vs_baseline compares same-batch protocols (the floor is a
        # batch-1024 figure); any other batch would read as a spurious
        # regression — same treatment as the degraded batch-128 path
        "vs_baseline": (round(value / ROUND1_FLOOR, 3)
                        if batch == 1024 else None),
        "loader_samples_per_sec": round(loader_rate, 2),
        "device_only_same_protocol": round(device_only, 2),
        "overlap_efficiency": round(value / device_only, 4),
        # the shared feed's overlap counters: bytes/batch (uint8 wire =
        # f32/4), time blocked on loader vs device, lookahead health
        "feed": feed_stats,
        "telemetry": _telemetry_overhead(batch / value),
        "variants": step.variant_table(),
        "collectives": (step.collective_accounting()
                        if hasattr(step, "collective_accounting")
                        else None),
        "device_memory": _mem_record(),
        "memory": _memory_record(step, warm.x, warm.y, warm.w),
        "device_kind": jax.devices()[0].device_kind,
        "batch_per_chip": batch,
        "n_samples_packed": n,
        "loader_workers": n_workers,
    }
    print(json.dumps(rec))


#: e2e attach (VERDICT r4 item 5: device_only AND e2e sections in the
#: machine-readable record): after a successful device-only measurement,
#: a SHORT e2e child (small batch/windows) runs in the leftover budget
#: and its record is merged into the final line. BENCH_ATTACH_E2E=0
#: disables; the reserve is the minimum leftover budget to even try.
E2E_RESERVE_S = float(os.environ.get("BENCH_E2E_RESERVE_S", "120"))
E2E_BUDGET_S = float(os.environ.get("BENCH_E2E_BUDGET_S", "240"))


def _run_e2e_attach(env, budget_s: float, state=None):
    """Run the e2e child with tight, short-run settings; return its parsed
    record, or a structured error record (never raises, never hangs past
    budget_s). Registers the child in `state` so the supervisor's signal
    handler can kill it — an orphaned e2e child would hold the chip."""
    e2e_env = dict(env, BENCH_MODE="e2e",
                   BENCH_BATCH=os.environ.get("BENCH_E2E_ATTACH_BATCH",
                                              "256"),
                   BENCH_STEPS="5", BENCH_WINDOWS="2",
                   BENCH_E2E_SAMPLES=os.environ.get(
                       "BENCH_E2E_ATTACH_SAMPLES", "1024"))
    child = None
    try:
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=e2e_env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        if state is not None:
            state["child"] = child
        out, err = child.communicate(timeout=budget_s)
        lines = [ln for ln in (out or "").splitlines() if ln.strip()]
        if child.returncode == 0 and lines:
            return json.loads(lines[-1])
        tail = (err or out or "").strip().splitlines()
        return {"error": f"e2e child rc={child.returncode}: "
                         + " | ".join(tail[-2:])}
    except subprocess.TimeoutExpired:
        child.kill()
        try:
            child.communicate(timeout=5)   # reap: no zombie per timeout
        except Exception:   # noqa: BLE001
            pass
        return {"error": f"e2e child timed out after {budget_s:.0f}s"}
    except (ValueError, OSError) as e:
        if child is not None and child.poll() is None:
            child.kill()
        return {"error": f"e2e attach failed: {e}"}
    finally:
        if state is not None:
            state["child"] = None


#: stderr markers of transient backend trouble worth a retry; anything
#: else (import error, bad config, ...) is deterministic — fail fast.
TRANSIENT_MARKERS = ("unavailable", "deadline", "failed to connect",
                     "connection", "backend", "socket", "grpc",
                     "resource exhausted")


def _error_record(err: str, attempt: int, provisional: bool = False):
    metric = ("alexnet_e2e_samples_per_sec_per_chip"
              if os.environ.get("BENCH_MODE") == "e2e" else METRIC)
    rec = {"metric": metric, "value": None, "unit": UNIT,
           "vs_baseline": None, "error": err[:500], "attempts": attempt}
    if provisional:
        rec["provisional"] = True
    return rec


#: where the FULL record lands; the stdout line stays compact (a full
#: record once outgrew the caller's capture window, so stdout carries a
#: summary no window can truncate, and the file carries everything)
RECORD_PATH = os.environ.get("BENCH_RECORD_PATH") or os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_RECORD.json")

#: full-record keys the compact stdout line keeps verbatim
_COMPACT_KEYS = ("metric", "value", "unit", "vs_baseline", "mfu",
                 "device_kind", "n_chips", "batch_per_chip", "variants",
                 "telemetry", "pred_err", "provisional",
                 "attempts")


def _compact(rec, record_path) -> dict:
    """The driver-facing summary: headline number, the lowering-variant
    table that produced it, the e2e headline, and where the full record
    file is. Everything bulky (layer tables, scaling inputs) stays in
    the file. `record_path` is None
    when the file write FAILED — the line must then not point the
    driver at a stale file from a previous run.

    The line LEADS with "status": "ok"/"failed" so a reader can
    classify without probing for null values; every emission flows
    through here."""
    out = {"status": "ok" if rec.get("value") is not None else "failed"}
    out.update({k: rec[k] for k in _COMPACT_KEYS if k in rec})
    e2e_feed = (rec.get("e2e") or {}).get("feed") if isinstance(
        rec.get("e2e"), dict) else None
    if isinstance(e2e_feed, dict):
        # one overlap-health number rides the compact line; the full
        # counter set stays in the record file
        out["e2e_uint8_wire"] = e2e_feed.get("uint8_wire")
    coll = rec.get("collectives")
    if isinstance(coll, dict):
        # the bytes-moved claim rides the compact line (ISSUE 12): the
        # measured number names the grad_reduce variant + its modeled
        # per-step DCN/ICI egress; full legs/geometry stay in the file
        out["collectives"] = {"variant": coll.get("variant"),
                              "dcn_bytes": coll.get("dcn_bytes"),
                              "ici_bytes": coll.get("ici_bytes")}
    ana = rec.get("analysis")
    if isinstance(ana, dict) and "errors" in ana:
        # counts only: the per-finding detail lives in the record file
        out["analysis"] = {"errors": ana["errors"],
                           "warnings": ana["warnings"]}
    if rec.get("error"):
        out["error"] = str(rec["error"])[:200]
    e2e = rec.get("e2e")
    if isinstance(e2e, dict):
        out["e2e_value"] = e2e.get("value")
        out["e2e_overlap"] = e2e.get("overlap_efficiency")
        if "variants" not in out and isinstance(e2e.get("variants"), dict):
            out["variants"] = e2e["variants"]
        if e2e.get("error"):
            out["e2e_error"] = str(e2e["error"])[:120]
    out["record"] = record_path
    return out


def _emit(rec) -> None:
    """Publish one measurement record: the FULL record to RECORD_PATH
    (atomic replace; last emission wins, mirroring stdout semantics) and
    ONE compact flushed JSON line to stdout. The driver parses stdout's
    last line, so every emission is complete — a provisional error
    flushed after a failed attempt is superseded by the success record
    of a later attempt, and survives even if we are SIGKILLed next."""
    record_path = RECORD_PATH
    try:
        tmp = f"{RECORD_PATH}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(rec, f, indent=1)
        os.replace(tmp, RECORD_PATH)
    except OSError:
        # a read-only checkout / full disk must not cost the stdout
        # record — but the line must also not point at a STALE file
        record_path = None
    print(json.dumps(_compact(rec, record_path)), flush=True)


def supervise() -> int:
    """Run child_main in a subprocess under a TOTAL deadline sized to the
    driver's capture window; guarantee stdout ends with a parseable JSON
    line no matter what (incl. SIGTERM from an outer `timeout`).

    Returns 0 only when a measurement landed; every failure path —
    exhausted attempts, a deterministic child error, a signal before
    the headline — returns/exits 1 after emitting its error record."""
    t_start = time.monotonic()

    def remaining() -> float:
        return TOTAL_DEADLINE_S - (time.monotonic() - t_start)

    state = {"last_err": "unknown", "attempt": 0, "child": None}

    def on_signal(signum, frame):
        # an outer timeout is killing us: leave a parseable record NOW.
        # If the device-only headline already landed (we may be mid e2e
        # attach), the LAST line must stay that success record, not an
        # error that would erase it.
        ch = state["child"]
        if ch is not None and ch.poll() is None:
            ch.kill()
        if state.get("success_rec") is not None:
            _emit(state["success_rec"])
            os._exit(0)
        _emit(_error_record(
            f"supervisor received signal {signum} after "
            f"{time.monotonic() - t_start:.0f}s; "
            f"last: {state['last_err']}",
            state["attempt"]))
        os._exit(1)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    env = dict(os.environ, BENCH_CHILD="1")
    for attempt in range(1, ATTEMPTS + 1):
        state["attempt"] = attempt
        budget = min(CHILD_TIMEOUT_S, remaining() - 10.0)
        if budget < MIN_ATTEMPT_S:
            state["last_err"] += " | deadline exhausted before retry"
            break
        retryable = True
        try:
            # Popen (not run) so the signal handler can kill the child
            child = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True)
            state["child"] = child
            out, err = child.communicate(timeout=budget)
            state["child"] = None
            lines = [ln for ln in (out or "").splitlines() if ln.strip()]
            if child.returncode == 0 and lines:
                try:
                    rec = json.loads(lines[-1])
                except ValueError:
                    state["last_err"] = \
                        f"unparseable child output: {lines[-1]!r}"
                    retryable = False
                else:
                    # emit the headline NOW: if the e2e attach below
                    # hangs and an outer timeout kills us, the driver
                    # still has this line (the handler re-emits it)
                    _emit(rec)
                    state["success_rec"] = rec
                    if (os.environ.get("BENCH_MODE") != "e2e"
                            and os.environ.get("BENCH_ATTACH_E2E", "1")
                            != "0"
                            and remaining() > E2E_RESERVE_S):
                        e2e = _run_e2e_attach(
                            env, min(remaining() - 15.0, E2E_BUDGET_S),
                            state)
                        full = dict(rec)
                        full["device_only"] = {
                            k: rec[k] for k in
                            ("value", "unit", "mfu", "batch_per_chip",
                             "tflops_per_chip") if k in rec}
                        full["e2e"] = e2e
                        _emit(full)
                        state["success_rec"] = full
                    return 0
            else:
                tail = (err or out or "").strip().splitlines()
                state["last_err"] = (
                    f"child rc={child.returncode}: " + " | ".join(tail[-3:])
                    if tail else f"child rc={child.returncode}, no output")
                retryable = any(m in state["last_err"].lower()
                                for m in TRANSIENT_MARKERS)
        except subprocess.TimeoutExpired:
            child.kill()
            try:
                _, err = child.communicate(timeout=5)
            except Exception:
                err = ""
            state["child"] = None
            tail = (err or "").strip().splitlines()
            state["last_err"] = (
                f"child timed out after {budget:.0f}s "
                "(TPU backend unreachable/hung?)"
                + (": " + " | ".join(tail[-2:]) if tail else ""))
        # incremental record: whatever happens after this instant, the
        # driver already has a parseable line for this failure (the
        # post-loop emit below is the authoritative final record)
        _emit(_error_record(state["last_err"], attempt, provisional=True))
        if not retryable:
            break
        if attempt < ATTEMPTS and remaining() > BACKOFF_S + MIN_ATTEMPT_S:
            sys.stderr.write(
                f"bench attempt {attempt}/{ATTEMPTS} failed: "
                f"{state['last_err']}; retrying in {BACKOFF_S:.0f}s "
                f"({remaining():.0f}s of budget left)\n")
            time.sleep(BACKOFF_S)

    _emit(_error_record(state["last_err"], state["attempt"]))
    return 1


if __name__ == "__main__":
    if os.environ.get("BENCH_CHILD") == "1":
        if os.environ.get("BENCH_MODE") == "e2e":
            e2e_child_main()
        else:
            child_main()
    else:
        sys.exit(supervise())
