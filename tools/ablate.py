"""Layer-family ablation: wall-clock attribution for the AlexNet step.

Usage (on a machine with the TPU visible):
    python tools/ablate.py full no-LRN no-dropout no-bigFC
    python tools/ablate.py --zero          # ZeRO update A/B (needs >=2 devices)
    python tools/ablate.py --collectives   # grad_reduce variant A/B (ISSUE 12)
    python tools/ablate.py --fusion        # fused vs composed lrn+maxpool A/B
                                           # (ISSUE 13; CPU mesh via interpret)
    python tools/ablate.py --plan          # planner top-1 vs hand-set defaults
                                           # (ISSUE 17; measured A/B of the
                                           # analysis-pass-7 config search)

Each variant builds the AlexNet fused train step with a layer family
removed and reports samples/s via train_repeat — the deltas attribute
step time to layer families (the measurement behind ROOFLINE.md).
Lowering-choice variants (s2d-stem, slicepool) are thin wrappers over
the ops.variants registry now — `tools/autotune.py` measures the same
candidates systematically and persists the winner; this script remains
for layer-family REMOVAL attribution, which the registry can't express.

`--zero` is the weight-update-sharding A/B (ISSUE 6 / arxiv 2004.13336):
the SAME dp-mode AlexNet step with the replicated update vs the
ZeRO-sharded one, reporting samples/s, per-device optimizer-state bytes
and the allocator peak — step-time and memory deltas land in a bench
record (VELES_ZERO_AB_PATH, default ZERO_AB_RECORD.json next to the
repo's other BENCH records) so the N× memory cut is a measured number.

The persistent compilation cache follows the one rule in
veles_tpu/caches.py (enabled in main())."""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH = 512
K = 8


def measure(layers, name: str) -> float:
    import jax

    from veles_tpu import prng
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    prng.seed_all(1)
    loader = SyntheticClassifierLoader(
        n_classes=64, sample_shape=(227, 227, 3), n_validation=64,
        n_train=128, minibatch_size=BATCH, noise=0.5)
    wf = StandardWorkflow(
        layers=layers, loader=loader, loss="softmax", n_classes=64,
        decision_config={"max_epochs": 1, "fail_iterations": 9},
        gd_config={"learning_rate": 0.01, "gradient_moment": 0.9},
        name=name)
    wf.initialize(device=None)
    step = wf.build_fused_step(compute_dtype="bfloat16")
    state = step.init_state()
    rng = np.random.RandomState(0)
    x = jax.device_put(rng.randn(BATCH, 227, 227, 3).astype(np.float32))
    y = jax.device_put(rng.randint(0, 64, BATCH))
    state, _ = step.train_repeat(state, x, y, K)       # compile + warm
    np.asarray(state["params"][-1]["bias"][:1])
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        state, _ = step.train_repeat(state, x, y, K)
        # measurement barrier BY DESIGN: the timed window must end at a
        # proven device sync (scalar fetch), not at dispatch
        # velint: disable=sync-feed
        np.asarray(state["params"][-1]["bias"][:1])
        best = min(best, time.perf_counter() - t0)
    rate = BATCH * K / best
    print(f"ABLATE {name}: {rate:.0f} samples/s", flush=True)
    return rate


def variant(name: str):
    """Layer list + registry selections for one ablation variant. EVERY
    variant derives from `full`, which pins the registry to the r3
    lowering table (direct stem, reduce_window pooling), so the
    layer-family deltas stay internally consistent against the
    documented r3 baseline (ROOFLINE.md, "full_r3_lowering") and a
    removal delta never conflates with a lowering rewrite; "s2d-stem"
    and "slicepool" are the variants that flip ONE registry entry."""
    from veles_tpu.ops import variants
    from veles_tpu.samples.alexnet import alexnet_layers
    variants.select("conv_stem", "direct")
    variants.select("maxpool", "reduce_window")
    full = list(alexnet_layers(64, 1.0, 4096))
    if name == "full":
        return full
    if name == "no-LRN":
        return [l for l in full if l["type"] not in ("lrn", "norm")]
    if name == "no-dropout":
        return [l for l in full if l["type"] != "dropout"]
    if name == "s2d-stem":
        # the space-to-depth entry-conv rewrite (exact numerics; WON its
        # on-chip A/B 8,656 -> 9,377 in r4 -> now the registry default)
        variants.select("conv_stem", "s2d")
        return full
    if name == "avgpool":
        # same geometry, max→avg: bounds the cost of maxpool's backward
        # (XLA lowers it to select-and-scatter; avg is reduce+broadcast).
        # The delta is an upper bound on what a Pallas argmax-offset
        # pooling pair could recover.
        out = [dict(l, type="avg_pooling")
               if l["type"] == "max_pooling" else l for l in full]
        assert any(l["type"] == "avg_pooling" for l in out), \
            "no max_pooling layers found to substitute"
        return out
    if name == "slicepool":
        # maxpool lowered as a max-fold over shifted strided slices:
        # backward = selects + pads instead of select_and_scatter
        variants.select("maxpool", "slices")
        return full
    if name == "no-bigFC":
        return [l for l in full
                if not l["type"].startswith("all2all")
                and l["type"] != "softmax"] + [
            {"type": "softmax", "output_sample_shape": 64,
             "weights_stddev": 0.01}]
    raise SystemExit(f"unknown variant {name}")


def measure_zero_ab() -> dict:
    """A/B the ZeRO-sharded vs replicated weight update on a dp mesh
    over every local device: step time (train_repeat protocol, same as
    the layer ablations), per-device optimizer-state bytes (measured
    from the state pytree's shards), and the per-device memory snapshot
    (parallel/memstats.py). Writes the record and prints one compact
    ABLATE line per arm plus the deltas."""
    import json

    import jax

    from veles_tpu import prng
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.parallel import make_mesh
    from veles_tpu.parallel.memstats import device_memory_stats
    from veles_tpu.samples.alexnet import alexnet_layers
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit("--zero needs a >=2-device mesh (the A/B is "
                         "data-parallel); this host exposes "
                         f"{len(devs)} device(s)")
    mesh = make_mesh(devs)
    n_data = len(devs)
    # CPU smoke knobs (the BENCH_E2E_WIDTH precedent): full-size AlexNet
    # at batch 512 is the on-chip protocol; a virtual-device CPU mesh
    # shrinks both to stay testable
    batch = int(os.environ.get("ZERO_AB_BATCH", str(BATCH)))
    width = float(os.environ.get("ZERO_AB_WIDTH", "1.0"))
    if batch % n_data:
        raise SystemExit(f"--zero: batch {batch} not divisible by the "
                         f"{n_data}-device data axis")
    record = {"metric": "zero_sharding_ab", "n_devices": n_data,
              "device_kind": devs[0].device_kind, "batch": batch,
              "width": width, "steps_per_window": K, "arms": {}}
    for name, zs in (("replicated", "off"), ("zero", "on")):
        prng.seed_all(1)
        loader = SyntheticClassifierLoader(
            n_classes=64, sample_shape=(227, 227, 3), n_validation=64,
            n_train=128, minibatch_size=batch, noise=0.5)
        wf = StandardWorkflow(
            layers=list(alexnet_layers(64, width,
                                       int(4096 * width) or 64)),
            loader=loader,
            loss="softmax", n_classes=64,
            decision_config={"max_epochs": 1, "fail_iterations": 9},
            gd_config={"learning_rate": 0.01, "gradient_moment": 0.9},
            name=f"ZeroAB-{name}")
        wf.initialize(device=None)
        step = wf.build_fused_step(mesh=mesh, mode="dp",
                                   compute_dtype="bfloat16",
                                   zero_sharding=zs)
        state = step.init_state()
        rng = np.random.RandomState(0)
        # pre-stage the batch sharded over the data axis (the feed's
        # layout): the timed windows below must measure the UPDATE
        # decomposition, not a synchronous full-batch H2D each window
        # (measure() stages the same way for the layer ablations)
        xs, ys_, _ = step.input_put_specs()
        x = jax.device_put(
            rng.randn(batch, 227, 227, 3).astype(np.float32),
            jax.sharding.NamedSharding(mesh, xs))
        y = jax.device_put(rng.randint(0, 64, batch),
                           jax.sharding.NamedSharding(mesh, ys_))
        state, _ = step.train_repeat(state, x, y, K)   # compile + warm
        # post-warm sync barrier BY DESIGN: the timed windows below must
        # start from a drained device (cf. measure())
        # velint: disable=sync-feed
        np.asarray(state["params"][-1]["bias"][:1])
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            state, _ = step.train_repeat(state, x, y, K)
            # measurement barrier BY DESIGN (cf. measure())
            # velint: disable=sync-feed
            np.asarray(state["params"][-1]["bias"][:1])
            best = min(best, time.perf_counter() - t0)
        opt_bytes = step.optimizer_state_bytes(state)
        arm = {
            "samples_per_sec": round(batch * K / best, 1),
            "zero_active": step.zero_active,
            "zero_reason": step.zero_reason,
            "opt_state_bytes_per_device": {
                str(d): b for d, b in sorted(opt_bytes.items())},
            "opt_state_bytes_max": max(opt_bytes.values(), default=0),
            "variants": step.variant_table(),
            "device_memory": device_memory_stats(),
        }
        record["arms"][name] = arm
        print(f"ABLATE zero[{name}]: {arm['samples_per_sec']:.0f} "
              f"samples/s, opt-state {arm['opt_state_bytes_max']} "
              f"B/device", flush=True)
        del state
    rep = record["arms"]["replicated"]
    zro = record["arms"]["zero"]
    record["deltas"] = {
        "step_time_ratio": round(
            rep["samples_per_sec"] / max(zro["samples_per_sec"], 1e-9),
            4),
        "opt_state_bytes_drop": round(
            1.0 - zro["opt_state_bytes_max"]
            / max(rep["opt_state_bytes_max"], 1), 4),
        "expected_drop_floor": round((n_data - 1) / n_data, 4),
    }
    path = os.environ.get("VELES_ZERO_AB_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "ZERO_AB_RECORD.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"ABLATE zero: opt-state drop "
          f"{record['deltas']['opt_state_bytes_drop']:.4f} "
          f"(floor {(n_data - 1) / n_data:.4f}), speed ratio "
          f"repl/zero {record['deltas']['step_time_ratio']:.3f} "
          f"-> {path}", flush=True)
    return record


def measure_collectives_ab() -> dict:
    """A/B the grad_reduce variant family on a dp ZeRO mesh over every
    local device (ISSUE 12): per variant — step time (train_repeat
    windows, the layer-ablation protocol), bytes/step REPORTED FROM the
    veles_collective_bytes_total counter family (the driver's model,
    incremented per timed step and read back from the one registry),
    an ISOLATED collective timing (a shard_map jit of just the
    grad_reduce over the plan's total flat size — fed into
    veles_collective_seconds_total and bracketed by a real `grad_reduce`
    tracer span), and the trained-loss delta vs the f32 arm after a
    short fixed-batch trajectory. Record lands in
    COLLECTIVE_AB_RECORD.json (env VELES_COLLECTIVE_AB_PATH); CPU smoke
    knobs COLLECTIVE_AB_BATCH/WIDTH/STEPS (the ZERO_AB precedent). On a
    single-host mesh the DCN split needs an explicit (hosts x local)
    geometry: VELES_GRAD_REDUCE_LOCAL defaults to n_devices/2 here so
    the CPU 8-device mesh runs as (2 x 4)."""
    import json

    import jax

    from veles_tpu import prng
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.ops import variants
    from veles_tpu.parallel import make_mesh
    from veles_tpu.samples.alexnet import alexnet_layers
    from veles_tpu.telemetry import metrics as tmetrics
    from veles_tpu.telemetry import tracer as ttracer
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit("--collectives needs a >=2-device mesh; this "
                         f"host exposes {len(devs)} device(s)")
    n_data = len(devs)
    prev_local = os.environ.get(variants.GRAD_REDUCE_LOCAL_ENV)
    if prev_local is None and n_data >= 4:
        os.environ[variants.GRAD_REDUCE_LOCAL_ENV] = str(n_data // 2)
    mesh = make_mesh(devs)
    batch = int(os.environ.get("COLLECTIVE_AB_BATCH", str(BATCH)))
    width = float(os.environ.get("COLLECTIVE_AB_WIDTH", "1.0"))
    loss_steps = int(os.environ.get("COLLECTIVE_AB_STEPS", "8"))
    if batch % n_data:
        raise SystemExit(f"--collectives: batch {batch} not divisible "
                         f"by the {n_data}-device data axis")
    reg = tmetrics.default_registry()
    bytes_fam = reg.counter("veles_collective_bytes_total",
                            labelnames=("op", "leg"))
    secs_fam = reg.counter("veles_collective_seconds_total",
                           labelnames=("op",))
    secs_h = secs_fam.labels(op="grad_reduce")
    tr = ttracer.active()
    record = {"metric": "grad_reduce_collectives_ab",
              "n_devices": n_data,
              "device_kind": devs[0].device_kind, "batch": batch,
              "width": width, "steps_per_window": K,
              "loss_steps": loss_steps,
              "geometry": dict(zip(("hosts", "local"),
                                   variants.grad_reduce_geometry(
                                       n_data))),
              "arms": {}}
    arms = ("f32", "bf16", "int8_block", "int8_ef", "hier2")
    prev = variants.selected("grad_reduce")
    try:
        for name in arms:
            variants.select("grad_reduce", name)
            prng.seed_all(1)
            loader = SyntheticClassifierLoader(
                n_classes=64, sample_shape=(227, 227, 3),
                n_validation=64, n_train=128, minibatch_size=batch,
                noise=0.5)
            wf = StandardWorkflow(
                layers=list(alexnet_layers(64, width,
                                           int(4096 * width) or 64)),
                loader=loader, loss="softmax", n_classes=64,
                decision_config={"max_epochs": 1, "fail_iterations": 9},
                gd_config={"learning_rate": 0.01,
                           "gradient_moment": 0.9},
                name=f"CollAB-{name}")
            wf.initialize(device=None)
            step = wf.build_fused_step(mesh=mesh, mode="dp",
                                       compute_dtype="bfloat16",
                                       zero_sharding="on")
            if not step.zero_active:
                raise SystemExit(f"--collectives: zero inactive "
                                 f"({step.zero_reason})")
            acct = step.collective_accounting()
            ch = tmetrics.collective_handles(acct, reg)
            state = step.init_state()
            rng = np.random.RandomState(0)
            xs, ys_, _ = step.input_put_specs()
            x = jax.device_put(
                rng.randn(batch, 227, 227, 3).astype(np.float32),
                jax.sharding.NamedSharding(mesh, xs))
            y = jax.device_put(rng.randint(0, 64, batch),
                               jax.sharding.NamedSharding(mesh, ys_))
            state, _ = step.train_repeat(state, x, y, K)  # compile+warm
            # post-warm sync barrier BY DESIGN (cf. measure())
            # velint: disable=sync-feed
            np.asarray(state["params"][-1]["bias"][:1])
            before = {leg: bytes_fam.labels(op="grad_reduce",
                                            leg=leg).value
                      for leg in ("dcn", "ici")}
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                state, _ = step.train_repeat(state, x, y, K)
                # measurement barrier BY DESIGN (cf. measure())
                # velint: disable=sync-feed
                np.asarray(state["params"][-1]["bias"][:1])
                best = min(best, time.perf_counter() - t0)
                # drive the counters the way the driver does: the
                # modeled egress per dispatched train step
                for _k in range(K):
                    ch.dcn.inc(ch.dcn_bytes)
                    ch.ici.inc(ch.ici_bytes)
            # bytes/step READ BACK from the counters (the acceptance
            # criterion's reporting path), over the 3x K timed steps
            after = {leg: bytes_fam.labels(op="grad_reduce",
                                           leg=leg).value
                     for leg in ("dcn", "ici")}
            counted = {leg: (after[leg] - before[leg]) / (3 * K)
                       for leg in ("dcn", "ici")}
            # isolated collective: time JUST the exchange over the
            # plan's total flat size — the seconds counter's producer
            coll_s = _time_isolated_reduce(step, mesh, repeats=3)
            secs_h.inc(coll_s)
            if tr is not None:
                tr.instant(f"grad_reduce:{name}", "collective")
            # trained-loss delta: a short fixed-batch trajectory (same
            # seed per arm; rates are for the window above)
            lstate = step.init_state()
            loss = None
            for _ in range(loss_steps):
                lstate, (loss, _) = step.train(lstate, x, y)
            arm = {
                "samples_per_sec": round(batch * K / best, 1),
                "bytes_per_step": {k: int(v)
                                   for k, v in counted.items()},
                "modeled": {k: acct[k] for k in
                            ("dcn_bytes", "ici_bytes",
                             "allgather_dcn_bytes",
                             "allgather_ici_bytes")},
                "collective_seconds": round(coll_s, 6),
                "trained_loss": float(loss),
                "variants": step.variant_table(),
            }
            record["arms"][name] = arm
            print(f"ABLATE collectives[{name}]: "
                  f"{arm['samples_per_sec']:.0f} samples/s, dcn "
                  f"{arm['bytes_per_step']['dcn']} B/step, loss "
                  f"{arm['trained_loss']:.4f}", flush=True)
            del state, lstate
    finally:
        if prev is None:
            variants.clear_selection("grad_reduce")
        else:
            variants.select("grad_reduce", prev)
        # the geometry default above is scoped to THIS A/B: a later
        # ablation in the same process must not inherit it
        if prev_local is None:
            os.environ.pop(variants.GRAD_REDUCE_LOCAL_ENV, None)
    f32 = record["arms"]["f32"]
    deltas = {}
    for name in arms[1:]:
        a = record["arms"][name]
        deltas[name] = {
            "dcn_ratio": round(
                a["bytes_per_step"]["dcn"]
                / max(f32["bytes_per_step"]["dcn"], 1), 4),
            "step_time_ratio": round(
                f32["samples_per_sec"]
                / max(a["samples_per_sec"], 1e-9), 4),
            "trained_loss_delta": round(
                a["trained_loss"] - f32["trained_loss"], 6),
        }
    record["deltas"] = deltas
    path = os.environ.get("VELES_COLLECTIVE_AB_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "COLLECTIVE_AB_RECORD.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print("ABLATE collectives: dcn ratios "
          + ", ".join(f"{n2}={d['dcn_ratio']:.3f}"
                      for n2, d in deltas.items())
          + f" -> {path}", flush=True)
    return record


def measure_fusion_ab() -> dict:
    """A/B the searched cross-op fusion (ISSUE 13): the SAME dp-mode
    AlexNet step with the composed (lrn, maxpool) pair vs the fused
    `lrn_maxpool` Pallas point claiming it, on a mesh over every local
    device (the 8-device CPU mesh runs the kernel in interpret mode —
    wall-clock there is a functional proxy; the real number needs the
    same command on a chip). Reports per arm:
    samples/s (train_repeat windows, the layer-ablation protocol) and
    the step's variant_table (the fused arm must NAME the fused winner
    for both member ops — reported == traced); plus the PRE-FUSION
    per-op shares from a short granular profile (tools/layer_profile.py
    — the ratio the search splits a fused kernel's time back by).
    Record lands in FUSION_AB_RECORD.json (env VELES_FUSION_AB_PATH);
    CPU smoke knobs FUSION_AB_BATCH/WIDTH/POINT (the ZERO_AB
    precedent)."""
    import importlib.util
    import json

    import jax

    from veles_tpu import prng
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.ops import variants
    from veles_tpu.parallel import make_mesh
    from veles_tpu.samples.alexnet import alexnet_layers
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    devs = jax.devices()
    mesh = make_mesh(devs) if len(devs) > 1 else None
    n_data = len(devs) if mesh is not None else 1
    batch = int(os.environ.get("FUSION_AB_BATCH", str(BATCH)))
    width = float(os.environ.get("FUSION_AB_WIDTH", "1.0"))
    point = os.environ.get("FUSION_AB_POINT",
                           "fused[rt=2,io=native,fuse=1]")
    steps = int(os.environ.get("FUSION_AB_STEPS", str(K)))
    if batch % max(n_data, 1):
        raise SystemExit(f"--fusion: batch {batch} not divisible by "
                         f"the {n_data}-device data axis")
    on_cpu = jax.default_backend() == "cpu"
    record = {"metric": "cross_op_fusion_ab", "n_devices": n_data,
              "device_kind": devs[0].device_kind, "batch": batch,
              "width": width, "steps_per_window": steps,
              "fused_point": point,
              "pallas": "interpret" if on_cpu else "compiled",
              "arms": {}}

    def build(name):
        prng.seed_all(1)
        loader = SyntheticClassifierLoader(
            n_classes=64, sample_shape=(227, 227, 3), n_validation=64,
            n_train=128, minibatch_size=batch, noise=0.5)
        return StandardWorkflow(
            layers=list(alexnet_layers(64, width,
                                       int(4096 * width) or 64)),
            loader=loader, loss="softmax", n_classes=64,
            decision_config={"max_epochs": 1, "fail_iterations": 9},
            gd_config={"learning_rate": 0.01, "gradient_moment": 0.9},
            name=name)

    # pre-fusion per-op shares: the granular graph (which never fuses)
    # attributes time per MEMBER op — the ratio layer_profile's
    # split_fused_shares uses and the search's combined-share input
    spec = importlib.util.spec_from_file_location(
        "layer_profile", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "layer_profile.py"))
    lp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lp)
    wf_prof = build("FusionAB-profile")
    wf_prof.initialize(device=None)
    record["pre_fusion_shares"] = lp.op_shares(
        lp.profile_workflow(wf_prof, steps=2))

    prev = variants.selected("lrn_maxpool")
    import contextlib
    ctx = variants.pallas_interpret() if on_cpu \
        else contextlib.nullcontext()
    try:
        with ctx:
            for name, sel in (("composed", "composed"),
                              ("fused", point)):
                variants.select("lrn_maxpool", sel)
                wf = build(f"FusionAB-{name}")
                wf.initialize(device=None)
                step = wf.build_fused_step(
                    mesh=mesh, mode="dp" if mesh is not None else "auto",
                    compute_dtype="bfloat16")
                state = step.init_state()
                rng = np.random.RandomState(0)
                x = rng.randn(batch, 227, 227, 3).astype(np.float32)
                y = rng.randint(0, 64, batch)
                if mesh is not None:
                    xs, ys_, _ = step.input_put_specs()
                    import jax.sharding as jsh
                    x = jax.device_put(x, jsh.NamedSharding(mesh, xs))
                    y = jax.device_put(y, jsh.NamedSharding(mesh, ys_))
                else:
                    # one-time pre-stage per arm BY DESIGN (cf.
                    # measure()): the timed windows must not pay H2D
                    # velint: disable=sync-feed
                    x, y = jax.device_put(x), jax.device_put(y)
                state, _ = step.train_repeat(state, x, y, steps)
                # post-warm sync barrier BY DESIGN (cf. measure())
                # velint: disable=sync-feed
                np.asarray(state["params"][-1]["bias"][:1])
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    state, _ = step.train_repeat(state, x, y, steps)
                    # measurement barrier BY DESIGN (cf. measure())
                    # velint: disable=sync-feed
                    np.asarray(state["params"][-1]["bias"][:1])
                    best = min(best, time.perf_counter() - t0)
                arm = {
                    "samples_per_sec": round(batch * steps / best, 1),
                    "fusion_pairs": len(step.fusion_pairs()),
                    "variants": step.variant_table(),
                }
                record["arms"][name] = arm
                print(f"ABLATE fusion[{name}]: "
                      f"{arm['samples_per_sec']:.0f} samples/s, "
                      f"{arm['fusion_pairs']} fused pair(s)",
                      flush=True)
                del state
    finally:
        if prev is None:
            variants.clear_selection("lrn_maxpool")
        else:
            variants.select("lrn_maxpool", prev)
    comp = record["arms"]["composed"]
    fus = record["arms"]["fused"]
    record["deltas"] = {
        "step_time_ratio": round(
            comp["samples_per_sec"]
            / max(fus["samples_per_sec"], 1e-9), 4),
        "speedup": round(
            fus["samples_per_sec"]
            / max(comp["samples_per_sec"], 1e-9), 4),
    }
    path = os.environ.get("VELES_FUSION_AB_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "FUSION_AB_RECORD.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"ABLATE fusion: fused/composed speedup "
          f"{record['deltas']['speedup']:.3f} "
          f"({record['pallas']} pallas) -> {path}", flush=True)
    return record


def measure_plan_ab() -> dict:
    """Measured A/B of the whole-system planner (ISSUE 17): let
    `analysis/planner.plan_search` price + gate the config space with
    the hand-set defaults as the incumbent, then TIME the model's
    top-k through the same train_repeat protocol as every other A/B
    here — the incumbent is always in the timed set, so the measured
    winner can never silently lose to the defaults. The measured
    protocol fixes batch and mesh (they are the A/B's controlled
    variables) and searches the system knobs the planner exists for:
    grad_reduce wire, ZeRO on/off, the fusion claim. On the CPU mesh
    the model's absolute seconds are uncalibrated (the MFU curve is
    fit to the v5e sweep) — the record carries predicted numbers for
    rank comparison only; the real number needs the same command on a
    chip.
    Record lands in PLAN_AB_RECORD.json (env VELES_PLAN_AB_PATH);
    CPU smoke knobs PLAN_AB_BATCH/WIDTH/STEPS/BUDGET."""
    import contextlib
    import json

    import jax

    from veles_tpu import prng
    from veles_tpu.analysis import planner
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.ops import variants
    from veles_tpu.parallel import make_mesh
    from veles_tpu.samples.alexnet import alexnet_layers
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit("--plan needs a >=2-device mesh (the planner "
                         "ranks data-parallel configs); this host "
                         f"exposes {len(devs)} device(s)")
    mesh = make_mesh(devs)
    n_data = len(devs)
    batch = int(os.environ.get("PLAN_AB_BATCH", str(BATCH)))
    width = float(os.environ.get("PLAN_AB_WIDTH", "1.0"))
    steps = int(os.environ.get("PLAN_AB_STEPS", str(K)))
    budget = int(os.environ.get("PLAN_AB_BUDGET", "16"))
    if batch % n_data:
        raise SystemExit(f"--plan: batch {batch} not divisible by the "
                         f"{n_data}-device data axis")
    on_cpu = jax.default_backend() == "cpu"
    kind = devs[0].device_kind
    layers = list(alexnet_layers(64, width, int(4096 * width) or 64))
    geom = planner.model_geometry(layers, name="alexnet-ab")

    # the hand-set defaults every earlier A/B ran at: full-mesh dp,
    # ZeRO on, registry-default f32 wire, composed kernels
    incumbent = planner.PlanConfig(
        mesh_shape=(n_data,), batch_per_chip=batch // n_data,
        zero="on", wire=variants.selected("grad_reduce") or "f32",
        fusion="composed")
    space = {
        "batch_per_chip": [batch // n_data],
        "mesh_shape": [(n_data,)],
        "wire": ["f32", "bf16", "int8_block", "int8_ef"],
        "zero": ["on", "off"],
        "fusion": ["composed", "fused"],
    }

    prev_wire = variants.selected("grad_reduce")
    prev_fuse = variants.selected("lrn_maxpool")
    fused_point = os.environ.get("FUSION_AB_POINT",
                                 "fused[rt=2,io=native,fuse=1]")
    timed_log = []

    def timer(cfg) -> float:
        """Seconds per step of `cfg` under the train_repeat 3-window
        protocol (the measure() discipline)."""
        prng.seed_all(1)
        variants.select("grad_reduce", cfg.wire)
        if cfg.fusion == "composed":
            variants.select("lrn_maxpool", "composed")
        else:
            variants.select("lrn_maxpool", fused_point)
        loader = SyntheticClassifierLoader(
            n_classes=64, sample_shape=(227, 227, 3), n_validation=64,
            n_train=128, minibatch_size=batch, noise=0.5)
        wf = StandardWorkflow(
            layers=[dict(l) for l in layers], loader=loader,
            loss="softmax", n_classes=64,
            decision_config={"max_epochs": 1, "fail_iterations": 9},
            gd_config={"learning_rate": 0.01, "gradient_moment": 0.9},
            name="PlanAB")
        wf.initialize(device=None)
        ctx = variants.pallas_interpret() if on_cpu \
            else contextlib.nullcontext()
        with ctx:
            step = wf.build_fused_step(
                mesh=mesh, mode="dp", compute_dtype="bfloat16",
                zero_sharding=cfg.zero)
            state = step.init_state()
            rng = np.random.RandomState(0)
            x = rng.randn(batch, 227, 227, 3).astype(np.float32)
            y = rng.randint(0, 64, batch)
            xs, ys_, _ = step.input_put_specs()
            import jax.sharding as jsh
            x = jax.device_put(x, jsh.NamedSharding(mesh, xs))
            y = jax.device_put(y, jsh.NamedSharding(mesh, ys_))
            state, _ = step.train_repeat(state, x, y, steps)
            # post-warm sync barrier BY DESIGN (cf. measure())
            # velint: disable=sync-feed
            np.asarray(state["params"][-1]["bias"][:1])
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                state, _ = step.train_repeat(state, x, y, steps)
                # measurement barrier BY DESIGN (cf. measure())
                # velint: disable=sync-feed
                np.asarray(state["params"][-1]["bias"][:1])
                best = min(best, time.perf_counter() - t0)
        per_step = best / steps
        timed_log.append((cfg, per_step))
        print(f"ABLATE plan[timed]: wire={cfg.wire} zero={cfg.zero} "
              f"fusion={cfg.fusion} -> "
              f"{batch / per_step:.0f} samples/s", flush=True)
        del state
        return per_step

    try:
        plan = planner.plan_search(
            geom, device_kind=kind, n_chips=n_data, budget=budget,
            incumbent=incumbent, space=space, timer=timer, top_k=2)
    finally:
        for op, prev in (("grad_reduce", prev_wire),
                         ("lrn_maxpool", prev_fuse)):
            if prev is None:
                variants.clear_selection(op)
            else:
                variants.select(op, prev)

    def arm(entry):
        return {"config": entry["config"],
                "measured_step_s": entry.get("measured_step_s"),
                "samples_per_sec": (
                    round(batch / entry["measured_step_s"], 1)
                    if entry.get("measured_step_s") else None),
                "predicted_samples_per_sec": round(
                    entry["predicted"]["samples_per_sec"], 1),
                "memory_verdict": entry["memory"]["verdict"]}

    inc_entry = plan["incumbent"]
    top = plan["measured_top1"]
    top_entry = next(e for e in plan["ranked"]
                     if e["config"] == top["config"])
    record = {
        "metric": "plan_ab", "n_devices": n_data, "device_kind": kind,
        "batch": batch, "width": width, "steps_per_window": steps,
        "budget": budget, "evaluated": plan["budget"]["evaluated"],
        "pallas": "interpret" if on_cpu else "compiled",
        "calibrated": plan["calibrated"],
        "arms": {"defaults": arm(inc_entry),
                 "planner_top1": arm(top_entry)},
    }
    inc_s = inc_entry["measured_step_s"]
    top_s = top["measured_step_s"]
    record["deltas"] = {
        "speedup": round(inc_s / max(top_s, 1e-12), 4),
        "meets_or_beats": top_s <= inc_s,
    }
    path = os.environ.get("VELES_PLAN_AB_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "PLAN_AB_RECORD.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=str)
    print(f"ABLATE plan: top-1/defaults measured speedup "
          f"{record['deltas']['speedup']:.3f} "
          f"(meets_or_beats={record['deltas']['meets_or_beats']}, "
          f"{record['evaluated']} configs priced, "
          f"{len(timed_log)} timed) -> {path}", flush=True)
    return record


def _time_isolated_reduce(step, mesh, repeats: int = 3) -> float:
    """Seconds per call of JUST the selected grad_reduce exchange over
    the step's total flat gradient size (one concatenated vector) —
    the veles_collective_seconds_total producer, bracketed by a real
    `grad_reduce` tracer span when tracing is live."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from veles_tpu.parallel.mesh import DATA_AXIS
    from veles_tpu.telemetry import tracer as ttracer
    v = step._grad_reduce_variant()
    n = mesh.shape[DATA_AXIS]
    elems = sum(lp.padded for plan in step.zero_plans()
                for lp in plan.values())
    flat = jax.random.normal(jax.random.PRNGKey(7), (n, elems),
                             jnp.float32)

    def body(g):
        r = v.apply(g.reshape(-1), DATA_AXIS)
        out = r[0] if isinstance(r, tuple) else r
        return out.reshape(1, -1)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(DATA_AXIS),
                          out_specs=P(DATA_AXIS)))
    jax.block_until_ready(f(flat))      # compile + warm
    tr = ttracer.active()
    best = float("inf")
    for _ in range(max(1, repeats)):
        tok = tr.begin("grad_reduce", "collective") if tr is not None \
            else None
        t0 = time.perf_counter()
        jax.block_until_ready(f(flat))
        best = min(best, time.perf_counter() - t0)
        if tok is not None:
            tr.end(tok)
    return best


if __name__ == "__main__":
    from veles_tpu.caches import enable_compilation_cache
    enable_compilation_cache()
    args = sys.argv[1:]
    if "--plan" in args:
        measure_plan_ab()
        args = [a for a in args if a != "--plan"]
        if not args:
            raise SystemExit(0)
    if "--fusion" in args:
        measure_fusion_ab()
        args = [a for a in args if a != "--fusion"]
        if not args:
            raise SystemExit(0)
    if "--collectives" in args:
        measure_collectives_ab()
        args = [a for a in args if a != "--collectives"]
        if not args:
            raise SystemExit(0)
    if "--zero" in args:
        measure_zero_ab()
        args = [a for a in args if a != "--zero"]
        if not args:
            raise SystemExit(0)
    for v in (args or ["full"]):
        measure(variant(v), v)
