#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that veles_tpu still starts on a TPU.

Drives the system's two user-facing paths once, on the chip, through the
entry points a user would call, at the full width of the flagship model
(Krizhevsky AlexNet: 227x227x3, FC 4096, 1000 classes, 62,378,344
params, bf16 compute) with random weights made from --seed:

  device    jax must report a TPU — checked before anything else
  train     `python -m veles_tpu samples/alexnet.py --fused` (the CLI's
            own main(), in this process): DeviceFeed, validation pass,
            Decision bookkeeping, one snapshot write; then a few more
            steps of the SAME step object for per-step losses
  snapshot  pickle + restore of the workflow after it ran on the chip;
            save_state/restore_state through parallel/checkpoint.py
  serve     the snapshot behind `--serve --serve-ring --serve-batch`,
            POST /predict + GET /healthz + /info over HTTP, argmax equal
            to an in-process forward of the same params; a second start
            on the same signature must load the AOT cache (0 compiles)
  kernels   every Pallas kernel compiled (never interpreted) at AlexNet
            widths against ops/reference.py; the fused step with
            lrn=pallas_one_pass lowers to a tpu_custom_call

`--four-chips` runs ONLY the data-parallel / ZeRO path on a 4-device
mesh and the one-chip step it is compared with (README: multi-chip).

ONE process: the chip belongs to one process at a time, so the server is
the CLI's own serve loop in the main thread and the HTTP client is a
jax-free thread that ends it with an interrupt. Any failed phase exits
non-zero; only a fully green run prints the last line
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import socket
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
#: scratch for snapshots/checkpoints: git-ignored, removed at the end
WORK = os.path.join(REPO, ".veles_cache", "chip_smoke")
ALEXNET = os.path.join(REPO, "veles_tpu", "samples", "alexnet.py")

#: sizes; a scratch rehearsal on the CPU may shrink them (never the repo)
CFG = {
    "batch": 1024,          # per chip — the `alexnet` cells' size
    "n_train": 2048, "n_validation": 1024, "epochs": 2,
    "extra_steps": 4,       # per-step losses after the CLI run
    "input_hw": 227, "n_classes": 1000,
    "n_params": 62378344,   # full Krizhevsky geometry
    "serve_ring": 8, "serve_batch": 4, "requests": 5, "rows": 2,
    "kernel_batch": 128,
    "lrn_sites": ((55, 55, 96), (27, 27, 256)),
    "flash": (1, 4096, 8, 64),
    "sgd_shape": (4096, 4096),
    "dp_steps": 3,
    "overrides": (),        # extra root.* overrides (rehearsal widths)
    "watchdog_s": 1150,
}


def say(*a) -> None:
    print(*a, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- steering: see what the CLI built, without changing the program ---------

SEEN = {"launchers": [], "servers": []}


def _tap_entry_points() -> None:
    """Record the Launcher and InferenceServer objects the CLI creates
    so the smoke can inspect what ran (state residency, variant table).
    The program's behaviour is untouched."""
    from veles_tpu import launcher as _l
    from veles_tpu import serving as _s
    if getattr(_l.Launcher, "_smoke_tapped", False):
        return
    run_module, start = _l.Launcher.run_module, _s.InferenceServer.start

    def tapped_run(self, module):
        SEEN["launchers"].append(self)
        return run_module(self, module)

    def tapped_start(self, *a, **k):
        SEEN["servers"].append(self)
        return start(self, *a, **k)

    _l.Launcher.run_module = tapped_run
    _s.InferenceServer.start = tapped_start
    _l.Launcher._smoke_tapped = True


def cli(argv) -> int:
    """`python -m veles_tpu <argv>` in this process."""
    from veles_tpu.__main__ import main
    say("  $ python -m veles_tpu", " ".join(
        os.path.relpath(a, REPO) if os.path.isabs(a) else a for a in argv))
    return main(list(argv))


def model_overrides():
    c = CFG
    return [
        "root.common.precision_type=bfloat16",
        f"root.alexnet.loader.minibatch_size={c['batch']}",
        f"root.alexnet.loader.n_train={c['n_train']}",
        f"root.alexnet.loader.n_validation={c['n_validation']}",
        f"root.alexnet.loader.input_hw={c['input_hw']}",
        f"root.alexnet.n_classes={c['n_classes']}",
        "root.alexnet.decision.fail_iterations=99",
    ] + list(c["overrides"])


def n_params_of(wf) -> int:
    import numpy as np
    return sum(int(np.prod(a.shape)) for u in wf.forwards
               for a in u.param_arrays().values() if a)


def release(wf) -> None:
    """Let go of a finished workflow's big host arrays — every unit's
    activation buffer at batch 1024 and the dataset, ~14 GB per restored
    workflow on a 40 GiB machine — whoever still holds the object."""
    import gc

    import numpy as np

    from veles_tpu.memory import Array
    for u in list(wf.units):
        for v in vars(u).values():
            if isinstance(v, Array) and v._host is not None \
                    and v._host.nbytes > (32 << 20):
                v.reset(np.zeros(0, np.float32))
    SEEN["launchers"].clear()
    SEEN["servers"].clear()
    gc.collect()


def on_device(tree, devices) -> bool:
    """Every leaf is a jax.Array living only on `devices`."""
    import jax
    want = set(devices)
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and all(
        isinstance(a, jax.Array) and set(a.devices()) <= want
        for a in leaves)


# -- phase: device -----------------------------------------------------------

def phase_device(want_count: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        say(f"chip_smoke: jax found no TPU (platform {d0.platform!r}, "
            f"{len(devs)} device(s)) — nothing was run")
        raise SystemExit(2)
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — version string only
        libtpu = "?"
    from veles_tpu.caches import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    say(f"device: platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"device: compile cache in force: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'fixed in-checkout default'}; "
        f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries at start)")
    say(f"device: native/build present at start: "
        f"{os.path.isdir(os.path.join(REPO, 'native', 'build'))}")
    check(len(devs) == want_count,
          f"this mode needs {want_count} chip(s), jax reports {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


class CompileCounters:
    """The program's own compile counters (`veles_compile_*`, written by
    `veles_tpu/telemetry/compile_stages.py` since `phase_device` turned
    the compile cache on), as what they grew by since the last `take()`."""

    def __init__(self) -> None:
        self.seen = (0.0, 0)

    def take(self):
        from veles_tpu.telemetry import metrics
        secs = metrics.family_values("veles_compile_seconds_total") or {}
        progs = metrics.family_values("veles_compile_programs_total") or {}
        now = (sum(v for (stage, _), v in secs.items() if stage == "backend"),
               int(sum(progs.values())))
        out = (round(now[0] - self.seen[0], 2), now[1] - self.seen[1])
        self.seen = now
        return out


# -- phase: train ------------------------------------------------------------

def phase_train(seed: int, clock: CompileCounters):
    import jax
    import numpy as np
    c = CFG
    snap_dir = os.path.join(WORK, "snapshots")
    os.makedirs(snap_dir, exist_ok=True)
    t0 = time.time()
    rc = cli([ALEXNET, "--fused", "--no-stats", "--random-seed", str(seed)]
             + model_overrides() + [
        f"root.alexnet.decision.max_epochs={c['epochs']}",
        f"root.alexnet.snapshotter.directory={snap_dir}",
        "root.alexnet.snapshotter.compression=",
        "root.alexnet.snapshotter.keep_last=1"])
    check(rc == 0, f"train: CLI exit code {rc}")
    wall = time.time() - t0
    compile_s, n_comp = clock.take()
    wf = SEEN["launchers"][-1].workflow
    step, state = wf.fused_step, wf.fused_state
    dec = wf.decision
    n_params = n_params_of(wf)
    say(f"train: {type(wf).__name__} params={n_params:,} "
        f"input={tuple(wf.loader.minibatch_data.shape)} "
        f"classes={wf.n_classes} compute_dtype={step.compute_dtype} "
        f"mode={step.mode}")
    check(n_params == c["n_params"],
          f"train: {n_params} params, expected {c['n_params']}")
    check(str(step.compute_dtype) == "bfloat16", "train: not bf16 compute")
    say(f"train: epochs={dec.epoch_number} epoch_n_err(test,valid,train)="
        f"{[None if m is None else int(m) for m in dec.epoch_metrics]} "
        f"best_validation_err={dec.best_validation_err} "
        f"wall={wall:.1f}s")
    check(dec.epoch_number == c["epochs"], "train: epochs not completed")
    say(f"train: compile seconds (XLA backend, {n_comp} programs): "
        f"{compile_s}")
    from veles_tpu.telemetry import metrics
    for family in ("veles_setup_seconds_total", "veles_compile_seconds_total",
                   "veles_compile_cache_total"):
        say(f"train: {family} " + json.dumps(
            {"/".join(k): round(v, 3) for k, v in sorted(
                (metrics.family_values(family) or {}).items())}))
    say(f"train: feed_stats={json.dumps(wf.feed_stats, default=str)}")
    check(wf.feed_stats and wf.feed_stats.get("batches", 0) > 0,
          "train: the DeviceFeed fed nothing")
    say(f"train: variant_table={json.dumps(step.variant_table())}")
    snaps = sorted(os.listdir(snap_dir))
    say(f"train: snapshots written: {snaps}")
    check(any(s.startswith("alexnet") for s in snaps),
          "train: no snapshot was written")

    # a few more steps of the SAME compiled step on one fixed batch, for
    # per-step losses (the CLI only surfaces per-epoch totals)
    check(step.input_normalize is None,
          "train: unexpected uint8 wire on the synthetic loader")
    n = c["batch"]
    base = sum(wf.loader.class_lengths[:2])
    x = np.asarray(wf.loader.data.mem[base:base + n])
    y = np.asarray(wf.loader.labels.mem[base:base + n])
    ones = np.ones(n, np.float32)
    losses = []
    for _ in range(c["extra_steps"]):
        state, (loss, _n_err) = step.train(state, x, y, ones)
        losses.append(float(loss))
    extra_compiles = clock.take()[1]
    say(f"train: per-step loss on one fixed batch: "
        f"{[round(v, 5) for v in losses]}")
    check(all(np.isfinite(losses)), "train: non-finite loss")
    check(losses[-1] < losses[0], "train: loss did not fall")
    check(extra_compiles == 0,
          f"train: {extra_compiles} recompiles on the steady-state step")
    step.write_back(state)
    wf.fused_state = state
    dev = jax.devices()[0]
    check(on_device({k: state[k] for k in ("params", "vel")}, [dev]),
          "train: trained state is not resident on the chip")
    stats = dev.memory_stats() or {}
    say(f"train: state resident on {dev}; peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")
    check(stats.get("peak_bytes_in_use", 0) > 0,
          "train: the device reports no memory in use")
    return wf, os.path.join(snap_dir, [s for s in snaps
                                       if s.startswith("alexnet")
                                       and not s.endswith(".sha256")][-1])


# -- phase: snapshot ---------------------------------------------------------

def phase_snapshot(wf) -> None:
    import gc
    import pickle

    import jax
    import numpy as np

    from veles_tpu.parallel.checkpoint import restore_state, save_state
    t0 = time.time()
    # a whole-workflow pickle carries the dataset AND every unit's host
    # activation buffer at batch 1024 (>10 GB): streamed through a file,
    # never held as bytes, and without the synthetic images
    path = os.path.join(WORK, "workflow.pickle")
    data, labels = wf.loader.data.mem, wf.loader.labels.mem
    wf.loader.data.reset(data[:8])
    wf.loader.labels.reset(labels[:8])
    try:
        with open(path, "wb") as f:
            pickle.dump(wf, f, protocol=pickle.HIGHEST_PROTOCOL)
    finally:
        wf.loader.data.reset(data)
        wf.loader.labels.reset(labels)
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        back = pickle.load(f)
    os.remove(path)
    for u, v in zip(wf.forwards, back.forwards):
        for k, a in u.param_arrays().items():
            if a:
                check(np.array_equal(np.asarray(a.mem),
                                     np.asarray(v.param_arrays()[k].mem)),
                      f"snapshot: {type(u).__name__}.{k} changed in the "
                      "pickle round trip")
    del back
    gc.collect()
    say(f"snapshot: workflow pickled after the chip run "
        f"({size / 1e9:.1f} GB) and restored, params equal "
        f"({time.time() - t0:.1f}s)")
    t0 = time.time()
    step, state = wf.fused_step, wf.fused_state
    ckpt = os.path.join(WORK, "ckpt")
    save_state(state, ckpt)
    got = restore_state(step, ckpt)
    def raw(a):     # typed PRNG keys compare by their key data
        if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
            a = jax.random.key_data(a)
        return np.asarray(a)

    same = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(np.array_equal(raw(a), raw(b))), state, got))
    check(same, "snapshot: save_state/restore_state changed the state")
    check(on_device({k: got[k] for k in ("params", "vel")},
                    [jax.devices()[0]]),
          "snapshot: restored state is not on the chip")
    say(f"snapshot: save_state/restore_state round trip exact, restored "
        f"onto the chip ({time.time() - t0:.1f}s)")


# -- phase: serve ------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body=None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    if data is not None:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _serve_once(snapshot: str, inputs, seed: int) -> dict:
    """One `--serve` run of the CLI in the main thread; a jax-free client
    thread talks HTTP to it and ends it with an interrupt."""
    import signal
    c = CFG
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    got = {"predict": [], "error": None}
    done = threading.Event()

    def client() -> None:
        try:
            deadline = time.time() + 900
            while True:
                try:
                    st, h = _http("GET", base + "/healthz", timeout=5)
                    if st == 200:
                        break
                except OSError:
                    pass
                if done.is_set() or time.time() > deadline:
                    raise RuntimeError("server never became healthy")
                time.sleep(0.5)
            got["healthz"] = (st, h)
            got["info"] = _http("GET", base + "/info")
            for rows in inputs:
                got["predict"].append(
                    _http("POST", base + "/predict", {"inputs": rows}))
        except Exception as e:  # noqa: BLE001 — reported by the main thread
            got["error"] = f"{type(e).__name__}: {e}"
        finally:
            if not done.is_set():
                # a REAL signal: the CLI's serve loop sleeps in C, which
                # only a delivered SIGINT wakes (its own Ctrl-C path)
                os.kill(os.getpid(), signal.SIGINT)

    t = threading.Thread(target=client, name="smoke-http-client",
                         daemon=True)
    t.start()
    try:
        rc = cli([ALEXNET, "--no-stats", "--random-seed", str(seed),
                  "-s", snapshot, "--serve", str(port),
                  "--serve-ring", str(c["serve_ring"]),
                  "--serve-batch", str(c["serve_batch"])]
                 + model_overrides())
    finally:
        done.set()
    t.join(30)
    check(got["error"] is None, f"serve: client failed: {got['error']}")
    check(rc == 0, f"serve: CLI exit code {rc}")
    return got


def phase_serve(snapshot: str, seed: int, clock: CompileCounters) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    c = CFG
    rs = np.random.RandomState(seed)
    hw = c["input_hw"]
    # multiples of 1/8: exact in float32 AND short in JSON (a 227x227x3
    # sample is ~1 MB of text; max_body is 32 MiB)
    batches = [np.round(rs.randn(c["rows"], hw, hw, 3) * 8) / 8
               for _ in range(c["requests"])]
    clock.take()
    first = _serve_once(snapshot, [b.tolist() for b in batches], seed)
    srv = SEEN["servers"][-1]
    wf = SEEN["launchers"][-1].workflow
    info = first["info"][1]
    say(f"serve: /healthz {first['healthz'][0]} dispatch="
        f"{first['healthz'][1].get('dispatch')} ring_slots="
        f"{first['healthz'][1].get('ring_slots')}")
    say(f"serve: /info dispatch={info.get('dispatch')} "
        f"ring_slots={info.get('ring_slots')} sharded="
        f"{info.get('sharded')} quantize={info.get('quantize')} "
        f"aot={info.get('aot')}  (cold start; XLA compiled "
        f"{clock.take()} s,programs)")
    check(info.get("dispatch") == "ring", "serve: not the ring dispatch")
    check(n_params_of(wf) == c["n_params"], "serve: wrong model restored")
    check(on_device(srv._params_dev, [jax.devices()[0]]),
          "serve: the server's params are not resident on the chip")

    # the in-process forward of the SAME params, at the ring's own batch
    # shape (same program shapes as the served executable)
    step = wf.build_fused_step()
    params = step.init_state()["params"]
    fwd = jax.jit(lambda p, x: jax.nn.softmax(
        step._forward(p, x, jax.random.PRNGKey(0), False), axis=-1))
    ring = int(info["ring_slots"])
    worst = 0.0
    for i, (b, (status, resp)) in enumerate(zip(batches,
                                                first["predict"])):
        check(status == 200, f"serve: request {i} answered {status}")
        out = np.asarray(resp["outputs"], np.float32)
        check(out.shape == (c["rows"], c["n_classes"]),
              f"serve: request {i} output shape {out.shape}")
        check(np.isfinite(out).all(), f"serve: request {i} not finite")
        x = np.zeros((ring, hw, hw, 3), np.float32)
        x[:c["rows"]] = b
        want = np.asarray(fwd(params, jnp.asarray(x)))[:c["rows"]]
        worst = max(worst, float(np.abs(out - want).max()))
        check((out.argmax(-1) == want.argmax(-1)).all(),
              f"serve: request {i} argmax {out.argmax(-1).tolist()} != "
              f"in-process forward {want.argmax(-1).tolist()}")
    say(f"serve: {len(batches)} POST /predict x {c['rows']} rows of "
        f"{hw}x{hw}x3 -> all 200, {c['n_classes']}-wide, argmax == "
        f"in-process forward (max |p - p_ref| = {worst:.2e}); server "
        f"params resident on {jax.devices()[0]}")

    # let go of the first restored workflow before the second start
    del srv, step, params, fwd
    release(wf)
    clock.take()
    second = _serve_once(snapshot, [batches[0].tolist()], seed)
    aot = second["info"][1].get("aot") or {}
    say(f"serve: second start on the same signature: aot={aot} "
        f"(XLA compiled {clock.take()} s,programs)")
    check(aot.get("source") == "cache" and aot.get("compiles") == 0,
          f"serve: second start did not load the AOT cache: {aot}")
    check(second["predict"][0][0] == 200, "serve: warm start refused")
    out2 = np.asarray(second["predict"][0][1]["outputs"], np.float32)
    out1 = np.asarray(first["predict"][0][1]["outputs"], np.float32)
    check(np.array_equal(out1, out2),
          "serve: the cached executable answers differently")


# -- phase: kernels ----------------------------------------------------------

def phase_kernels(seed: int, wf) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from veles_tpu.ops import pallas_kernels as pk
    from veles_tpu.ops import reference as ref
    from veles_tpu.ops import variants
    c = CFG
    check(pk.available() and not pk._interpret(),
          "kernels: pallas would run interpreted")
    rs = np.random.RandomState(seed)
    bf = jnp.bfloat16

    def compiled_has_kernel(fn, *args) -> None:
        txt = jax.jit(fn).lower(*args).as_text()
        check("tpu_custom_call" in txt,
              "kernels: no tpu_custom_call in the lowered program")

    def r32(a):     # what the kernel saw: the bf16-rounded values
        return np.asarray(jnp.asarray(a, bf).astype(jnp.float32))

    kb = c["kernel_batch"]
    for (h, w, ch) in c["lrn_sites"]:
        x = rs.randn(kb, h, w, ch).astype(np.float32)
        g = rs.randn(kb, h, w, ch).astype(np.float32)
        f = lambda a, b: jax.vjp(pk.lrn_pallas, a)[1](b)[0]  # noqa: E731
        compiled_has_kernel(f, jnp.asarray(x, bf), jnp.asarray(g, bf))
        y = np.asarray(jax.jit(pk.lrn_pallas)(jnp.asarray(x, bf))
                       .astype(jnp.float32))
        dx = np.asarray(jax.jit(f)(jnp.asarray(x, bf), jnp.asarray(g, bf))
                        .astype(jnp.float32))
        n = 2
        ey = np.abs(y[:n] - ref.lrn_forward(r32(x[:n]))).max()
        ed = np.abs(dx[:n] - ref.lrn_backward(r32(x[:n]),
                                              r32(g[:n]))).max()
        say(f"kernels: lrn_pallas fwd+bwd ({kb},{h},{w},{ch}) bf16 "
            f"compiled; vs ops.reference max err fwd {ey:.2e} "
            f"bwd {ed:.2e}")
        check(ey < 3e-2 and ed < 3e-2, "kernels: lrn_pallas != reference")

    h, w, ch = c["lrn_sites"][0]
    x = rs.randn(kb, h, w, ch).astype(np.float32)
    fp = lambda a: pk.lrn_maxpool_pallas(a)                  # noqa: E731
    compiled_has_kernel(fp, jnp.asarray(x, bf))
    y, vjp = jax.vjp(fp, jnp.asarray(x, bf))
    g = rs.randn(*y.shape).astype(np.float32)
    dx = np.asarray(vjp(jnp.asarray(g, bf))[0].astype(jnp.float32))
    y = np.asarray(y.astype(jnp.float32))
    n = 2
    ey = np.abs(y[:n] - ref.lrn_maxpool_forward(r32(x[:n]))).max()
    # routing ties differ after bf16 rounding of y: compare where the
    # reference's own argmax is unambiguous — i.e. almost everywhere
    want = ref.lrn_maxpool_backward(r32(x[:n]), r32(g[:n]))
    close = np.isclose(dx[:n], want, atol=3e-2).mean()
    say(f"kernels: lrn_maxpool_pallas fwd+bwd ({kb},{h},{w},{ch}) bf16 "
        f"compiled (rt={pk._LRN_POOL_ROW_TILE}); vs ops.reference fwd "
        f"max err {ey:.2e}, bwd {100 * close:.3f}% of elements equal")
    check(ey < 3e-2 and close > 0.999,
          "kernels: lrn_maxpool_pallas != reference")

    shp = c["sgd_shape"]
    p, gr, v = (rs.randn(*shp).astype(np.float32) for _ in range(3))
    for rt in (8, 1024):
        fs = lambda a, b, d: pk.sgd_update_pallas(         # noqa: E731
            a, b, d, 0.01, 0.9, 5e-4, row_tile=rt)
        compiled_has_kernel(fs, p, gr, v)
        pn, vn = jax.jit(fs)(p, gr, v)
        vw = 0.9 * v - 0.01 * (gr + 5e-4 * p)
        e = max(np.abs(np.asarray(vn) - vw).max(),
                np.abs(np.asarray(pn) - (p + vw)).max())
        say(f"kernels: sgd_update_pallas {shp} f32 row_tile={rt} "
            f"compiled; max err {e:.2e}")
        check(e < 1e-5, "kernels: sgd_update_pallas != reference")

    b, s, hh, d = c["flash"]
    q, k, vv = (rs.randn(b, s, hh, d).astype(np.float32) * 0.5
                for _ in range(3))
    ff = lambda a, bb, cc: pk.flash_attention_pallas(        # noqa: E731
        a, bb, cc, causal=True)
    compiled_has_kernel(ff, *(jnp.asarray(t, bf) for t in (q, k, vv)))
    out = np.asarray(jax.jit(ff)(*(jnp.asarray(t, bf)
                                   for t in (q, k, vv)))
                     .astype(jnp.float32))
    want = ref.mha_forward(r32(q), r32(k), r32(vv), causal=True)
    e = np.abs(out - want).max()
    gq = jax.jit(jax.grad(lambda a, bb, cc: ff(a, bb, cc)
                          .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(
        *(jnp.asarray(t, bf) for t in (q, k, vv)))
    fin = all(bool(jnp.isfinite(t.astype(jnp.float32)).all()) for t in gq)
    say(f"kernels: flash_attention_pallas B{b} S{s} H{hh} D{d} bf16 "
        f"causal fwd compiled, max err vs ops.reference {e:.2e}; "
        f"fwd+bwd compiled, grads finite={fin}")
    check(e < 3e-2 and fin, "kernels: flash_attention_pallas != reference")

    # the fused train step with the Pallas LRN selected: the kernel must
    # be IN the step's program (lowered text), not swapped for a fallback
    variants.select("lrn", "pallas_one_pass")
    try:
        step = wf.build_fused_step()
        check(step.variant_table().get("lrn") == "pallas_one_pass",
              f"kernels: selected pallas LRN resolved to "
              f"{step.variant_table().get('lrn')}")
        state = wf.fused_state
        n = c["batch"]
        xs = jax.ShapeDtypeStruct(
            (n,) + tuple(wf.loader.minibatch_data.shape[1:]), jnp.float32)
        ys = jax.ShapeDtypeStruct((n,), jnp.int32)
        ws = jax.ShapeDtypeStruct((n,), jnp.float32)
        txt = jax.jit(step.train_callable()).lower(
            state, xs, ys, ws).as_text()
        check("tpu_custom_call" in txt,
              "kernels: fused step with lrn=pallas_one_pass holds no "
              "tpu_custom_call")
        say("kernels: fused step with lrn=pallas_one_pass lowers with "
            f"{txt.count('tpu_custom_call')} tpu_custom_call site(s); "
            "interpret=False throughout")
    finally:
        variants.clear_selection("lrn")


# -- four chips: the data-parallel / ZeRO path -------------------------------

def phase_four_chips(seed: int, clock: CompileCounters) -> None:
    import jax
    import numpy as np

    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.loader.device_feed import make_batch_put
    from veles_tpu.parallel import make_mesh
    from veles_tpu.samples import alexnet
    from veles_tpu.znicz.dropout import DropoutForward
    c = CFG
    n = c["batch"]              # GLOBAL batch: 256 per chip on four
    root.common.precision_type = "bfloat16"
    root.alexnet.decision.max_epochs = 1
    root.alexnet.decision.fail_iterations = 99
    for ov in c["overrides"]:
        from veles_tpu.launcher import apply_overrides
        apply_overrides([ov])

    def fresh():
        prng.seed_all(seed)
        wf = alexnet.create_workflow(
            minibatch_size=n, n_train=2 * n, n_validation=n,
            input_hw=c["input_hw"], n_classes=c["n_classes"])
        wf.initialize(device=None)
        # dropout keys are folded with the shard index BY DESIGN, so a
        # dp step and a local step never draw the same masks: the two
        # arms are compared with the masks off (ratio 0), same params
        for u in wf.forwards:
            if isinstance(u, DropoutForward):
                u.dropout_ratio = 0.0
        return wf

    mesh = make_mesh()
    say(f"four: mesh {dict(mesh.shape)} over "
        f"{[str(d) for d in mesh.devices.flat]}")
    wf = fresh()
    check(n_params_of(wf) == c["n_params"], "four: not the full AlexNet")
    base = sum(wf.loader.class_lengths[:2])
    x = np.asarray(wf.loader.data.mem[base:base + n])
    y = np.asarray(wf.loader.labels.mem[base:base + n])

    def run(step):
        state = step.init_state()
        ev0 = float(step.evaluate(state, x, y)[0])
        losses = []
        for _ in range(c["dp_steps"]):
            state, (loss, _e) = step.train(state, x, y)
            losses.append(float(loss))
        return state, ev0, losses

    # sharded on request: AlexNet's state (0.75 GB) asks for no ZeRO by
    # itself, and no benchmark cell runs that path (the default step,
    # the replicated update, is run_fused's below and vgg16.dp4's)
    dp = wf.build_fused_step(mesh=mesh, zero_sharding="on")
    say(f"four: dp step mode={dp.mode} zero_active={dp.zero_active} "
        f"({dp.zero_reason}) variant_table={json.dumps(dp.variant_table())}")
    check(dp.mode == "dp" and dp.zero_active, "four: not the dp/ZeRO step")
    clock.take()
    state, ev_dp, loss_dp = run(dp)
    say(f"four: dp/ZeRO  eval loss {ev_dp:.7f}  train losses "
        f"{[round(v, 7) for v in loss_dp]}  (XLA compiled "
        f"{clock.take()} s,programs)")

    wf_loc = fresh()
    loc = wf_loc.build_fused_step()
    _s, ev_loc, loss_loc = run(loc)
    del _s
    say(f"four: one-chip eval loss {ev_loc:.7f}  train losses "
        f"{[round(v, 7) for v in loss_loc]}  (XLA compiled "
        f"{clock.take()} s,programs)")
    rel = [abs(a - b) / abs(b) for a, b in
           zip([ev_dp] + loss_dp, [ev_loc] + loss_loc)]
    say(f"four: relative |dp - local| per reading: "
        f"{[f'{r:.2e}' for r in rel]} (tolerance 2e-5)")
    check(all(np.isfinite(loss_dp)), "four: non-finite dp loss")
    check(max(rel) <= 2e-5, f"four: dp loss differs from the one-chip "
                            f"step by {max(rel):.2e} relative")

    # where everything lives: a batch, the params, the ZeRO slices
    put = make_batch_put(dp)
    xb, = put((x,))
    p0 = jax.tree_util.tree_leaves(state["params"])[0]
    v_big = max(jax.tree_util.tree_leaves(state["vel"]),
                key=lambda a: a.size)
    say(f"four: device_set sizes — batch {len(xb.sharding.device_set)} "
        f"(shard shape {xb.addressable_shards[0].data.shape}), params "
        f"{len(p0.sharding.device_set)} (replicated="
        f"{p0.sharding.is_fully_replicated}), largest ZeRO optimizer "
        f"slice {len(v_big.sharding.device_set)} (global {v_big.shape} "
        f"-> shard {v_big.addressable_shards[0].data.shape})")
    check(len(xb.sharding.device_set) == 4
          and len(v_big.sharding.device_set) == 4
          and v_big.addressable_shards[0].data.shape[0] * 4
          == v_big.shape[0], "four: the batch or the optimizer state is "
                             "not spread over the four chips")
    opt = dp.optimizer_state_bytes(state)
    say(f"four: optimizer-state bytes per device: {opt}")
    check(len(opt) == 4 and min(opt.values()) > 0,
          "four: a chip holds no optimizer slice")
    for d in jax.devices():
        st = d.memory_stats() or {}
        say(f"four: {d} memory_stats bytes_in_use="
            f"{st.get('bytes_in_use')} peak_bytes_in_use="
            f"{st.get('peak_bytes_in_use')} bytes_limit="
            f"{st.get('bytes_limit')}")
        check(st.get("peak_bytes_in_use", 0) > 0,
              f"four: {d} reports no memory in use")
    say(f"four: collective_accounting="
        f"{json.dumps(dp.collective_accounting(), default=str)}")
    del state, xb

    # the documented multi-chip path end to end (README): the DeviceFeed
    # puts sharded batches, Decision closes one epoch; zero_sharding is
    # the default here, so memory decides and the update is replicated
    clock.take()
    t0 = time.time()
    wf.run_fused(mesh=make_mesh())
    say(f"four: wf.run_fused(mesh=make_mesh()) 1 epoch in "
        f"{time.time() - t0:.1f}s; feed_stats="
        f"{json.dumps(wf.feed_stats, default=str)} (XLA compiled "
        f"{clock.take()} s,programs)")
    check(wf.decision.epoch_number == 1, "four: run_fused epoch missing")
    say("four: --serve-replicas N builds N InferenceServer rings in one "
        "process, each over the same serve mesh (mesh='auto': all local "
        "devices when the ring divides them) — rings are NOT pinned one "
        "per chip; not changed in this PR")


# -- main --------------------------------------------------------------------

def _watchdog(seconds: float) -> None:
    def bark() -> None:
        time.sleep(seconds)
        sys.stderr.write(f"chip_smoke: watchdog after {seconds:.0f}s\n")
        sys.stderr.flush()
        os._exit(3)
    threading.Thread(target=bark, name="smoke-watchdog",
                     daemon=True).start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the 4-chip dp/ZeRO path and the "
                         "one-chip step it is compared with")
    ap.add_argument("--seed", type=int, default=1234)
    args = ap.parse_args(argv)
    _watchdog(CFG["watchdog_s"])
    sys.path.insert(0, REPO)
    t0 = time.time()
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK, exist_ok=True)
    try:
        device = phase_device(4 if args.four_chips else 1)
        clock = CompileCounters()
        if args.four_chips:
            phase_four_chips(args.seed, clock)
        else:
            _tap_entry_points()
            wf, snapshot = phase_train(args.seed, clock)
            phase_kernels(args.seed, wf)
            phase_snapshot(wf)
            release(wf)     # before the server restores its own copy
            phase_serve(snapshot, args.seed, clock)
    except SmokeFailure as e:
        say(f"chip_smoke: FAILED — {e}")
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    say(f"chip_smoke: all phases passed in {time.time() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
