"""Run one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

One process, which holds the chip. Off a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result. The last line
of the standard output is the result: one JSON object. With `--trace 0`
its metrics are the cell's end-to-end metrics, with `--trace 1` (a run
under the profiler) its per-layer metrics, `device.busy_s`,
`device.window_s` and `breakdown`.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(text: str) -> None:
    print(text, flush=True)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, say=say, **driver_kw):
    """Everything of a run but the look for a chip. Returns the result
    line's object."""
    import jax

    from benchmark.manifest import Manifest
    man = Manifest(root)
    cell = man.cell(workload)
    driver = man.driver(cell["traffic_data"]["driver"])
    out = driver.run(cell, man, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start, say=say, **driver_kw)
    d0 = out["devices"][0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out["peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in man.metrics("end_to_end", workload):
            result["metrics"][m["name"]] = {
                "value": out["end_to_end"][m["name"]], "unit": m["unit"]}
        return result
    ctx = {"cell": cell, "counters": out["counters"], "trace": out["trace"],
           "peaks": man.peaks(), "device_kind": d0.device_kind}
    for m in man.metrics("per_layer", workload):
        value = man.layer_metric(m["name"]).read(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    device["busy_s"] = out["trace"]["busy_s"]
    device["window_s"] = out["trace"]["window_s"]
    result["breakdown"] = out["trace"]["breakdown"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmark.manifest import Manifest
    chips = Manifest(ROOT).cell(args.workload)["chips"]
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmark: cell {args.workload} needs {chips} TPU chip(s); "
              f"jax found {len(devs)} device(s) of platform "
              f"{devs[0].platform!r}. Nothing was run.", file=sys.stderr)
        return 2
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
