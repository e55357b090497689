#!/usr/bin/env python
"""Answer "best config for this model on N chips of kind K" — purely
statically.

The whole-system planner (veles_tpu/analysis/planner.py, analysis
pass 7) prices every candidate configuration with the analytical step
model and gates it through the PR-14 VMEM/HBM ledgers; nothing here
traces, compiles, or touches a device. The compact PLAN line carries
`jax_backends=<n>` as the per-run proof: it reads the jax backend
cache AFTER planning, and a static plan must report 0 (tier-1 pins
it).

    python tools/plan.py --chips 8 --kind "TPU v5 lite" --budget 32

Writes the ranked PLAN.json (env VELES_PLAN_PATH overrides the path):
every entry = config + predicted step time (with the compute/comms
split and byte counts) + the ledger's memory verdict — feasible, or
refused with the ledger's own reasons.

Env: VELES_PLAN_PATH (record path), VELES_PLAN_PEAK_FLOPS /
VELES_PLAN_DCN_BW / VELES_PLAN_FEED_BW / VELES_HBM_LIMIT (model
constants for uncatalogued hardware), VELES_LAYER_PROFILE_PATH
(measured cost shares, when present).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _arg(args, flag, default, cast):
    if flag in args:
        i = args.index(flag)
        return cast(args[i + 1])
    return default


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    n_chips = _arg(args, "--chips", 8, int)
    kind = _arg(args, "--kind", "TPU v5 lite", str)
    hosts = _arg(args, "--hosts", 1, int)
    budget = _arg(args, "--budget", 32, int)
    n_classes = _arg(args, "--classes", 1000, int)
    width = _arg(args, "--width", 1.0, float)

    from veles_tpu.analysis import planner
    from veles_tpu.telemetry import metrics as tm

    geom = planner.alexnet_geometry(n_classes=n_classes,
                                    width_mult=width)
    plan = planner.plan_search(geom, device_kind=kind, n_chips=n_chips,
                               hosts=hosts, budget=budget)

    # the staticness proof: planning must not have initialized any
    # jax backend (no devices, no compile) — read the cache, never
    # jax.devices(), which would CREATE one
    from jax._src import xla_bridge
    n_backends = len(xla_bridge._backends)
    plan["jax_backends_after_planning"] = n_backends

    path = os.environ.get("VELES_PLAN_PATH", "PLAN.json")
    with open(path, "w") as fh:
        json.dump(plan, fh, indent=1, default=str)
        fh.write("\n")

    tm.flush_installed()

    top = plan["ranked"][0] if plan["ranked"] else None
    compact = {
        "model": plan["model"]["name"],
        "device_kind": kind,
        "n_chips": n_chips,
        "evaluated": plan["budget"]["evaluated"],
        "feasible": plan["n_feasible"],
        "refused": plan["n_refused"],
        "calibrated": plan["calibrated"],
        "jax_backends": n_backends,
        "record": path,
    }
    if top is not None:
        compact["top1"] = {
            "batch_per_chip": top["config"]["batch_per_chip"],
            "mesh_shape": top["config"]["mesh_shape"],
            "zero": top["config"]["zero"],
            "wire": top["config"]["wire"],
            "fusion": top["config"]["fusion"],
            "predicted_samples_per_sec":
                round(top["predicted"]["samples_per_sec"], 1),
            "verdict": top["memory"]["verdict"],
        }
    print("PLAN " + json.dumps(compact, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
