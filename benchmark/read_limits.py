"""Read what the limits of a cell are set from, on the chip.

    python3 benchmark/read_limits.py --workload <cell> --seeds 1,2,3,...
                                     [--control-seeds 1,2,3]

For every seed: build the cell's program, drive its first steps (training
needs no measured window for this), free it, run the plain reference, and
print the gaps between the two: what sound runs give. For every control
seed also run the reference in the precision below the configuration's
(`precision="float8"`) and print ITS gaps against the float32 reference:
what the control gives. One process, so set-up is paid once per seed and
the compiled programs are shared. A limit goes above the sound runs'
largest and below the control's smallest (PERF.md section 2).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    import jax

    from benchmark import reference
    from benchmark.manifest import Manifest
    from veles_tpu.caches import enable_compilation_cache
    man = Manifest(ROOT)
    cell = man.cell(args.workload)
    if jax.devices()[0].platform != "tpu" \
            or len(jax.devices()) < cell["chips"]:
        print("read_limits: needs the cell's TPU chips", file=sys.stderr)
        return 2
    enable_compilation_cache()
    driver = man.driver(cell["traffic_data"]["driver"])
    control = {int(s) for s in args.control_seeds.split(",") if s}
    no_limit = dict.fromkeys(("loss_rel_gap", "grad_norm_gap",
                              "grad_rel_err", "head_grad_rel_err",
                              "dparam_norm_gap"), float("inf"))
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ses = driver.TrainSession(cell, seed, t0, print)
        prog = ses.first_steps()
        peak = max(driver.device_peak_bytes(d.memory_stats() or {})
                   for d in ses.devices)
        ses.free_program()
        prog, ref = ses.check_against_reference(prog)
        row = {"seed": seed, "peak_bytes": peak, "sound": {
            r["name"]: [r["value"], r["at"]]
            for r in reference.compare(prog, ref, no_limit)}}
        row["leaves_sound"] = {n: ref["grad_diff_norm"][n] / v
                               for n, v in ref["grad_norm"].items()}
        rows = ses.fed_rows_wrong()
        if rows is not None:
            row["fed_rows_wrong"] = rows["value"]
        if seed in control:
            low = ses.reference(precision="float8", keep_first_grad=True)
            ref = ses.reference(first_grad_of_program=low.pop("first_grad"))
            row["leaves_control"] = {n: ref["grad_diff_norm"][n] / v
                                     for n, v in ref["grad_norm"].items()}
            row["control"] = {
                r["name"]: [r["value"], r["at"]]
                for r in reference.compare(low, ref, no_limit)}
        row["seconds"] = time.perf_counter() - t0
        print("LIMITS " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
