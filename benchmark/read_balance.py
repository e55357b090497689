"""Read what a cell's balance warm-up is set from, on the chip.

    python3 benchmark/read_balance.py --workload <cell> --seeds 1,2,3,...
                                      [--steps 80]

For a cell whose expert layers hold their loads to the mean by a rule
that runs with the step (`sessions/xing4_lm.py`): one program, and for
every seed a run from that seed's first state through the first steps
and `--steps` more, the step's counters copied on the device after every
step and read at the end. Prints, per seed, for every multiple of 20
warm-up steps each expert layer's held share of the slots over the
`balance_last` steps before the window would open there, whether all lay
within `balance_band` (relative) of the even share, and the largest
share of the even load that the held experts of one layer were given in
one step (what the sorted buffer of `ops/moe.py` is sized against).
`warmup_steps` of the traffic file is the smallest multiple of 20 at
which every seed is within the band, times 1.25 (PERF.md section 4).
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
    ap.add_argument("--steps", type=int, default=80)
    args = ap.parse_args(argv)
    import jax

    from benchmark.manifest import Manifest
    from veles_tpu.caches import enable_compilation_cache
    from veles_tpu.znicz import lm
    man = Manifest(ROOT)
    cell = man.cell(args.workload)
    enable_compilation_cache()
    session = man.session(cell)
    tr = cell["traffic_data"]
    d = session.xing4_ops_count.dims(cell["config_data"])
    last, band = int(tr["balance_last"]), float(tr["balance_band"])
    lag = int(tr.get("steps_in_flight", 1))
    t0 = time.perf_counter()
    ses = session.TrainSession(cell, 0, t0, print)
    first = session.CHECK_STEPS
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ses.start_from(seed)
        copies = [ses.counters_now()]
        for _ in range(first + args.steps):
            ses.pending.append(ses.dispatch()[0])
            copies.append(ses.counters_now())
            if len(ses.pending) > lag:
                ses.sync_oldest()
        while ses.pending:
            ses.sync_oldest()
        seen = [lm.moe_counts(ses.step, c) for c in jax.device_get(copies)]
        layers = sorted(seen[0])
        # the even share of the slots, and of one step's slots of a layer
        even = d["held"] / d["experts"]
        even_load = seen[1][layers[0]]["slots"] * even
        row = {"seed": seed, "steps": first + args.steps, "at": {},
               "fullest_layer_step": max(
                   (b[n]["held"] - a[n]["held"]) / even_load
                   for a, b in zip(seen, seen[1:]) for n in layers),
               "dropped": sum(seen[-1][n]["dropped"] for n in layers)}
        for warm in range(20, args.steps + 1, 20):
            hi = first + warm       # the window would open at this step
            lo = max(0, hi - last)
            shares = {n: (seen[hi][n]["held"] - seen[lo][n]["held"])
                      / max(seen[hi][n]["slots"] - seen[lo][n]["slots"], 1)
                      for n in layers}
            off = max(abs(s / even - 1.0) for s in shares.values())
            row["at"][warm] = {"shares": shares, "worst_off": off,
                               "reached": off <= band}
        row["seconds"] = time.perf_counter() - t0
        print("BALANCE " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
