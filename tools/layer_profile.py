"""Per-LAYER wall-time profile of AlexNet on the device, using the
granular unit graph's built-in per-unit timing table (the reference's
profiler) with a device sync after every unit so times are attributable.

Besides the human table, results persist as machine-readable JSON to
LAYER_PROFILE.json (override: --json PATH or $VELES_LAYER_PROFILE_PATH)
— the budgeted kernel search (ops.autotune.search_workflow, CLI
`--autotune-budget`) reads the per-OP cost shares from that file as its
priority order, so the trial budget is spent on the ops that own the
roofline gap. `--trace-json` folds a PR-7 `--trace`
capture's span totals into the record, so an on-chip profile carries the
driver-level context (step/feed/device_sync) next to the per-unit table.

Usage: python tools/layer_profile.py [batch] [steps] [--json PATH]
       [--trace-json TRACE.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ONE path rule shared with the consumer (the search's priority_order):
# jax-free at module scope, so the import is safe here
from veles_tpu.ops.autotune import default_profile_path  # noqa: E402


def profile_workflow(wf, steps: int = 10) -> List[Dict[str, Any]]:
    """Drive forward+backward by hand with a sync after every unit so
    per-unit time is attributable; returns one record per unit:
    {name, class, op (variant_op or None), run_time_s, run_count}."""
    import jax

    from veles_tpu.loader.base import TRAIN

    def timed(u):
        t0 = time.perf_counter()
        u.run()
        out = getattr(u, "output", None) or getattr(u, "err_input", None)
        if out and u.device is not None:
            jax.block_until_ready(out.devmem(u.device))
        u.run_time += time.perf_counter() - t0
        u.run_count += 1

    ld = wf.loader
    done = 0
    while done < steps:
        ld.run()
        if ld.minibatch_class != TRAIN:
            continue
        for u in wf.forwards:
            timed(u)
        timed(wf.evaluator)
        for g in wf.gds:
            timed(g)
        done += 1

    def op_of(u):
        """The tunable op a unit's time belongs to. A GD twin's cost is
        its FORWARD's op (the LRN backward is the LRN lowering's cost);
        twins are matched through the link_attrs-shared output Array,
        with the VJP family's `_fwd` as the direct route."""
        op = getattr(u, "variant_op", None)
        if op is not None:
            return op
        fwd = getattr(u, "_fwd", None)
        if fwd is None:
            out = getattr(u, "output", None)
            if out is not None:
                for f in wf.forwards:
                    if getattr(f, "output", None) is out:
                        fwd = f
                        break
        return getattr(fwd, "variant_op", None)

    records: List[Dict[str, Any]] = []
    for u in list(wf.forwards) + [wf.evaluator] + list(wf.gds):
        records.append({
            "name": getattr(u, "name", type(u).__name__),
            "class": type(u).__name__,
            "op": op_of(u),
            "run_time_s": round(float(getattr(u, "run_time", 0.0)), 6),
            "run_count": int(getattr(u, "run_count", 0)),
        })
    return records


def op_shares(records: List[Dict[str, Any]]) -> Dict[str, float]:
    """{op: fraction of total profiled unit time} over every unit that
    maps to a tunable op — the search's priority weights."""
    total = sum(r["run_time_s"] for r in records) or 1.0
    out: Dict[str, float] = {}
    for r in records:
        if r["op"]:
            out[r["op"]] = out.get(r["op"], 0.0) + r["run_time_s"]
    return {k: round(v / total, 4) for k, v in out.items()}


def split_fused_shares(shares: Dict[str, float]) -> Dict[str, float]:
    """Attribute a FUSED kernel's time back to its member ops. A record
    whose shares carry a fusion-op key (e.g. "lrn_maxpool" from an
    on-chip span capture of a fused step — the granular graph never
    fuses, so its own records always carry per-member keys) would make
    the search see ONE op where two live: the fused pair's time must
    land on `lrn` and `maxpool`, split by the PRE-FUSION share ratio
    (the members' own shares in the same record; equal split when both
    are absent/zero), or a later search round would starve the
    neighbor's budget. The inverse of `priority_order`'s combined-share
    charging — between them, fused time is neither dropped nor
    double-counted."""
    from veles_tpu.ops import templates
    out = dict(shares)
    for op in list(out):
        members = templates.fusion_members(op)
        if not members:
            continue
        fused = out.pop(op)
        base = [max(float(out.get(m, 0.0)), 0.0) for m in members]
        total = sum(base)
        if total <= 0.0:
            base = [1.0] * len(members)
            total = float(len(members))
        for m, b in zip(members, base):
            out[m] = round(out.get(m, 0.0) + fused * b / total, 4)
    return out


def fold_trace_spans(trace_path: str) -> Dict[str, Any]:
    """Total duration per span name from a PR-7 --trace capture
    (Chrome-trace JSON) — driver-level context for the record. Missing
    or unreadable trace degrades to {}."""
    try:
        with open(trace_path) as f:
            data = json.load(f)
        events = data.get("traceEvents", [])
    except (OSError, ValueError, AttributeError):
        return {}
    totals: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        name = str(ev.get("name", "?"))
        totals[name] = totals.get(name, 0.0) \
            + float(ev.get("dur", 0.0)) / 1e6
        counts[name] = counts.get(name, 0) + 1
    return {name: {"total_s": round(t, 6), "count": counts[name]}
            for name, t in sorted(totals.items())}


def write_profile(records: List[Dict[str, Any]], path: str,
                  meta: Optional[Dict[str, Any]] = None,
                  trace_json: Optional[str] = None) -> Dict[str, Any]:
    """Assemble + atomically persist the machine-readable record the
    search consumes. Returns the record."""
    raw = op_shares(records)
    split = split_fused_shares(raw)
    record = {
        "schema": "veles-layer-profile",
        "version": 1,
        "units": records,
        # the search consumes PER-MEMBER shares: any fused-kernel key is
        # split back to its member ops (split_fused_shares) so a fusion
        # winner landing never starves its neighbor's budget
        "ops": split,
        **(meta or {}),
    }
    if split != raw:
        record["ops_raw"] = raw
    if trace_json:
        spans = fold_trace_spans(trace_json)
        if spans:
            record["driver_spans"] = spans
            record["trace_json"] = trace_json
    tmp = f"{path}.tmp.{os.getpid()}"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return record


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("batch", nargs="?", type=int, default=256)
    p.add_argument("steps", nargs="?", type=int, default=10)
    p.add_argument("--json", default=None, metavar="PATH",
                   help="machine-readable output (default: "
                        "$VELES_LAYER_PROFILE_PATH or "
                        "LAYER_PROFILE.json)")
    p.add_argument("--trace-json", default=None, metavar="TRACE.json",
                   help="fold a --trace capture's span totals into the "
                        "record (driver-level context)")
    args = p.parse_args(argv)

    import jax

    from veles_tpu import prng
    from veles_tpu.samples.alexnet import create_workflow

    prng.seed_all(1)
    wf = create_workflow(minibatch_size=args.batch,
                         n_train=2 * args.batch,
                         n_validation=args.batch)
    wf.initialize(device=None)
    records = profile_workflow(wf, steps=args.steps)
    print(wf.print_stats())          # the human table stays
    path = args.json or default_profile_path()
    record = write_profile(
        records, path,
        meta={"batch": args.batch, "steps": args.steps,
              "device_kind": jax.devices()[0].device_kind,
              "profiled_at": time.time()},
        trace_json=args.trace_json)
    print(f"LAYER_PROFILE -> {path}  ops="
          + json.dumps(record["ops"], sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
