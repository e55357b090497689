"""From a profiler trace (`.xplane.pb`) to the numbers the metrics read.

Reads the trace with `jax.profiler.ProfileData` and nothing else. What it
keeps of a trace is a small plain structure (`events_of`), so that the
arithmetic (`reduce_events`) runs the same on a recorded fixture.

- A device plane is a plane named `/device:TPU:<n>`. Its line `XLA Ops`
  holds one event for every operation that ran on that chip, its line
  `XLA Modules` one event for every run of a compiled program.
- The step is the module with the largest total time. Its first and its
  last run in a trace are cut by the trace's own start and stop, so they
  are left out: the traced window of a device runs from the start of its
  first whole step to the end of its last.
- busy = the union of the intervals of the operations inside the window;
  idle share = 1 - busy / window; device time of a step = busy / steps.
- exposed collective time = the part of the union of the collective
  operations' intervals in which no other operation runs on that device.
- The host plane's lines hold the loop's `TraceAnnotation` spans
  (`bench.*`); an idle gap of device 0 is named after the span that
  covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPAN = re.compile(r"^bench\.")
#: an operation's name in a trace is its whole HLO line; this much names it
NAME_CHARS = 96


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the union `a` that the union `b` does not cover."""
    out, b = [], list(b)
    for lo, hi in a:
        cur = lo
        for blo, bhi in b:
            if bhi <= cur or blo >= hi:
                continue
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
        if cur < hi:
            out.append((cur, hi))
    return out


def events_of(path: str) -> Dict[str, Any]:
    """The plain structure: per device its ops and modules as
    (name, start_s, end_s), and the host's `bench.*` spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                rows = devices.setdefault(
                    int(m.group(1)), {OPS_LINE: [], MODULES_LINE: []})
                rows[line.name] = [
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
            elif not m and plane.name.startswith("/host:"):
                host += [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events if HOST_SPAN.match(e.name)]
    return {"devices": devices, "host": host}


def _module_key(name: str) -> str:
    """`jit_train(123)` and `jit_train(124)` are one program."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_device(ops: list, modules: list) -> Optional[Dict[str, Any]]:
    if not ops or not modules:
        return None
    by_key: Dict[str, float] = {}
    for name, lo, hi in modules:
        by_key[_module_key(name)] = by_key.get(_module_key(name), 0) + hi - lo
    step_key = max(by_key, key=by_key.get)
    steps = sorted((lo, hi) for name, lo, hi in modules
                   if _module_key(name) == step_key)[1:-1]
    if not steps:
        return None
    w_lo, w_hi = steps[0][0], steps[-1][1]
    inside = [(n, lo, hi) for n, lo, hi in ops if lo >= w_lo and hi <= w_hi]
    busy = union([(lo, hi) for _n, lo, hi in inside])
    coll = union([(lo, hi) for n, lo, hi in inside if COLLECTIVE.match(n)])
    rest = union([(lo, hi) for n, lo, hi in inside
                  if not COLLECTIVE.match(n)])
    by_op: Dict[str, float] = {}
    for n, lo, hi in inside:
        by_op[n[:NAME_CHARS]] = by_op.get(n[:NAME_CHARS], 0.0) + hi - lo
    gaps = subtract([(w_lo, w_hi)], busy)
    return {"step_module": step_key, "steps": len(steps),
            "window": (w_lo, w_hi), "window_s": w_hi - w_lo,
            "busy_s": total(busy), "collective_s": total(coll),
            "collective_exposed_s": total(subtract(coll, rest)),
            "by_op": by_op, "gaps": gaps}


def name_gap(gap: Interval, host: list) -> str:
    best, name = 0.0, "no bench span"
    for n, lo, hi in host:
        cover = min(hi, gap[1]) - max(lo, gap[0])
        if cover > best:
            best, name = cover, n
    return name


def reduce_events(ev: Dict[str, Any], n_devices: int) -> Dict[str, Any]:
    per = {d: reduce_device(rows[OPS_LINE], rows[MODULES_LINE])
           for d, rows in sorted(ev["devices"].items())}
    per = {d: r for d, r in per.items() if r}
    if len(per) < n_devices:
        raise RuntimeError(f"the trace holds {len(per)} device planes with "
                           f"operations, the cell uses {n_devices}")
    used = [per[d] for d in sorted(per)[:n_devices]]
    d0 = used[0]
    ops = sorted(d0["by_op"].items(), key=lambda kv: -kv[1])[:10]
    by_host: Dict[str, float] = {}
    for gap in d0["gaps"]:
        n = name_gap(gap, ev["host"])
        by_host[n] = by_host.get(n, 0.0) + gap[1] - gap[0]
    return {
        "busy_s": sum(r["busy_s"] for r in used) / len(used),
        "window_s": sum(r["window_s"] for r in used) / len(used),
        "steps": d0["steps"],
        "step_module": d0["step_module"],
        "step_device_s": d0["busy_s"] / d0["steps"],
        "collective_exposed_s_per_step":
            d0["collective_exposed_s"] / d0["steps"],
        "collective_s_per_step": d0["collective_s"] / d0["steps"],
        "breakdown": {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in sorted(
                by_host.items(), key=lambda kv: -kv[1])[:10]],
        },
    }


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, n_devices: int) -> Dict[str, Any]:
    return reduce_events(events_of(find_xplane(trace_dir)), n_devices)


def describe(path: str, limit: int = 12) -> str:
    """What a trace looks like, for a first look by hand."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            seen: Dict[str, List[float]] = {}
            for e in evs:
                seen.setdefault(e.name, []).append(e.duration_ns * 1e-9)
            top = sorted(seen.items(), key=lambda kv: -sum(kv[1]))[:limit]
            for n, ds in top:
                out.append(f"    {n[:90]!r}: n={len(ds)} total={sum(ds):.6f}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(find_xplane(sys.argv[1])))
