"""From a profiler trace to device time per program scope.

The fused step names its work (`veles_tpu/parallel/fused.py`): every
operation of the compiled step carries a `jax.named_scope` path,
`jit(train_step)/jvp(L01.norm)/...` forward,
`.../transpose(jvp(L01.norm))/...` backward, `.../update/L05.softmax/...`
for the optimizer. This module opens a run's `.xplane.pb` a second time
(`trace_reduce` reduced it once, by operation) and sorts device 0's
operations by that path:

- `collective`: by opcode, as `trace_reduce.COLLECTIVE`;
- `update`:     `update` is a component of the path;
- `backward`:   `transpose(` is in the path;
- `forward`:    any other program scope (a unit's `L<nn>.<type>`,
                `input_normalize`, `cast_params`, `loss`);
- `unscoped`:   none of these (the instrument's own check). An operation
                the compiler made, which has no path of its own, is first
                given that of the operation that produced its first
                operand (`inherit_scopes`).

A phase's time is the union of its operations' intervals inside the
traced window, per step. The window and the steps are
`trace_reduce.reduce_device`'s, imported, not copied.

Where the path is carried: `jax.profiler.ProfileData` shows an event's
own stats only, and a TPU operation's event has none but its offsets; its
name is the instruction's HLO text, which ends before `metadata={...}`.
The capture's `/host:metadata` plane holds each executed module's
`HloProto` (the `Hlo Proto` stat of the module's event metadata), which
only the raw protobuf gives: every instruction's `metadata.op_name` (the
same string the device plane's event metadata carries as `tf_op`) and its
operands. `module_scopes` reads just that with a wire reader of its own
(the installation has no `xplane_pb2` or `hlo_pb2` outside tensorflow).
Operations the compiler made itself (a packed pooling mask, a layout
copy, a combined all-reduce) have no `op_name`: each is counted with the
instruction that produced its first operand, followed through the whole
graph, bitcasts and tuple elements included.

The program's spans (`telemetry/tracer.py`) are `TraceAnnotation`s in
the same capture's host plane, on the device's timeline:
`dispatch_spans` reads `train.dispatch` with its `seq`.

    python -m benchmark.scope_reduce <trace dir>

prints device time per unit scope and per phase: the table to read
instead of `%fusion.171`.
"""

from __future__ import annotations

import functools
import re
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmark import trace_reduce as T

PHASES = ("forward", "backward", "update", "collective", "unscoped")
#: a unit's scope, alone or as a searched fused pair
UNIT = re.compile(r"(?<![A-Za-z0-9_])L\d\d\.[A-Za-z0-9_]+"
                  r"(?:\+L\d\d\.[A-Za-z0-9_]+)*")
#: the scopes the fused step opens that are no unit's
OTHER = re.compile(
    r"(?:^|[/(])(input_normalize|cast_params|loss|update)(?=[/)]|$)")
UPDATE = re.compile(r"(?:^|/)update(?:/|$)")
ALLREDUCE = re.compile(r"^%?(all-reduce|reduce-scatter)")
ALLGATHER = re.compile(r"^%?all-gather")
NORM_POOL = re.compile(r"L\d\d\.(?:norm|max_pooling)(?![A-Za-z0-9_])")
DISPATCH_SPAN = "train.dispatch"
#: `%fusion.171 = ...`, an operation's name in a trace -> `fusion.171`
INSTRUCTION = re.compile(r"^%([A-Za-z0-9_.\-]+)")
INHERIT_HOPS = 16


# -- the raw protobuf: just enough of xplane.proto and hlo.proto -------------------
# XSpace.planes=1; XPlane.name=2 .event_metadata=4 (map: key=1 value=2);
# XEventMetadata.name=2 .stats=5; XStat.bytes_value=6.
# HloProto.hlo_module=1; HloModuleProto.computations=3;
# HloComputationProto.instructions=2; HloInstructionProto.name=1 .metadata=7
# .id=35 .operand_ids=36; OpMetadata.op_name=2


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: an int for a varint, the
    bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _sub(buf, *path: int) -> Iterator[Any]:
    """Every value at a path of field numbers below one message."""
    if not path:
        yield buf
        return
    for f, v in _fields(buf):
        if f == path[0]:
            yield from _sub(v, *path[1:])


def hlo_protos(path: str) -> Dict[str, Any]:
    """{module name as the trace gives it: its serialized HloProto}."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for plane in _sub(space, 1):
        if next((_text(v) for v in _sub(plane, 2)), "") != "/host:metadata":
            continue
        for meta in _sub(plane, 4, 2):
            blobs = list(_sub(meta, 5, 6))
            if blobs:
                out[next((_text(v) for v in _sub(meta, 2)), "")] = blobs[0]
    return out


def instructions(hlo_proto) -> Dict[str, Tuple[str, Optional[str]]]:
    """{instruction name: (op_name, name of its first operand)} over every
    computation of the module."""
    rows = {}
    for ins in _sub(hlo_proto, 1, 3, 2):
        name = op_name = ""
        ident, operands = None, []
        for f, v in _fields(ins):
            if f == 1:
                name = _text(v)
            elif f == 7:
                op_name = next((_text(x) for x in _sub(v, 2)), "")
            elif f == 35:
                ident = v
            elif f == 36 and isinstance(v, int):
                operands.append(v)
            elif f == 36:                       # packed
                i = 0
                while i < len(v):
                    one, i = _varint(v, i)
                    operands.append(one)
        rows[ident] = (name, op_name, operands[:1])
    return {name: (op_name, rows[ops[0]][0] if ops and ops[0] in rows
                   else None)
            for name, op_name, ops in rows.values()}


def inherit_scopes(ins: Dict[str, Tuple[str, Optional[str]]]
                   ) -> Dict[str, str]:
    """{instruction name: scope path}: its own `op_name`, or that of the
    instruction that produced its first operand, followed up to
    `INHERIT_HOPS` times."""
    out = {}
    for name, (scope, operand) in ins.items():
        for _ in range(INHERIT_HOPS):
            if scope or operand not in ins:
                break
            scope, operand = ins[operand]
        out[name] = scope
    return out


@functools.lru_cache(maxsize=4)
def module_scopes(path: str, module_key: str) -> Dict[str, str]:
    """{instruction name: scope path} of the module `trace_reduce` found
    to be the step (`jit_train_step`); empty where the capture holds no
    HloProto of it. One parse per path."""
    for name, blob in hlo_protos(path).items():
        if T._module_key(name) == module_key:
            return inherit_scopes(instructions(blob))
    return {}


def scope_of_event(name: str, scopes: Dict[str, str]) -> str:
    """The scope of an operation named as a trace names it (its HLO
    text), from a map keyed by instruction name."""
    m = INSTRUCTION.match(name)
    return scopes.get(m.group(1), "") if m else ""


# -- sorting the operations ----------------------------------------------------------


def phase_of(name: str, scope: str) -> str:
    if T.COLLECTIVE.match(name):
        return "collective"
    if UPDATE.search(scope):
        return "update"
    if "transpose(" in scope:
        return "backward"
    return "forward" if unit_of(scope) else "unscoped"


def unit_of(scope: str) -> str:
    """The unit an operation belongs to: its `L<nn>.<type>` (the
    innermost, so `update/L05.softmax/param_gather` is L05's), else the
    program scope that is not a unit, else nothing."""
    units = UNIT.findall(scope)
    if units:
        return units[-1]
    other = OTHER.search(scope)
    return other.group(1) if other else ""


def reduce_scopes(ops: list, modules: list, scopes: Dict[str, str]
                  ) -> Optional[Dict[str, Any]]:
    """`ops` and `modules` as `trace_reduce.events_of` gives them for one
    device, `scopes` by instruction name (`module_scopes`). None where
    there is no whole step, or no operation of the trace carries a program
    scope (a program from before the scopes)."""
    base = T.reduce_device(ops, modules)
    if base is None:
        return None
    w_lo, w_hi = base["window"]
    steps = base["steps"]
    inside = [(n, lo, hi, scope_of_event(n, scopes)) for n, lo, hi in ops
              if lo >= w_lo and hi <= w_hi]
    if not any(unit_of(s) for _n, _lo, _hi, s in inside):
        return None
    by_phase: Dict[str, list] = {p: [] for p in PHASES}
    by_unit: Dict[Tuple[str, str], list] = {}
    norm_pool, rest, reduce_, gather = [], [], [], []
    for n, lo, hi, s in inside:
        phase = phase_of(n, s)
        by_phase[phase].append((lo, hi))
        by_unit.setdefault((unit_of(s), phase), []).append((lo, hi))
        if phase in ("forward", "backward") and NORM_POOL.search(s):
            norm_pool.append((lo, hi))
        if phase != "collective":
            rest.append((lo, hi))
        elif ALLREDUCE.match(n):
            reduce_.append((lo, hi))
        elif ALLGATHER.match(n):
            gather.append((lo, hi))
    rest_u = T.union(rest)

    def per_step(intervals: list) -> float:
        return T.total(T.union(intervals)) / steps

    def exposed(intervals: list) -> float:
        return T.total(T.subtract(T.union(intervals), rest_u)) / steps

    return {
        "steps": steps,
        "window": (w_lo, w_hi),
        "step_device_s": base["busy_s"] / steps,
        "phase_s": {p: per_step(v) for p, v in by_phase.items()},
        "collective_exposed_s": exposed(by_phase["collective"]),
        "allreduce_exposed_s": exposed(reduce_),
        "allgather_exposed_s": exposed(gather),
        "norm_pool_s": per_step(norm_pool),
        "unit_s": {key: per_step(v) for key, v in by_unit.items()},
        "scope_names": sorted({u for (u, _p) in by_unit if u}),
    }


def dispatch_spans(path: str, window: Tuple[float, float]
                   ) -> List[Tuple[int, float]]:
    """(seq, seconds) of the program's `train.dispatch` spans in the host
    plane that lie inside the window."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != DISPATCH_SPAN:
                    continue
                lo = e.start_ns * 1e-9
                hi = lo + e.duration_ns * 1e-9
                if lo >= window[0] and hi <= window[1]:
                    seq = dict(e.stats).get("seq", -1)
                    out.append((int(seq), hi - lo))
    return sorted(out)


@functools.lru_cache(maxsize=4)
def reduce_path(path: str) -> Optional[Dict[str, Any]]:
    """Everything the metrics read of one `.xplane.pb`; one parse per
    path."""
    ev = T.events_of(path)
    if 0 not in ev["devices"]:
        return None
    ops, modules = (ev["devices"][0][k] for k in (T.OPS_LINE,
                                                  T.MODULES_LINE))
    base = T.reduce_device(ops, modules)
    if base is None:
        return None
    out = reduce_scopes(ops, modules,
                        module_scopes(path, base["step_module"]))
    if out is None:
        return None
    spans = dispatch_spans(path, out["window"])
    out["dispatch_s"] = [s for _seq, s in spans]
    out["dispatch_seq"] = [seq for seq, _s in spans]
    return out


def of_run(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """What a layer metric's `read(ctx)` gets: the reduction of the trace
    this run wrote, found by the driver's own rule; None where the run
    was not traced or its program carries no scopes."""
    if ctx.get("trace") is None:
        return None
    import os

    from veles_tpu.caches import cache_path
    trace_dir = os.path.join(
        cache_path("benchmark", ctx["cell"]["name"]), "trace")
    try:
        return reduce_path(T.find_xplane(trace_dir))
    except FileNotFoundError:
        return None


def registry_ratio(num: str, den: Tuple[str, ...], scale: float
                   ) -> Optional[float]:
    """`scale * num / sum(den)` of counters in the program's default
    metrics registry; None where the program has no such counter (a
    program from before them) or the denominator is 0 (no feed ran)."""
    from veles_tpu.telemetry import metrics
    flat = metrics.default_registry().snapshot_flat()
    if num not in flat or any(d not in flat for d in den):
        return None
    total = sum(flat[d] for d in den)
    return scale * flat[num] / total if total else None


def table(r: Dict[str, Any]) -> str:
    ms = 1e3
    lines = [f"{r['steps']} whole steps; device time of a step "
             f"{ms * r['step_device_s']:.3f} ms", "",
             f"{'phase':<12}{'ms/step':>10}{'share':>9}"]
    for p in PHASES:
        v = r["phase_s"][p]
        lines.append(f"{p:<12}{ms * v:>10.3f}"
                     f"{100 * v / r['step_device_s']:>8.1f}%")
    lines.append(f"{'exposed':<12}{ms * r['collective_exposed_s']:>10.3f}"
                 f"  (all-reduce/reduce-scatter "
                 f"{ms * r['allreduce_exposed_s']:.3f}, all-gather "
                 f"{ms * r['allgather_exposed_s']:.3f})")
    lines += ["", f"{'unit':<36}" + "".join(f"{p:>11}" for p in PHASES)]
    units = sorted({u for (u, _p) in r["unit_s"]})
    for u in units:
        row = [r["unit_s"].get((u, p), 0.0) for p in PHASES]
        lines.append(f"{u or '(no scope)':<36}"
                     + "".join(f"{ms * v:>11.3f}" for v in row))
    lines.append(f"norm and max_pooling units, forward and backward: "
                 f"{ms * r['norm_pool_s']:.3f} ms")
    if r.get("dispatch_s"):
        d = r["dispatch_s"]
        lines.append(f"{DISPATCH_SPAN}: {len(d)} spans, seq "
                     f"{r['dispatch_seq'][0]}..{r['dispatch_seq'][-1]}, "
                     f"mean {ms * sum(d) / len(d):.3f} ms")
    return "\n".join(lines)


if __name__ == "__main__":
    found = reduce_path(T.find_xplane(sys.argv[1]))
    print("no operation of this trace carries a program scope"
          if found is None else table(found))
