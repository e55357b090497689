"""The scope reduction (`benchmark/scope_reduce.py`) and the readers of the
metrics PR 24 added: on a hand-made trace with the arithmetic written
out (as events, and as a real `.xplane.pb` written from a text proto with
a hand-encoded HloProto inside, so that the wire reader and `ProfileData`
are both under test), on a
cut-down copy of a real trace of `alexnet.step` with scopes, and against
the program's registry."""

import json
import os

import pytest

from bench_paths import FIXTURES, ROOT

from benchmark import scope_reduce as S
from benchmark import trace_reduce as T
from benchmark.manifest import Manifest, problems

CONV, NORM, POOL = "L00.conv_strictrelu", "L01.norm", "L02.max_pooling"
J = "jit(train_step)/jit(main)/"

#: device 0, seconds: (name, scope path, start, end). The whole step
#: runs from 10 to 20; the first and last runs are cut by the trace.
#:
#:   forward   conv 10..12, norm 12..13, pool 13..13.5, loss 13.5..14 = 4.0
#:   backward  norm+pool fusion 14..15.5, pool 15.5..16, conv 16..18   = 4.0
#:   all-reduce 17..18.5: conv's backward hides 17..18, 0.5 exposed
#:   update    18.5..19                                                = 0.5
#:   all-gather 19..19.6: nothing beside it, 0.6 exposed
#:   a copy with no scope 19.6..19.8                                   = 0.2
#:   idle 19.8..20: busy = 9.8 = 4.0 + 4.0 + 0.5 + (0.5 + 0.6) + 0.2
#:   norm and pool units: 1 + 0.5 forward, 1.5 + 0.5 backward        = 3.5
HAND_OPS = [
    ("%convolution.1 = bf16[8]", J + f"jvp({CONV})/conv_general", 10, 12),
    ("%fusion.2 = bf16[8]", J + f"jvp({NORM})/mul", 12, 13),
    ("%reduce-window.3 = bf16[8]", J + f"jvp({POOL})/reduce_window", 13,
     13.5),
    ("%fusion.4 = f32[]", J + "jvp(loss)/jit(log_softmax)/sub", 13.5, 14),
    ("%fusion.5 = bf16[8]", J + f"transpose(jvp({NORM}))/mul", 14, 15.5),
    ("%select-and-scatter.6 = bf16[8]",
     J + f"transpose(jvp({POOL}))/select_and_scatter_add", 15.5, 16),
    ("%convolution.7 = f32[8]", J + f"transpose(jvp({CONV}))/conv_general",
     16, 18),
    ("%all-reduce.8 = f32[8]",
     J + f"update/{CONV}/grad_exchange/psum_scatter", 17, 18.5),
    ("%fusion.9 = f32[8]", J + f"update/{CONV}/add", 18.5, 19),
    ("%all-gather.10 = f32[8]",
     J + f"update/{CONV}/param_gather/all_gather_invariant", 19, 19.6),
    ("%copy.11 = f32[8]", "", 19.6, 19.8),
    ("%convolution.1 = bf16[8]", J + f"jvp({CONV})/conv_general", 8, 9.5),
    ("%convolution.1 = bf16[8]", J + f"jvp({CONV})/conv_general", 20, 21),
]
HAND_MODULES = [("jit_train_step(7)", 8, 10), ("jit_train_step(7)", 10, 20),
                ("jit_train_step(7)", 20, 21), ("jit_norms(5)", 1, 2)]
#: the program's spans: (seq, start, end); the first lies before the window
HAND_DISPATCH = [(5, 9.0, 9.3), (6, 10.5, 10.6), (7, 19.5, 19.7)]


def ident(name: str) -> str:
    """`%fusion.2 = bf16[8]` -> `fusion.2`, the instruction's name."""
    return S.INSTRUCTION.match(name).group(1)


def hand_reduction():
    ops = [(n, lo, hi) for n, _s, lo, hi in HAND_OPS]
    scopes = {ident(n): s for n, s, _lo, _hi in HAND_OPS}
    return S.reduce_scopes(ops, HAND_MODULES, scopes)


def check_the_hand_numbers(r):
    assert r["steps"] == 1
    assert r["step_device_s"] == pytest.approx(9.8)
    assert r["phase_s"] == pytest.approx(
        {"forward": 4.0, "backward": 4.0, "update": 0.5,
         "collective": 2.1, "unscoped": 0.2})
    assert r["collective_exposed_s"] == pytest.approx(1.1)
    assert r["allreduce_exposed_s"] == pytest.approx(0.5)
    assert r["allgather_exposed_s"] == pytest.approx(0.6)
    assert r["norm_pool_s"] == pytest.approx(3.5)
    assert sum(r["phase_s"][p] for p in ("forward", "backward", "update",
                                         "unscoped")) \
        + r["collective_exposed_s"] == pytest.approx(r["step_device_s"])
    assert r["unit_s"][(CONV, "forward")] == pytest.approx(2.0)
    assert r["unit_s"][(CONV, "backward")] == pytest.approx(2.0)
    assert r["unit_s"][(CONV, "update")] == pytest.approx(0.5)
    assert r["unit_s"][(CONV, "collective")] == pytest.approx(2.1)
    assert r["unit_s"][(NORM, "backward")] == pytest.approx(1.5)
    assert r["unit_s"][("loss", "forward")] == pytest.approx(0.5)
    assert r["unit_s"][("", "unscoped")] == pytest.approx(0.2)
    assert r["scope_names"] == [CONV, NORM, POOL, "loss"]


def test_the_hand_made_trace_sorts_into_the_phases_worked_out_by_hand():
    check_the_hand_numbers(hand_reduction())


@pytest.mark.parametrize("name,scope,phase,unit", [
    ("%fusion.1", J + "jvp(L03.norm)/mul", "forward", "L03.norm"),
    ("%fusion.1", J + "transpose(jvp(L03.norm))/mul", "backward",
     "L03.norm"),
    ("%fusion.1", J + "jvp(L01.norm+L02.max_pooling)/pallas_call",
     "forward", "L01.norm+L02.max_pooling"),
    ("%fusion.1", "jit(train_step)/shard_map/update/L14.softmax/mul",
     "update", "L14.softmax"),
    ("%reduce-scatter.2",
     "jit(train_step)/shard_map/update/L14.softmax/grad_exchange/x",
     "collective", "L14.softmax"),
    ("%all-reduce.3", J + "loss/psum", "collective", "loss"),
    ("%fusion.1", J + "jvp(input_normalize)/convert_element_type",
     "forward", "input_normalize"),
    ("%fusion.1", J + "transpose(jvp(cast_params))/convert_element_type",
     "backward", "cast_params"),
    ("%fusion.1", "jit(train_step)/jit(main)/jit(_threefry_fold_in)/x",
     "unscoped", ""),
    ("%copy.4", "", "unscoped", ""),
    ("%fusion.1", "jit(train_step)/jit(main)/dynamic_update_slice",
     "unscoped", ""),
    ("%fusion.1", "jit(f)/jit(main)/closs/mul", "unscoped", ""),
    # an `update` inside another word is no update scope
    ("%fusion.1", J + "jvp(L05.dynamic_update_slice)/x", "forward",
     "L05.dynamic_update_slice"),
])
def test_phase_and_unit_of_a_scope_path(name, scope, phase, unit):
    assert S.phase_of(name, scope) == phase
    assert S.unit_of(scope) == unit


def test_an_operation_the_compiler_made_counts_with_its_first_operand():
    pool = J + f"jvp({POOL})/reduce_window"
    ins = {
        "reduce-window.3": (pool, "fusion.2"),
        # a packed mask of the pooling, made by the compiler: no op_name
        "convert_reduce_fusion.1": ("", "reduce-window.3"),
        # a copy of that through a bitcast, which is no event of a trace
        "bitcast.4": ("", "convert_reduce_fusion.1"),
        "copy-start.5": ("", "bitcast.4"),
        "copy-done.5": ("", "copy-start.5"),
        # nothing to inherit from: a parameter, an operand not in the
        # module, a cycle
        "param.1": ("", None),
        "copy.7": ("", "gone.3"),
        "a.1": ("", "b.2"),
        "b.2": ("", "a.1"),
        "fusion.2": (J + "jvp(loss)/x", "param.1"),
    }
    got = S.inherit_scopes(ins)
    assert [got[n] for n in ins] == [pool] * 5 + [""] * 4 + [
        J + "jvp(loss)/x"]
    assert S.scope_of_event("%copy-done.5 = u32[8] copy-done(...)",
                            got) == pool
    assert S.scope_of_event("copy-done.5", got) == ""     # no HLO text


def test_a_program_without_scopes_gives_nothing():
    """The parent of PR 24: operations carry jax's own paths, but no
    scope of the program's."""
    ops = [(n, lo, hi) for n, _s, lo, hi in HAND_OPS]
    scopes = {ident(n): "jit(<lambda>)/jit(main)/jvp(jit(relu))/max"
              for n, _s, _lo, _hi in HAND_OPS}
    assert S.reduce_scopes(ops, HAND_MODULES, scopes) is None
    assert S.reduce_scopes(ops, HAND_MODULES, {}) is None
    assert S.reduce_scopes(ops, HAND_MODULES[:1], {}) is None


# -- the same trace as a real file --------------------------------------------------


def ps(seconds: float) -> int:
    return int(round(seconds * 1e12))


def enc(field: int, value) -> bytes:
    """One protobuf field: a varint for an int, length-delimited else."""
    def varint(n: int) -> bytes:
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(field << 3 | 2) + varint(len(value)) + value


def hand_hlo_proto() -> bytes:
    """The hand-made step as an HloProto: every operation with its
    `op_name`, the scopeless copy reading a bitcast of the update."""
    names = sorted({n for n, *_ in HAND_OPS})
    ids = {ident(n): 300 + i for i, n in enumerate(names)}
    scopes = {ident(n): s for n, s, _lo, _hi in HAND_OPS}
    rows = b""
    for name, iid in ids.items():
        row = enc(1, name) + enc(35, iid)
        if scopes[name]:
            row += enc(7, enc(1, "op") + enc(2, scopes[name]))
        rows += enc(2, row)
    # operand ids, packed as proto3 packs them and one by one
    rows += enc(2, enc(1, "param.0") + enc(35, 7))
    rows += enc(2, enc(1, "bitcast.12") + enc(35, 400)
                + enc(36, bytes([7, 7])))
    rows += enc(2, enc(1, "copy.13") + enc(35, 401) + enc(36, 400))
    module = enc(1, "jit_train_step") + enc(3, enc(1, "main") + rows)
    return enc(1, module)


def hand_xplane(tmp_path) -> str:
    """The hand-made trace as an `.xplane.pb` where the driver would have
    written it: the operations' events carry no stats, as on a TPU; the
    scope paths are in the module's HloProto in the metadata plane, which
    ProfileData does not show."""
    from jax.profiler import ProfileData
    names = sorted({n for n, *_ in HAND_OPS} | {m for m, *_ in HAND_MODULES})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())

    def events(rows):
        return "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {ps(lo)} "
            f"duration_ps: {ps(hi - lo)} }}\n" for n, lo, hi in rows)
    spans = "".join(
        f"events {{ metadata_id: 1 offset_ps: {ps(lo)} duration_ps: "
        f"{ps(hi - lo)} stats {{ metadata_id: 1 int64_value: {seq} }} }}\n"
        for seq, lo, hi in HAND_DISPATCH)
    blob = "".join(f"\\{b:03o}" for b in hand_hlo_proto())
    text = f'''
planes {{ name: "/device:TPU:0"
  lines {{ name: "XLA Modules" {events(HAND_MODULES)} }}
  lines {{ name: "XLA Ops"
           {events([(n, lo, hi) for n, _s, lo, hi in HAND_OPS])} }}
  {meta}
}}
planes {{ name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_other(3)"
    stats {{ metadata_id: 1 bytes_value: "\\012\\000" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_train_step(7)"
    stats {{ metadata_id: 1 bytes_value: "{blob}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
planes {{ name: "/host:CPU"
  lines {{ name: "python3" {spans}
           events {{ metadata_id: 2 offset_ps: {ps(10.5)}
                    duration_ps: {ps(0.1)} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "train.dispatch" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.dispatch" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "seq" }} }}
}}
'''
    out = tmp_path / "trace" / "plugins" / "profile" / "2026_09_27"
    out.mkdir(parents=True)
    path = out / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path / "trace")


def test_the_same_trace_as_a_file_reads_the_metadata_and_the_spans(tmp_path):
    trace_dir = hand_xplane(tmp_path)
    path = T.find_xplane(trace_dir)
    assert set(S.hlo_protos(path)) == {"jit_other(3)", "jit_train_step(7)"}
    ins = S.instructions(S.hlo_protos(path)["jit_train_step(7)"])
    assert ins["fusion.2"] == (J + f"jvp({NORM})/mul", None)
    assert ins["copy.11"] == ("", None)
    assert ins["bitcast.12"] == ("", "param.0")
    assert ins["copy.13"] == ("", "bitcast.12")
    scopes = S.module_scopes(path, "jit_train_step")
    assert scopes["fusion.9"] == J + f"update/{CONV}/add"
    assert scopes["copy.11"] == "" and S.module_scopes(path, "jit_x") == {}
    r = S.reduce_path(path)
    check_the_hand_numbers(r)
    # the span before the window does not count; bench.dispatch is not
    # the program's
    assert r["dispatch_seq"] == [6, 7]
    assert r["dispatch_s"] == pytest.approx([0.1, 0.2])
    assert "L01.norm" in S.table(r) and "train.dispatch: 2 spans" in \
        S.table(r)


def test_the_readers_read_the_trace_the_run_wrote(tmp_path, monkeypatch):
    from veles_tpu import caches
    trace_dir = hand_xplane(tmp_path)
    monkeypatch.setattr(
        caches, "cache_path",
        lambda *parts: str(tmp_path) if parts == ("benchmark", "a.cell")
        else str(tmp_path / "nothing"))
    man = Manifest(ROOT)
    ctx = {"cell": {"name": "a.cell"}, "counters": {"chips": 4},
           "trace": {"step_device_s": 9.8}}
    read = lambda name: man.layer_metric(name).read(ctx)  # noqa: E731
    assert read("step_fwd_ms") == pytest.approx(4000.0)
    assert read("step_bwd_ms") == pytest.approx(4000.0)
    assert read("step_update_ms") == pytest.approx(500.0)
    assert read("step_norm_pool_ms") == pytest.approx(3500.0)
    assert read("step_unscoped_share") == pytest.approx(100 * 0.2 / 9.8)
    assert read("allreduce_exposed_ms") == pytest.approx(500.0)
    assert read("allgather_exposed_ms") == pytest.approx(600.0)
    assert read("dispatch_ms") == pytest.approx(150.0)
    assert os.path.isdir(trace_dir)
    trace_names = ("step_fwd_ms", "step_bwd_ms", "step_update_ms",
                   "step_norm_pool_ms", "step_unscoped_share",
                   "allreduce_exposed_ms", "allgather_exposed_ms",
                   "dispatch_ms")
    # nothing to read: one chip has no collectives; a run that was not
    # traced, and a cell whose trace is not there, read nothing
    ctx["counters"]["chips"] = 1
    assert read("allreduce_exposed_ms") is None
    assert read("allgather_exposed_ms") is None
    for broken in ({"trace": None}, {"cell": {"name": "other.cell"}}):
        for name in trace_names:
            assert man.layer_metric(name).read({**ctx, **broken}) is None


# -- a real trace, cut down ----------------------------------------------------------


def test_the_recorded_alexnet_step_trace_with_scopes():
    """Worked out beside the fixture when it was cut (PR 24)."""
    with open(os.path.join(FIXTURES, "alexnet_step_scopes_trace.json")) as f:
        fx = json.load(f)
    rows = fx["devices"]["0"]
    assert len(fx["scopes"]) > 300            # by instruction name
    r = S.reduce_scopes(rows[T.OPS_LINE], rows[T.MODULES_LINE],
                        fx["scopes"])
    want = fx["worked_out"]
    assert r["steps"] == want["steps"]
    assert 1e3 * r["step_device_s"] == pytest.approx(
        want["step_device_ms"], abs=1e-4)
    for phase, ms in want["phase_ms"].items():
        assert 1e3 * r["phase_s"][phase] == pytest.approx(ms, abs=1e-4)
    assert 1e3 * r["norm_pool_s"] == pytest.approx(want["norm_pool_ms"],
                                                   abs=1e-4)
    assert r["scope_names"] == want["scope_names"]
    # the instrument's own check on a real trace: the phases add up to
    # the step, and next to nothing is left without a scope
    assert sum(r["phase_s"].values()) == pytest.approx(
        r["step_device_s"], rel=1e-3)
    assert r["phase_s"]["unscoped"] / r["step_device_s"] < 0.03
    # the same step through trace_reduce: one window, one busy time
    base = T.reduce_device(rows[T.OPS_LINE], rows[T.MODULES_LINE])
    assert base["busy_s"] / base["steps"] == pytest.approx(
        r["step_device_s"])
    assert base["step_module"] == "jit_train_step"


# -- the registry's counters -----------------------------------------------------------


def test_the_fed_metrics_read_the_programs_registry():
    from veles_tpu.telemetry import metrics
    metrics.reset_default_registry()
    man = Manifest(ROOT)
    read = lambda name: man.layer_metric(name).read({})  # noqa: E731
    fed = ("feed_gather_ms", "feed_put_ms", "feed_lookahead_ready_share",
           "feed_h2d_late_share")
    try:
        # no feed ran: the counters are there and read 0 batches
        assert [read(n) for n in fed] == [None] * 4
        lh, fh = metrics.loader_handles(), metrics.feed_handles()
        lh.produce_s.inc(9.6)
        lh.produced.inc(240)
        lh.ready.inc(180)
        lh.waited.inc(60)
        fh.put_s.inc(14.4)
        fh.batches.inc(240)
        fh.h2d_late.inc(24)
        fh.h2d_ready.inc(216)
        assert read("feed_gather_ms") == pytest.approx(40.0)
        assert read("feed_put_ms") == pytest.approx(60.0)
        assert read("feed_lookahead_ready_share") == pytest.approx(75.0)
        assert read("feed_h2d_late_share") == pytest.approx(10.0)
        # a program from before the counters: nothing, and no error
        metrics.reset_default_registry()
        metrics._DEFAULT = metrics.MetricsRegistry()
        assert [read(n) for n in fed] == [None] * 4
    finally:
        metrics.reset_default_registry()


# -- the manifest ----------------------------------------------------------------------


NEW = {
    "step_fwd_ms": ("device_trace", "fused step",
                    ["alexnet.step", "vgg16.step", "vgg16.dp4"]),
    "step_bwd_ms": ("device_trace", "fused step",
                    ["alexnet.step", "vgg16.step", "vgg16.dp4"]),
    "step_update_ms": ("device_trace", "fused step",
                       ["alexnet.step", "vgg16.step", "vgg16.dp4"]),
    "step_norm_pool_ms": ("device_trace", "ops and kernels",
                          ["alexnet.step", "vgg16.step"]),
    "step_unscoped_share": ("device_trace", "fused step",
                            ["alexnet.step", "vgg16.step", "vgg16.dp4"]),
    "allreduce_exposed_ms": ("device_trace", "collectives", ["vgg16.dp4"]),
    "allgather_exposed_ms": ("device_trace", "collectives", ["vgg16.dp4"]),
    "dispatch_ms": ("program_span", "fused step",
                    ["alexnet.step", "vgg16.step", "vgg16.dp4"]),
    "feed_gather_ms": ("program_counter", "feed", ["alexnet.feed"]),
    "feed_put_ms": ("program_counter", "feed", ["alexnet.feed"]),
    "feed_lookahead_ready_share": ("program_counter", "feed",
                                   ["alexnet.feed"]),
    "feed_h2d_late_share": ("program_counter", "feed", ["alexnet.feed"]),
}


def test_the_manifest_holds_the_twelve_new_metrics_and_no_problem():
    man = Manifest(ROOT)
    assert problems(man) == []
    entries = {m["name"]: m for m in man.data["per_layer"]}
    # appended: what PR 23 defined is where it was
    assert [m["name"] for m in man.data["per_layer"]][9:] == list(NEW)
    for name, (source, layer, cells) in NEW.items():
        m = entries[name]
        assert (m["source"], m["layer"], m["workloads"]) == (
            source, layer, cells), name
        assert callable(man.layer_metric(name).read)
        assert man.layer_metric(name).__doc__
    fed = [m for m in NEW if m.startswith("feed_")]
    assert all(entries[m]["moves"] == "fed_samples_per_s_per_chip"
               for m in fed)
    assert all(entries[m]["moves"] == "train_samples_per_s_per_chip"
               for m in NEW if m not in fed)
