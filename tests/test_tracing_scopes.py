"""The measurement inside the program (ISSUE 24; docs/OBSERVABILITY.md):

- the compiled step's operations carry `jax.named_scope` paths that depend
  on the layer table only: the same set over two builds and over the
  local / dp / ZeRO modes, in the lowered and in the compiled text;
- every `pallas_call` site has its fixed kernel name;
- a span recorded with a profiler session open is in the session's host
  plane with its sequence number; with none open it is a shared no-op;
- the loader's and the feed's counters add up, in `feed.stats()` and in
  the one registry, with the producers themselves writing them.
"""

import glob
import os
import re
import time

import jax
import numpy as np
import pytest

import veles_tpu.ops.pallas_kernels as pk
from veles_tpu import prng
from veles_tpu.loader.base import PrefetchingLoader
from veles_tpu.loader.device_feed import DeviceFeed
from veles_tpu.telemetry import metrics, tracer

LAYERS = [
    {"type": "conv_strictrelu", "n_kernels": 8, "kx": 3, "ky": 3},
    {"type": "norm"},
    {"type": "max_pooling", "kx": 2, "ky": 2},
    {"type": "all2all_strictrelu", "output_sample_shape": 16},
    {"type": "dropout", "dropout_ratio": 0.5},
    {"type": "softmax", "output_sample_shape": 4},
]
UNITS = ["L00.conv_strictrelu", "L01.norm", "L02.max_pooling",
         "L03.all2all_strictrelu", "L04.dropout", "L05.softmax"]
TRAINED = ["L00.conv_strictrelu", "L03.all2all_strictrelu", "L05.softmax"]
MODES = {"local": {}, "dp": {"mesh": 4, "zero_sharding": "off"},
         "zero": {"mesh": 4, "zero_sharding": "on"}}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    tracer.uninstall()
    metrics.reset_default_registry()
    yield
    tracer.uninstall()
    metrics.reset_default_registry()


def build_step(mode: str):
    # the layer modules register their types on import
    import veles_tpu.znicz.conv  # noqa: F401
    import veles_tpu.znicz.dropout  # noqa: F401
    import veles_tpu.znicz.normalization  # noqa: F401
    import veles_tpu.znicz.pooling  # noqa: F401
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.parallel.mesh import make_mesh
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    prng.seed_all(13)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(12, 12, 3), n_validation=16, n_train=64,
        minibatch_size=16, shuffle_train=False)
    wf = StandardWorkflow(layers=LAYERS, loader=loader, loss="softmax",
                          n_classes=4)
    wf.initialize(device=None)
    kw = dict(MODES[mode])
    n = kw.pop("mesh", None)
    if n:
        kw["mesh"] = make_mesh(jax.devices()[:n])
    return wf.build_fused_step(compute_dtype="bfloat16", **kw)


def step_texts(mode: str):
    step = build_step(mode)
    step._build()
    low = step._train_fn.lower(
        step.init_state(), np.zeros((16, 12, 12, 3), np.float32),
        np.zeros(16, np.int32), np.ones(16, np.float32))
    return step, low.as_text(debug_info=True), low.compile().as_text()


def scope_set(text: str):
    """The unit scopes (forward, backward twin, under `update`) and the
    exchange's among the quoted paths of a lowered or compiled text."""
    found = set()
    for path in re.findall(r'"([^"]*)"', text):
        for part in path.split("/"):
            if re.search(r"L\d\d\.|^update$|grad_exchange|param_gather",
                         part):
                found.add(part)
    return found


@pytest.fixture(scope="module")
def texts():
    return {mode: step_texts(mode) for mode in MODES}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_every_unit_has_its_scope_its_backward_twin_and_update(texts, mode):
    step, lowered, compiled = texts[mode]
    assert list(step.scopes) == UNITS
    for text in (lowered, compiled):
        for unit in UNITS:
            assert f"jvp({unit})" in text, unit
        # dropout's mask and the pooling's argmax have a backward too;
        # every unit with parameters certainly has
        for unit in TRAINED + ["L01.norm", "L02.max_pooling"]:
            assert f"transpose(jvp({unit}))" in text, unit
        for unit in TRAINED:
            assert f"update/{unit}" in text, unit
        assert "jvp(loss)" in text and "jvp(cast_params)" in text
    # on a mesh the update sums the gradients itself, sharded or not
    assert ("grad_exchange" in compiled) == (mode != "local")
    assert ("param_gather" in compiled) == (mode == "zero")


def test_the_names_do_not_depend_on_the_build_or_the_mode(texts):
    _step, lowered, compiled = step_texts("local")
    # a second build: the same names, lowered and compiled
    assert scope_set(lowered) == scope_set(texts["local"][1])
    assert scope_set(compiled) == scope_set(texts["local"][2])
    # as traced (a compile fuses operations and keeps one name of each
    # fusion), the mesh adds the exchange's own scopes and nothing else
    local = scope_set(lowered)
    assert local >= {"update"} | set(TRAINED) | {
        f"jvp({u})" for u in UNITS} | {
        f"transpose(jvp({u}))" for u in TRAINED}
    assert scope_set(texts["dp"][1]) == local | {"grad_exchange"}
    assert scope_set(texts["zero"][1]) == local | {"grad_exchange",
                                                   "param_gather"}


def test_a_searched_fused_pair_is_one_scope_naming_both():
    from veles_tpu.ops import variants
    step = build_step("local")
    variants.select("lrn_maxpool", "fused[rt=1,io=native,fuse=1]")
    try:
        with variants.pallas_interpret():
            assert [(i, j) for i, j, _v in step.fusion_pairs()] == [(1, 2)]
            jaxpr = jax.make_jaxpr(
                lambda p, x: step._forward(p, x, jax.random.PRNGKey(0),
                                           False))(
                step.init_state()["params"],
                np.zeros((16, 12, 12, 3), np.float32))
    finally:
        variants.clear_selection("lrn_maxpool")
    stacks = {str(e.source_info.name_stack) for e in jaxpr.jaxpr.eqns}
    assert "L01.norm+L02.max_pooling" in stacks
    assert "L01.norm" not in stacks and "L02.max_pooling" not in stacks


def kernel_names(fn, *args):
    """The `name` of every pallas_call in the traced function."""
    out = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                out.append(e.params["name"])
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


X4 = np.ones((2, 7, 7, 16), np.float32)
XV = np.ones((8, 2, 2, 128), np.float32)
QKV = np.ones((2, 32, 2, 8), np.float32)
PALLAS_SITES = {
    "sgd_update": (lambda p: pk.sgd_update_pallas(p, p, p, 0.1),
                   np.ones((33, 17), np.float32), ["veles_sgd_update"]),
    # the kernels themselves take only a shape with a lane-dense view
    # (here channels in lanes)
    "lrn_fwd": (lambda x: pk.lrn_forward_pallas(x), XV, ["veles_lrn_fwd"]),
    "lrn_bwd": (lambda x: pk.lrn_backward_pallas(x, x), XV,
                ["veles_lrn_bwd"]),
    # lrn_pallas traces the kernels where the shape has a lane-dense
    # view (here batch in lanes) and the XLA closed form elsewhere
    "lrn_custom_vjp": (jax.grad(lambda x: pk.lrn_pallas(x).sum()),
                       np.ones((128, 2, 2, 16), np.float32),
                       ["veles_lrn_fwd", "veles_lrn_bwd"]),
    "lrn_no_view": (jax.grad(lambda x: pk.lrn_pallas(x).sum()), X4, []),
    "lrn_maxpool": (jax.grad(lambda x: pk.lrn_maxpool_pallas(x).sum()), X4,
                    ["veles_lrn_maxpool_fwd", "veles_lrn_maxpool_bwd"]),
    "flash": (jax.grad(lambda q: pk.flash_attention_pallas(
        q, q, q, blk_q=16, blk_k=16).sum()), QKV,
        ["veles_flash_fwd", "veles_flash_dq", "veles_flash_dkv"]),
    # one hyper-connection of 2 streams of 128 around the identity: the
    # pre and the post side forward, the post side's and the pre side's
    # backward, each through its module-level jit
    "hc": (jax.grad(lambda x: _connection(x).sum()),
           np.ones((128, 256), np.float32),
           ["veles_hc_pre_fwd", "veles_hc_post_fwd", "veles_hc_post_bwd",
            "veles_hc_pre_bwd"]),
    # two groups' products over 64 sorted rows and their gradients: the
    # product, the same kernel the other way round, the transposed one
    "gmm": (jax.grad(lambda x: _grouped(x).sum()),
            np.ones((64, 128), np.float32),
            ["veles_gmm", "veles_gmm", "veles_tgmm"]),
    # the indexer's scores of 128 queries against 128 keys under 2 heads
    # of 8 and their gradient by the queries' side
    "dsa_index": (jax.grad(lambda w: pk.index_scores_pallas(
        np.ones((128, 2, 8), np.float32), w, np.ones((128, 8), np.float32),
        0).sum()), np.ones((128, 2), np.float32),
        ["veles_dsa_index_fwd", "veles_dsa_index_bwd"]),
    # the chunked Gated DeltaNet over two chunks of 64 and two heads of
    # 128 (ISSUE 42): the operand stage forward and, by the values'
    # gradient, backward
    "gdn_chunk": (jax.grad(lambda v: _delta_chunks(v).sum()),
                  np.ones((1, 128, 2, 128), np.float32),
                  ["veles_gdn_chunk_fwd", "veles_gdn_chunk_bwd"]),
    # the held experts' combine over a buffer of 128 rows for 64 tokens
    # (ISSUE 43): the sum, and the transpose of the rows' gather
    "seg_sum": (jax.grad(lambda h: _combined(h).sum()),
                np.ones((64, 128), np.float32),
                ["veles_seg_sum", "veles_seg_sum"]),
}


def _combined(h):
    from veles_tpu.ops import moe as om
    pairs = np.arange(128, dtype=np.int32)      # two slots a token, all held
    seg = pk.seg_sum_view(128, 64, 128, 4)
    plan = pk.seg_sum_plan(pairs, 100, 2, 64, seg)
    rows = om._take_rows(h, pairs // 2, plan, 100, seg)
    return om._sum_rows(rows, pairs // 2, plan, 100, seg)


def _delta_chunks(v):
    import jax.numpy as jnp

    from veles_tpu.ops import linear_attention as la
    v = v.astype(jnp.bfloat16)
    g = -jnp.ones(v.shape[:3], jnp.float32)
    return la.gated_delta_chunked(0.1 * v, 0.1 * v, v, g, -0.5 * g,
                                  kernels=True)[0]


def _grouped(x):
    w = x[:2, :, None] * np.ones((1, 1, 128), np.float32)
    items = pk.gmm_items(np.asarray([40, 9], np.int32), 64, 64)
    return pk.grouped_matmul(x, w, *items, True)


def _connection(x, n=2):
    from veles_tpu.ops import lm as ol
    c = x.shape[1] // n
    p = {"p_pre": np.ones((n * c, n), np.float32),
         "p_post": np.ones((n * c, n), np.float32),
         "p_res": np.ones((n * c, n * n), np.float32),
         **{k: np.ones((1,), np.float32)
            for k in ("a_pre", "a_post", "a_res")},
         **ol.hc_init_biases(n)}
    return ol.hyper_connection(
        ol.hc_pre_pallas, ol.hc_post_pallas, p, x, lambda h: (h, None), n,
        iters=2, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)[0]


@pytest.mark.parametrize("site", sorted(PALLAS_SITES))
def test_every_pallas_call_has_its_fixed_name(site):
    fn, arg, want = PALLAS_SITES[site]
    pk._FORCE_INTERPRET = True
    try:
        assert kernel_names(fn, arg) == want
    finally:
        pk._FORCE_INTERPRET = False
    assert set(want) <= set(pk.KERNEL_NAMES.values())


def test_all_twenty_three_kernels_are_named_and_no_name_twice():
    names = list(pk.KERNEL_NAMES.values())
    assert len(names) == 23 == len(set(names))
    with open(pk.__file__) as f:
        src = f.read()
    assert src.count("pl.pallas_call(") == src.count("name=KERNEL_NAMES[")


def test_the_combines_kernel_stands_under_the_expert_layers_scope():
    """`veles_seg_sum` (ISSUE 43) is called under `moe/experts`, forward
    and backward, in a block whose step allows Pallas kernels whatever
    forms its grouped products: `step_moe_ms`, `moe_experts_mxu_share` and
    `step_unscoped_share` keep reading it. With `allow_pallas = False` the
    block traces no kernel."""
    import jax.numpy as jnp

    from veles_tpu.ops import variants
    from veles_tpu.znicz.lm import BlockSpec
    assert "veles_seg_sum" in pk.KERNEL_NAMES.values()
    spec = BlockSpec(features=128, n_heads=2, ffn="experts", width=128,
                     n_experts=8, held=(0, 4), top_k=2, residual="plain",
                     scoring="softmax", shared=False, attention="gated",
                     kv_heads=1, head_dim=64, rotary_dim=16)
    assert spec.grouped == "ragged_dot"
    p = {k: jnp.full(v, 0.01, jnp.float32) for k, v in spec.shapes().items()}
    x = jnp.ones((1, 128, 128), jnp.float32)

    def sites():
        txt = jax.jit(jax.value_and_grad(
            lambda p: spec.apply(p, x)[0].sum())).lower(p).as_text(
                debug_info=True)
        return set(re.findall(r'loc\("([^"]*jit\(seg_sum_pallas\))"', txt))
    with variants.pallas_interpret():
        found = sites()
        spec.allow_pallas = False
        assert not sites()
    # the forward's `jvp(moe)/experts`, the backward's `transpose(jvp(moe))`
    assert len(found) == 2 and all("/moe/experts/" in re.sub(
        r"transpose\(|jvp\(|\)", "", s) for s in found), found


# -- spans on the profiler's clock ------------------------------------------------


def host_spans(trace_dir: str, name: str):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, dict(e.stats), e.duration_ns)
                        for e in line.events if e.name == name]
    return out


def test_a_span_under_an_open_session_is_in_its_host_plane(tmp_path):
    """Nobody installed the ring: the profiler session alone holds the
    span, with the batch number as the event's `seq` stat."""
    assert tracer.active() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in (7, 8):
            with tracer.span("train.dispatch", "step", k):
                time.sleep(0.002)
        with tracer.span("decision", "bookkeeping"):
            pass
    finally:
        jax.profiler.stop_trace()
    spans = host_spans(str(tmp_path), "train.dispatch")
    assert sorted(int(s["seq"]) for _n, s, _d in spans) == [7, 8]
    assert all(d >= 2e6 for _n, _s, d in spans)
    assert len(host_spans(str(tmp_path), "decision")) == 1
    # the session is closed: the next span is the shared no-op again
    assert tracer.span("train.dispatch", "step", 9) is tracer._OFF


def test_a_span_with_the_ring_and_a_session_is_in_both(tmp_path):
    ring = tracer.install()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("feed.device_put", "feed", 3):
            pass
    finally:
        jax.profiler.stop_trace()
    (name, cat, _ts, _dur, _tid, ph, seq), = ring.events()
    assert (name, cat, ph, seq) == ("feed.device_put", "feed", "X", 3)
    assert [int(s["seq"]) for _n, s, _d in
            host_spans(str(tmp_path), "feed.device_put")] == [3]
    doc = ring.trace_events()
    assert doc[0]["args"] == {"seq": 3}


def test_a_span_with_nothing_open_is_a_shared_noop_and_raises_nothing():
    assert tracer.span("a") is tracer.span("b", "c", 5) is tracer._OFF
    n = 20000
    t0 = time.perf_counter()
    for k in range(n):
        with tracer.span("train.dispatch", "step", k):
            pass
    per_span = (time.perf_counter() - t0) / n
    # measured 0.36 us here; the bound only has to catch an object, a
    # lock or a clock read creeping into the off path on a loaded host
    assert per_span < 5e-6, per_span
    with pytest.raises(ZeroDivisionError):
        with tracer.span("x"):
            1 / 0


def test_the_ring_export_states_its_epoch_in_unix_ns(tmp_path):
    """ONE epoch a process (PR 37), read when the tracer is imported:
    every ring's events, the set-up ring's too, lie on one timeline."""
    import json
    before = time.time_ns()
    ring = tracer.Tracer(256)
    with ring.span("s", "c", 1):
        pass
    other = json.load(open(ring.export(str(tmp_path / "t.json"))))[
        "otherData"]
    assert other["epoch_unix_ns"] <= before
    assert other["epoch_unix_ns"] == tracer.setup_ring()._epoch_unix_ns \
        == tracer.Tracer(256)._epoch_unix_ns
    assert other["epoch_unix"] == pytest.approx(
        other["epoch_unix_ns"] / 1e9)


def test_train_records_its_own_dispatch_span_with_the_steps_number():
    ring = tracer.install()
    step = build_step("local")
    state = step.init_state()
    x = np.zeros((16, 12, 12, 3), np.float32)
    y = np.zeros(16, np.int32)
    for _ in range(3):
        state, _ = step.train(state, x, y)
    spans = [e for e in ring.events() if e[0] == "train.dispatch"]
    assert [e[6] for e in spans] == [0, 1, 2] == list(
        range(step.n_dispatched))


# -- counters where the feed's work happens --------------------------------------


class RowsLoader(PrefetchingLoader):
    """120 train rows of 4 floats; `_produce_batch` sleeps `delay`."""

    def __init__(self, delay=0.0, **kw):
        super().__init__(None, minibatch_size=10, shuffle_train=False,
                         on_device=False, name="rows", **kw)
        self.delay = delay

    def load_data(self):
        self.class_lengths = [0, 0, 120]

    def _produce_batch(self, indices):
        time.sleep(self.delay)
        return (np.repeat(indices[:, None], 4, 1).astype(np.float32),
                indices.astype(np.int64))


def flat():
    return metrics.default_registry().snapshot_flat()


@pytest.mark.parametrize("delay,moves", [(0.0, "ready"), (0.1, "waited")])
def test_the_lookahead_counters_add_up(delay, moves):
    """Every fill asks once and consumes one produced batch; the epoch's
    last fill submits one more, for the next epoch's first batch. A fast
    producer is ready when asked; a slow one, asked at once, is waited
    for."""
    ld = RowsLoader(delay=delay, n_workers=1, prefetch=1)
    ld.initialize(device=None)
    try:
        for k in range(12):                 # one whole epoch
            ld.run()
            assert ld.batch_seq == k
            assert ld.minibatch_data.mem[0, 0] == 10 * k
            while not delay and not all(
                    f.done() for f in list(ld._pending.values())):
                time.sleep(0.001)           # the worker finishes meanwhile
    finally:
        ld.stop()
    asked = ld.lookahead_ready + ld.lookahead_waited
    assert asked == 13                      # 12 runs + the shape probe
    assert ld.lookahead_cross_epoch == 1
    # (stop() cancels the next epoch's batch unless it was being made)
    assert asked <= ld.batches_produced <= asked + 1
    other = "waited" if moves == "ready" else "ready"
    assert getattr(ld, "lookahead_" + moves) >= 10
    assert getattr(ld, "lookahead_" + other) <= 3
    assert ld.produce_s >= 13 * delay
    f = flat()
    assert f["veles_loader_batches_produced_total"] == ld.batches_produced
    assert f["veles_loader_lookahead_cross_epoch_total"] == 1
    assert f["veles_loader_lookahead_ready_total"] == ld.lookahead_ready
    assert f["veles_loader_lookahead_waited_total"] == ld.lookahead_waited
    assert f["veles_loader_produce_seconds_total"] == pytest.approx(
        ld.produce_s)


def test_the_batch_number_runs_on_over_the_epochs_and_the_pickle():
    import pickle
    ld = RowsLoader(n_workers=1, prefetch=2)
    ld.initialize(device=None)
    try:
        for k in range(30):
            assert ld.next_batch_seq == k
            ld.run()
        assert ld.batch_seq == 29 and ld.epoch_number == 2
        blob = pickle.dumps(ld)
    finally:
        ld.stop()
    back = pickle.loads(blob)
    assert back.next_batch_seq == 30
    # the counters and the handles are process-local: never pickled
    assert back.batches_produced == 0 and back.produce_s == 0.0
    assert back._m is None
    assert b"produce_s" not in blob and b"lookahead_" not in blob


def test_the_feed_writes_its_own_counters_and_numbers_its_spans():
    ring = tracer.install()
    ld = RowsLoader(n_workers=2, prefetch=2)
    ld.initialize(device=None)
    feed = DeviceFeed(ld, put=lambda xs: tuple(jax.device_put(a)
                                               for a in xs), ahead=1)
    try:
        seqs = []
        for _ in range(8):
            b = feed.next()
            jax.block_until_ready(b.x)
            seqs.append(b.seq)
            feed.prefetch()
        st = feed.stats()
    finally:
        feed.stop()
    assert seqs == list(range(8))
    assert st["batches"] == 9 and st["on_demand"] == 1
    assert st["h2d_ready"] + st["h2d_late"] == 8
    # (a lookahead batch may still be in the making when stats() reads)
    assert 9 <= st["batches_produced"] <= ld.batches_produced <= 12
    assert st["lookahead_ready"] + st["lookahead_waited"] == 10
    f = flat()
    assert f["veles_feed_batches_total"] == 9
    assert f["veles_feed_h2d_bytes_total"] == st["bytes_h2d"]
    assert f["veles_feed_on_demand_total"] == 1
    assert f["veles_feed_h2d_ready_total"] == st["h2d_ready"]
    assert f["veles_feed_h2d_late_total"] == st["h2d_late"]
    assert f["veles_feed_put_seconds_total"] == pytest.approx(
        st["put_block_s"], abs=1e-5)
    assert f["veles_feed_loader_block_seconds_total"] == pytest.approx(
        st["loader_block_s"], abs=1e-5)
    # one chain per batch: loader.produce#k on a produce thread (or the
    # loop's, for the first), feed.produce#k > loader.run#k, device_put#k
    by_name = {}
    for name, _c, ts, dur, tid, _ph, seq in ring.events():
        by_name.setdefault(name, {})[seq] = (ts, ts + dur, tid)
    for k in range(9):
        lo, hi, tid = by_name["feed.produce"][k]
        for child in ("loader.run", "feed.device_put"):
            clo, chi, ctid = by_name[child][k]
            assert ctid == tid and lo <= clo and chi <= hi + 1e-3
        assert by_name["loader.produce"][k][1] <= by_name[
            "loader.run"][k][1] + 1e-3
    put_tids = {v[2] for v in by_name["feed.device_put"].values()}
    produce_tids = {v[2] for v in by_name["loader.produce"].values()}
    assert produce_tids - put_tids, "no batch came from a produce thread"


def test_a_host_handoff_feed_counts_no_transfers():
    ld = RowsLoader(n_workers=1, prefetch=1)
    ld.initialize(device=None)
    feed = DeviceFeed(ld, put=None)
    try:
        assert isinstance(feed.next().x, np.ndarray)
        st = feed.stats()
    finally:
        feed.stop()
    assert st["h2d_ready"] == st["h2d_late"] == 0
