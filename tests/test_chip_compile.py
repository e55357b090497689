"""What the TPU v5e COMPILER says, asked without a chip (ISSUE 21).

Interpret mode hid the compiler for nineteen PRs: kernels that passed
every interpret-mode test were refused on the chip for scoped-VMEM
overflow and an unsupported gather. The TPU compiler is installed here
and compiles for a chip that is DESCRIBED, not attached — so the main
path's kernels at AlexNet's real widths, the generated points the search
would try, and the whole fused train step (one chip, and the dp step
over the 2x2 mesh, with the replicated update and with ZeRO) are
compiled here — and the Pallas LRN is held to taking the activation in
the layout the convs emit, with no relayout beside it (ISSUE 27) —, at no chip time, on every tier-1 run. A compile that
passes is not a chip run; `chip_smoke.py` is.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described ONLY inside the module-scoped fixture below —
never at import, in a skipif/parametrize argument or in conftest.py —
because one process at a time may load libtpu and xdist workers import
every test file; everything built from it (shardings, meshes, shapes)
is built in fixtures or tests; compiles happen in the test's own
process; the persistent compilation cache is off around them; and all of
it lives in this ONE file so one worker owns the library.

What that one worker pays for on every run (ISSUE 39): tier-1 holds the
kernels compiled at published widths (seconds each), AlexNet's whole
steps, and a language-model cell's step traced and lowered; the
whole-step compile at a cell's size is `slow`, run by name
(`pytest -m slow tests/test_chip_compile.py -k <config>`) before a
change's first chip call, and guarded on every PR by the cell itself,
which the driver compiles and runs on the chip (`hbm_peak_gb`, the
kernels' rooflines; a step that does not fit fails the cell). A
configuration costs this file its trace, 15-25 s, not the 200 of its
compile: its tier-1 test asks of `tools/trace_cost.py`'s `measure` what
is known before `.compile()`, and a twin marked `slow` beside it, on the
same lowered step, asks the compiled program the rest.
"""

import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from veles_tpu.analysis import resources as res
from veles_tpu.ops import pallas_kernels as pk
from veles_tpu.ops import variants

#: the smoke's per-chip batch (chip_smoke.py) — the LRN sites are
#: (B*55*55, 96) and (B*27*27, 256) rows x channels
BATCH = 1024
LRN_SITES = ((55, 55, 96), (27, 27, 256))
V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip, say why
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one (it warns and recompiles)."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_pallas(monkeypatch):
    """Steer the kernels to their COMPILED form although the default
    backend here is the CPU (the program itself never interprets unless
    asked — this only answers its `available()` question the way the
    described chip would)."""
    monkeypatch.setattr(pk, "available", lambda: True)
    assert not pk._interpret()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    """Compile for the described chip; returns the compiled text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _refusal(fn, *args) -> str:
    with pytest.raises(Exception) as ei:  # noqa: PT011 — XLA's own type
        _compile(fn, *args)
    return str(ei.value)


# -- the kernels of the main path, forward AND backward ----------------------

@pytest.mark.parametrize("site", LRN_SITES, ids=lambda s: "x".join(map(str, s)))
def test_lrn_pallas_fwd_bwd_compiles_at_alexnet_sites(one_chip, site,
                                                      compiled_pallas):
    x = _sds(one_chip, (BATCH,) + site, jnp.bfloat16)

    def fwd_bwd(a):
        return jax.grad(
            lambda v: pk.lrn_pallas(v).astype(jnp.float32).sum())(a)

    assert "tpu_custom_call" in _compile(pk.lrn_pallas, x)
    assert "tpu_custom_call" in _compile(fwd_bwd, x)


@pytest.mark.parametrize("row_tile", (8, 1024))
def test_sgd_update_pallas_compiles(one_chip, row_tile, compiled_pallas):
    p = _sds(one_chip, (4096, 4096), jnp.float32)
    txt = _compile(lambda a, b, c: pk.sgd_update_pallas(
        a, b, c, 0.01, 0.9, 5e-4, row_tile=row_tile), p, p, p)
    assert "tpu_custom_call" in txt


def test_flash_attention_pallas_fwd_bwd_compiles(one_chip, compiled_pallas):
    q = _sds(one_chip, (1, 4096, 8, 64), jnp.bfloat16)

    def fwd(a, b, c):
        return pk.flash_attention_pallas(a, b, c, causal=True)

    def fwd_bwd(a, b, c):
        return jax.grad(lambda *t: fwd(*t).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(a, b, c)

    assert "tpu_custom_call" in _compile(fwd, q, q, q)
    assert "tpu_custom_call" in _compile(fwd_bwd, q, q, q)


def test_dsa_kernels_compile_at_published_widths(one_chip, compiled_pallas):
    """The main attention of `keye2_ep8.long16k` (ISSUE 35): 32 query heads
    on 4 key-value heads of 128, one sequence of 16,384 tokens, bfloat16,
    the selection as int8: the four `veles_dsa_*` kernels, each under its
    fixed name. And the indexer's two (ISSUE 36): 16 index heads of 64, a
    block of 256 queries (what a `lax.map` body hands them, its place an
    int32 they read from SMEM) and a whole band of 4,096 against all
    16,384 keys, a block against the first band's 4,096, the keys' whole
    gradient resident in the backward, under the VMEM the kernels ask
    for."""
    h, hkv, t, d = 32, 4, 16384, 128
    assert pk.dsa_view(t, d) and not pk.dsa_view(32, 16)
    hi, di = 16, 64
    assert pk.dsa_index_view(t, hi, di)
    assert pk._DSA_INDEX_VMEM_LIMIT <= 100 << 20      # of a v5e's 128 MiB
    q0 = _sds(one_chip, (), jnp.int32)
    for block, keys in ((256, t), (4096, t), (256, 4096)):
        qi = _sds(one_chip, (hi, block, di), jnp.bfloat16)
        w = _sds(one_chip, (block, hi), jnp.float32)
        ki = _sds(one_chip, (keys, di), jnp.bfloat16)
        ct = _sds(one_chip, (block, keys), jnp.float32)
        txt = _compile(pk.dsa_index_forward_pallas, qi, w, ki, q0)
        assert "tpu_custom_call" in txt and "veles_dsa_index_fwd" in txt
        assert f"f32[{block},{keys}]" in txt
        txt = _compile(pk.dsa_index_backward_pallas, qi, w, ki, q0, ct)
        assert "tpu_custom_call" in txt and "veles_dsa_index_bwd" in txt
        for shape in (f"bf16[{hi},{block},{di}]", f"f32[{block},{hi}]",
                      f"f32[{keys},{di}]"):
            assert shape in txt, shape
    q = _sds(one_chip, (h, t, d), jnp.bfloat16)
    kv = _sds(one_chip, (hkv, t, d), jnp.bfloat16)
    mask = _sds(one_chip, (t, t), jnp.int8)
    row = _sds(one_chip, (h, t, 1), jnp.float32)
    scale = d ** -0.5
    for fn, args, names in (
            (lambda *a: pk.dsa_attend_forward_pallas(*a, scale=scale),
             (q, kv, kv, mask), ("veles_dsa_attend_fwd", "f32[32,1,16384]")),
            (lambda *a: pk.dsa_pmean_pallas(*a, scale=scale),
             (q, kv, row, mask), ("veles_dsa_pmean",)),
            (lambda *a: pk.dsa_attend_backward_pallas(*a, scale=scale),
             (q, kv, kv, q, row, row, mask),
             ("veles_dsa_attend_dq", "veles_dsa_attend_dkv"))):
        txt = _compile(fn, *args)
        assert "tpu_custom_call" in txt
        for name in names:
            assert name in txt, name


def test_grouped_product_kernels_compile_at_published_widths(
        one_chip, compiled_pallas):
    """The held experts' products of `keye2_ep8.long16k` (ISSUE 35): 16
    matrices of 2048 x 768 and of 768 x 2048 against the sorted buffer's
    49,152 rows (and the 131,072 of the whole one), bfloat16: `veles_gmm`
    either way round and `veles_tgmm`, each under its fixed name; the
    widths of `xing4_ep8`'s experts are within their view too."""
    from veles_tpu.ops import moe as om
    assert pk.gmm_view(6144, 3584, 1024, 2) == 512
    for rows, a, b in ((49152, 2048, 768), (49152, 768, 2048),
                       (131072, 2048, 768)):
        tile = pk.gmm_view(rows, a, b, 2)
        assert tile == 512

        def fwd_bwd(x, w, sizes, dy):
            dot = om._grouped_product(sizes, rows, x, w, "pallas", True)
            y, vjp = jax.vjp(dot, x, w)
            return y, vjp(dy)
        txt = _compile(fwd_bwd, _sds(one_chip, (rows, a), jnp.bfloat16),
                       _sds(one_chip, (16, a, b), jnp.bfloat16),
                       _sds(one_chip, (16,), jnp.int32),
                       _sds(one_chip, (rows, b), jnp.bfloat16))
        assert "tpu_custom_call" in txt and "ragged-dot" not in txt
        assert txt.count("veles_gmm") >= 2 and "veles_tgmm" in txt


def test_seg_sum_kernel_compiles_at_the_cells_shapes(one_chip,
                                                     compiled_pallas):
    """The held experts' combine (ISSUE 43) at the three language-model
    cells' buffers, fast rows and whole ones, bfloat16 (and float32 once:
    its one-hot product runs at `HIGHEST`): the rows' permutation and
    `veles_seg_sum` under its fixed name, forward and as the transpose of
    the rows' gather, with no gather of a row a (token, slot) pair left."""
    from veles_tpu.ops import moe as om
    for rows, tokens, k, c, dtype in (
            (61440, 32768, 10, 2048, jnp.bfloat16),
            (49152, 16384, 8, 2048, jnp.bfloat16),
            (131072, 16384, 8, 2048, jnp.bfloat16),
            (6144, 8192, 4, 3584, jnp.bfloat16),
            (32768, 8192, 4, 3584, jnp.bfloat16),
            (6144, 8192, 4, 3584, jnp.float32)):
        tile = pk.seg_sum_view(rows, tokens, c, jnp.dtype(dtype).itemsize)
        assert tile == 256
        plan = jax.tree.map(
            lambda s: _sds(one_chip, s.shape, s.dtype), jax.eval_shape(
                lambda p: pk.seg_sum_plan(p, 7, k, tokens, tile),
                jax.ShapeDtypeStruct((rows,), jnp.int32)))

        def both(y, h, *plan):
            token_of = plan[0] // k
            return (om._sum_rows(y, token_of, plan, 7, tile),
                    jax.grad(lambda h: om._take_rows(
                        h, token_of, plan, 7, tile).astype(
                            jnp.float32).sum())(h))
        txt = _compile(both, _sds(one_chip, (rows, c), dtype),
                       _sds(one_chip, (tokens, c), dtype), *plan)
        assert txt.count("veles_seg_sum") >= 2 and "tpu_custom_call" in txt
        assert f"[{tokens},{k}," not in txt, (rows, tokens)


def test_gdn_chunk_kernels_compile_at_the_cells_shapes(one_chip,
                                                       compiled_pallas):
    """One call of `qwen3next_ep16.seq8k` (ISSUE 42): 8,192 chunk-heads
    (2 sequences x 128 chunks x 32 value heads) of 64 tokens, keys and
    values of 128, bfloat16 under float32 decays. The stage forward, and
    forward + backward through its `custom_vjp`, hold the two kernels
    under their fixed names, the backward under `gdn/scan` again, inside
    the scoped VMEM they ask for (32 chunk-heads a grid step: the
    backward's eleven blocks a chunk-head, double-buffered)."""
    from veles_tpu.ops import linear_attention as la
    n, b, c, d = 128, 64, 64, 128
    g = pk.gdn_view(n * b, c, d, d, jnp.float32, jnp.bfloat16)
    assert g == 32
    assert 2 * g * 2 * c * 11 * d <= pk._GDN_BLOCK_BUDGET < pk._GDN_VMEM_LIMIT
    assert la._kernels_take(True, n * b, c, d, d, jnp.float32, jnp.bfloat16)
    assert not la._kernels_take(False, n * b, c, d, d, jnp.float32,
                                jnp.bfloat16)
    mat = _sds(one_chip, (n, b, c, d), jnp.bfloat16)
    row = _sds(one_chip, (n, b, c), jnp.float32)

    def fwd(q, k, v, gam, beta):
        return la._operands_kernels(q, k, v, gam, beta)[:6]

    def fwd_bwd(q, k, v, gam, beta):
        out, vjp = jax.vjp(fwd, q, k, v, gam, beta)
        return vjp(out)                     # the cotangent: the results

    txt = _compile(fwd, mat, mat, mat, row, row)
    assert txt.count("tpu_custom_call") == 1 and "veles_gdn_chunk_fwd" in txt
    txt = _compile(fwd_bwd, mat, mat, mat, row, row)
    for name in ("veles_gdn_chunk_fwd", "veles_gdn_chunk_bwd"):
        assert name in txt, name
    assert re.search(
        r'op_name="[^"]*gdn\)*/scan/jit\(gdn_chunk_backward_pallas\)', txt)
    # a shape the view refuses is the caller's fault
    with pytest.raises(ValueError, match="gdn_view"):
        pk.gdn_chunk_forward_pallas(
            *(jnp.zeros((4, 32, d), jnp.bfloat16),) * 3,
            *(jnp.zeros((4, 32), jnp.float32),) * 2,
            inverse_block=la.INVERSE_BLOCK)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_hyper_connection_kernels_compile_at_published_widths(
        one_chip, dtype, compiled_pallas):
    """One connection of `xing4_ep8`, 8,192 tokens x 4 streams of 3,584
    (ISSUE 34): forward, and forward + backward by autodiff through both
    `custom_vjp`s, hold the four kernels under their scopes; the float32
    streams of a float32 run fit the same 64 MB of VMEM (`hc_view` is a
    rule of the shape alone)."""
    n, c, tokens = 4, 3584, 8192
    assert pk.hc_view(tokens, c, n) * 2 * (3 * n + 2) * c * 4 \
        <= pk._HC_BLOCK_BUDGET < pk._HC_VMEM_LIMIT
    dt = jnp.dtype(dtype)
    p = {"p_pre": (n * c, n), "p_post": (n * c, n), "p_res": (n * c, n * n),
         "a_pre": (1,), "a_post": (1,), "a_res": (1,), "b_pre": (n,),
         "b_post": (n,), "b_res": (n, n)}
    p = {k: _sds(one_chip, s, dt) for k, s in p.items()}
    x = _sds(one_chip, (tokens, n * c), dt)
    apply = variants.get("hc", "pallas_one_pass").apply

    def fwd(pp, xx):
        return apply(pp, xx, lambda h: (h, None), n, iters=20, eps=1e-6,
                     clamp=(-30.0, 30.0), norm_eps=1e-6)[0]

    def fwd_bwd(pp, xx):
        return jax.vjp(fwd, pp, xx)[1](xx)     # the cotangent: x itself

    txt = _compile(fwd, p, x)
    assert txt.count("tpu_custom_call") >= 2
    txt += _compile(fwd_bwd, p, x)
    for name in ("veles_hc_pre_fwd", "veles_hc_post_fwd",
                 "veles_hc_post_bwd", "veles_hc_pre_bwd"):
        assert name in txt, name
    for scope in ("/hc_pre/", "/hc_post/"):
        assert scope in txt, scope


@pytest.mark.parametrize("site", LRN_SITES, ids=lambda s: "x".join(map(str, s)))
def test_lrn_maxpool_pallas_fwd_bwd_compiles(one_chip, site,
                                             compiled_pallas):
    """PR 13's fused template survived ISSUE 21 by routing its window
    taps through strided REF loads/stores (the value-slice form was
    refused: "Only 2D gather is supported"), one sample per block."""
    x = _sds(one_chip, (128,) + site, jnp.bfloat16)
    txt = _compile(lambda a: jax.grad(
        lambda v: pk.lrn_maxpool_pallas(v).astype(jnp.float32).sum())(a),
        x)
    assert "tpu_custom_call" in txt


# -- the ledger and the compiler agree ----------------------------------------

def _lrn_bwd_through(view):
    return lambda a, g: pk._lrn_view_call(
        pk._lrn_bwd_kernel, (a, g), view, 2.0, 1e-4, 0.75, 5)


@pytest.mark.parametrize("batch", (BATCH, BATCH // 4))
@pytest.mark.parametrize("site", LRN_SITES, ids=lambda s: "x".join(map(str, s)))
def test_lrn_view_block_matches_the_compiler(one_chip, site, batch,
                                             compiled_pallas):
    """The block `lrn_view` picks is priced by `lrn_view_vmem_bytes`
    under half the limit the kernels compile under, and Mosaic admits
    its backward (the worst direction) at a chip's batch alone and at
    its quarter of the 2x2 mesh's."""
    x = _sds(one_chip, (batch,) + site, jnp.bfloat16)
    view = pk.lrn_view(x.shape, 2)
    assert pk.lrn_view_vmem_bytes(view[2], 2) <= res.SCOPED_VMEM_LIMIT // 2
    assert "tpu_custom_call" in _compile(_lrn_bwd_through(view), x, x)


def test_lrn_view_rule_prices_what_the_compiler_refuses(one_chip,
                                                        compiled_pallas):
    """VMEM_BUDGETS holds the limit the kernels compile under, and the
    view rule prices what Mosaic allocates: a hand-made block the rule
    puts at twice that limit is refused for it."""
    x = _sds(one_chip, (BATCH,) + LRN_SITES[1], jnp.bfloat16)
    walk, vshape, _ = pk.lrn_view(x.shape, 2)
    block = (11664, 256)
    assert vshape[0] % block[0] == 0
    assert pk.lrn_view_vmem_bytes(block, 2) >= 2 * res.SCOPED_VMEM_LIMIT
    msg = _refusal(_lrn_bwd_through((walk, vshape, block)), x, x)
    assert "exceeded scoped vmem limit" in msg
    assert "limit 16.00M" in msg
    assert res.vmem_budget(V5E) == res.SCOPED_VMEM_LIMIT == 16 << 20


def test_lrn_maxpool_ledger_prunes_what_the_compiler_refuses(
        one_chip, compiled_pallas):
    site = LRN_SITES[0]
    shapes = {"h": site[0], "w": site[1], "c": site[2]}
    budget = res.vmem_budget(V5E)
    assert res.kernel_verdict("lrn_maxpool", "fused[rt=1,io=native,fuse=1]",
                              shapes=shapes, dtype="bfloat16",
                              budget=budget) is None
    assert res.kernel_verdict("lrn_maxpool", "fused[rt=2,io=native,fuse=1]",
                              shapes=shapes, dtype="bfloat16",
                              budget=budget) is not None
    x = _sds(one_chip, (128,) + site, jnp.bfloat16)
    msg = _refusal(lambda a: jax.grad(lambda v: pk.lrn_maxpool_pallas(
        v, 2.0, 1e-4, 0.75, 5, (3, 3), (2, 2), 2, "native")
        .astype(jnp.float32).sum())(a), x)
    assert "exceeded scoped vmem limit" in msg


# -- the whole fused train step from described state --------------------------

@pytest.fixture(scope="module")
def alexnet():
    """Full-geometry AlexNet (227x227x3, FC 4096, 1000 classes), host
    params only: nothing is put on a device."""
    from veles_tpu import prng
    from veles_tpu.samples.alexnet import create_workflow
    prng.seed_all(1234)
    wf = create_workflow(minibatch_size=8, n_train=8, n_validation=8)
    wf.initialize(device=None)
    return wf


def _abstract_step_args(step, batch, shardings, xsh):
    """(state, x, y, w) ShapeDtypeStructs for `step` from HOST shapes and
    the step's own sharding plan (the checkpoint restore target)."""
    from veles_tpu.parallel import checkpoint as ck
    tmpl = ck._abstract_state(step, "threefry2x32")
    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        tmpl, shardings(tmpl))
    key = jax.eval_shape(lambda: jax.random.key(0))
    state["key"] = jax.ShapeDtypeStruct(key.shape, key.dtype,
                                        sharding=state["key"].sharding)
    in_shape = tuple(step.forwards[0].input.shape[1:])
    return (state,
            jax.ShapeDtypeStruct((batch,) + in_shape, jnp.float32,
                                 sharding=xsh),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=xsh),
            jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=xsh))


@pytest.fixture(scope="module")
def local_step_programs(one_chip, alexnet):
    """lrn variant -> the compiled one-chip fused step chip_smoke.py
    trains (batch 1024, bf16), each compiled once for the module."""
    programs = {}

    def get(lrn):
        if lrn not in programs:
            variants.select("lrn", lrn)
            try:
                with pytest.MonkeyPatch.context() as mp:
                    mp.setattr(pk, "available", lambda: True)
                    step = alexnet.build_fused_step(compute_dtype="bfloat16")
                    assert step.variant_table()["lrn"] == lrn
                    args = _abstract_step_args(
                        step, BATCH,
                        lambda t: jax.tree_util.tree_map(lambda _: one_chip,
                                                         t),
                        one_chip)
                    programs[lrn] = jax.jit(
                        step.train_callable(),
                        donate_argnums=(0,)).lower(*args).compile()
            finally:
                variants.clear_selection("lrn")
        return programs[lrn]
    return get


@pytest.mark.parametrize("lrn", ("banded_matmul", "pallas_one_pass"))
def test_local_alexnet_train_step_compiles(local_step_programs, lrn):
    """With the XLA closed form and with the Pallas LRN (the default on a
    TPU since PR 27) — the kernel must be IN the program then, and the
    program must fit the chip's 16 GB."""
    compiled = local_step_programs(lrn)
    assert ("tpu_custom_call" in compiled.as_text()) \
        == (lrn == "pallas_one_pass")
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < 16 << 30, total


def _relayouts_of(txt, shapes):
    """The compiled program's `copy`, `pad`, `slice` and `transpose`
    instructions whose result has one of `shapes` (an LRN site's
    activation, logical or in a kernel's view)."""
    import re
    dims = "|".join(",".join(map(str, s)) for s in shapes)
    pat = re.compile(rf"= \w+\[({dims})\]\S* (copy|pad|slice|transpose)\(")
    return [ln.strip()[:160] for ln in txt.splitlines() if pat.search(ln)]


def test_pallas_lrn_takes_the_activation_where_it_lies(local_step_programs):
    """ISSUE 27's finding, kept: a kernel over the flattened NHWC array
    cost eight relayout copies of 595 / 382 MB, a pad and a slice. The
    kernels take the view whose row-major order is the layout the convs
    emit, so around all four of them NOTHING moves an activation of an
    LRN site, and the program needs no more memory than the XLA form's
    (it needs less: no `s` residual)."""
    compiled = local_step_programs("pallas_one_pass")
    txt = compiled.as_text()
    for name, view in (("veles_lrn_fwd", "bf16[3025,96,1024]"),
                       ("veles_lrn_bwd", "bf16[3025,96,1024]"),
                       ("veles_lrn_fwd", "bf16[746496,256]"),
                       ("veles_lrn_bwd", "bf16[746496,256]")):
        assert any(name in ln and "tpu_custom_call" in ln
                   and ln.split(" = ")[1].startswith(view)
                   for ln in txt.splitlines() if " = " in ln), (name, view)
    sites = [(BATCH,) + s for s in LRN_SITES]
    views = [(55 * 55, 96, BATCH), (27 * 27 * BATCH, 256),
             (55, 55, 96, BATCH), (27, 27, BATCH, 256)]
    assert _relayouts_of(txt, sites + views) == []
    # the reader itself finds such a line when there is one
    assert _relayouts_of(
        "%copy.61 = bf16[1024,55,55,96]{3,2,1,0:T(8,128)(2,1)} copy(%x)",
        sites) != []
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= local_step_programs("banded_matmul") \
        .memory_analysis().temp_size_in_bytes


def _compile_dp_step_for_2x2(topo, alexnet, **step_kw):
    """(step, lowered, compiled) of AlexNet's dp step over the described
    2x2 at global batch 1024, shard_map's varying-axes check ON."""
    from jax.sharding import PartitionSpec as P

    from veles_tpu.parallel import checkpoint as ck
    from veles_tpu.parallel.mesh import DATA_AXIS, make_mesh
    mesh = make_mesh(topo.devices)
    assert dict(mesh.shape)[DATA_AXIS] == 4
    step = alexnet.build_fused_step(mesh=mesh, compute_dtype="bfloat16",
                                    **step_kw)
    assert step.mode == "dp"
    args = _abstract_step_args(
        step, BATCH, lambda t: ck._target_shardings(step, t),
        NamedSharding(mesh, P(DATA_AXIS)))
    lowered = jax.jit(step.train_callable(),
                      donate_argnums=(0,)).lower(*args)
    return step, lowered, lowered.compile()


def _n_alexnet_params(alexnet):
    n_params = sum(int(np.prod(a.shape)) for u in alexnet.forwards
                   for a in u.param_arrays().values() if a)
    assert n_params == 62378344
    return n_params


def test_dp_zero_alexnet_train_step_compiles_for_2x2(topo, alexnet):
    """The dp step with the update sharded on request (ZeRO over four
    chips, global batch 1024) compiles for the described 2x2 mesh, asks
    for the reduce-scatter and the all-gather, and keeps a 1/4 optimizer
    slice per chip."""
    step, lowered, compiled = _compile_dp_step_for_2x2(
        topo, alexnet, zero_sharding="on")
    assert step.zero_active, step.zero_reason
    asked = lowered.as_text()
    # what the step ASKS for: one reduce-scatter (the grad_reduce
    # registry op) and one invariant all-gather per param leaf
    assert "reduce_scatter" in asked and "all_gather" in asked
    txt = compiled.as_text()
    # what the v5e compiler MAKES of it (PR 21: 11 all-gathers and 3
    # combined all-reduces on the 2x2 — it decomposes the reduce-scatters
    # into all-reduce + slice, so ZeRO saves no byte of the exchange here)
    assert "all-gather" in txt
    assert "reduce-scatter" in txt or "all-reduce" in txt
    mem = compiled.memory_analysis()
    # per-device arguments: replicated f32 params + a quarter of the
    # velocity (+ the 256-row batch shard) — far below two full copies
    assert mem.argument_size_in_bytes \
        < 4 * _n_alexnet_params(alexnet) * 1.5 + (256 << 20)


def test_dp_default_alexnet_train_step_compiles_for_2x2(topo, alexnet,
                                                        monkeypatch,
                                                        compiled_pallas):
    """The default multi-chip step (`run_fused(mesh=make_mesh())`) held
    against a v5e's limit: AlexNet's 0.75 GB of state asks for no
    sharding, so every chip applies the full update: to float32 gradients
    all-reduced leaf by leaf, except FC1's and FC2's (151 and 67 MB
    against 27 and 17 MB of operands at 256 a chip), which every chip
    forms whole from all-gathered operands (PR 29); no parameter is
    gathered. Its LRN is the default too: the kernels under shard_map at
    256 a chip, LRN1 still batch in lanes, and no relayout around them."""
    monkeypatch.setenv(res.HBM_LIMIT_ENV, str(16_900_000_000))
    step, lowered, compiled = _compile_dp_step_for_2x2(topo, alexnet)
    assert step.variant_table()["lrn"] == "pallas_one_pass"
    kernels = [ln.split(" = ")[1].split("{")[0]
               for ln in compiled.as_text().splitlines()
               if "tpu_custom_call" in ln and "veles_lrn_" in ln]
    assert sorted(kernels) == ["bf16[186624,256]"] * 2 \
        + ["bf16[3025,96,256]"] * 2, kernels
    per_chip = BATCH // 4
    assert _relayouts_of(
        compiled.as_text(),
        [(per_chip,) + s for s in LRN_SITES]
        + [(55 * 55, 96, per_chip), (27 * 27 * per_chip, 256),
           (55, 55, 96, per_chip), (27, 27, per_chip, 256)]) == []
    assert not step.zero_active, step.zero_reason
    n_params = _n_alexnet_params(alexnet)
    assert str(12 * n_params) in step.zero_reason
    assert step.variant_table()["grad_exchange"].startswith(
        "2 of 8 units gather at 256 rows x 4 chips: 44.0 MB of operands "
        "all-gathered for 218.1 MB of gradient")
    asked = lowered.as_text()
    assert "all_reduce" in asked and "all_gather" in asked
    assert "reduce_scatter" not in asked
    txt = compiled.as_text()
    # every all-reduced gradient leaf is float32, and the two large
    # dense layers' are not among them: their operands are gathered
    reduced = [line.split(" all-reduce(")[0] for line in txt.splitlines()
               if " all-reduce(" in line]
    assert any("f32[4096,1000]" in r for r in reduced), reduced
    assert not any("f32[9216,4096]" in r or "f32[4096,4096]" in r
                   for r in reduced), reduced
    assert not any("bf16[" in r for r in reduced), reduced
    gathered = [line.split(" all-gather(")[0] for line in txt.splitlines()
                if " all-gather(" in line]
    assert any("bf16[1024,9216]" in g for g in gathered), gathered
    assert any("bf16[1024,4096]" in g for g in gathered), gathered
    assert not any("f32[" in g for g in gathered), gathered
    # each of the two updates rides in its weight-gradient fusion, as on
    # one chip: a fusion that takes the gathered operands and returns the
    # new float32 weights and velocity
    for shape in ("f32[9216,4096]", "f32[4096,4096]"):
        assert any(
            line.split(" = ")[1].startswith(f"({shape}") and " fusion(" in line
            and "kind=kOutput" in line
            for line in txt.splitlines() if " = " in line), shape
    mem = compiled.memory_analysis()
    # per-device arguments: f32 params and the whole velocity
    assert 8 * n_params <= mem.argument_size_in_bytes \
        < 8 * n_params + (256 << 20)


# -- the language-model cells' steps at their published widths (ISSUE 32, 35) --
#
# Each cell twice (ISSUE 39): what `measure` gives before `.compile()`, in
# tier-1 under the test's old name; and the compiled program's memory and
# text, in a twin marked `slow` on the same lowered step.

def _trace_cost():
    """`tools/trace_cost.py` as a module (tools/ is no package)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_cost", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "trace_cost.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lowered(*args, **kw):
    """`measure`'s row of one trace and one lowering for the described
    chip (`compiled_pallas`'s answer, for a fixture that lives a module),
    its text kept as the module's locations name it (`debug_text`: the
    scope paths of every operation and call site)."""
    prev, pk.available = pk.available, lambda: True
    try:
        row = _trace_cost().measure(*args, **kw)
    finally:
        pk.available = prev
    del row["text"]             # (the counts are taken; 150 MB for keye2_ep8)
    row["debug_text"] = row["lowered"].as_text(debug_info=True)
    return row


def _compiled(row):
    """The lowered step of `row` compiled for the described chip: the one
    XLA compile of a whole step at a cell's size, minutes here."""
    t0 = time.perf_counter()
    compiled = row["lowered"].compile()
    return {"compiled": compiled, "text": compiled.as_text(),
            "compile_s": time.perf_counter() - t0}


def _n_leaves(row):
    return sum(int(np.prod(a.shape)) for layer in row["args"][0]["params"]
               for a in layer.values())


def _units_calling(txt, scope, fn):
    """The units (`L03.hc_block` ...) with a call site of the jitted kernel
    wrapper `fn` under `scope`, read from the lowered module's locations."""
    paths = set(re.findall(r'loc\("([^"]*/%s/jit\(%s\))"' % (scope, fn), txt))
    return {m for p_ in paths for m in re.findall(r"L\d\d\.\w+", p_)}, paths


@pytest.fixture(scope="module")
def xing4_lowered(one_chip):
    """`xing4_ep8`'s step traced and lowered under the kernels: ONE trace
    for the tier-1 tests below and for the slow twins."""
    return _lowered("pallas_one_pass", one_chip)


@pytest.fixture(scope="module")
def xing4_step(one_chip, xing4_lowered):
    """That step compiled, and the step traced and lowered under the XLA
    forms to compare with: ONE of each for the slow twins below."""
    xla = _lowered("xla", one_chip, flash="xla_mha")
    return {"xla": xla, "one": xing4_lowered, **_compiled(xing4_lowered)}


def test_xing4_ep8_train_step_compiles_and_fits_one_chip(xing4_lowered):
    """`benchmark/configs/xing4_ep8.json` through the sample's layer table,
    `StandardWorkflow` and `FusedTrainStep`: 8,192 tokens, bfloat16, one
    `jax.checkpoint` a block, traced and lowered for a described v5e (the
    compile of what is lowered here, and whether it fits, is
    `test_xing4_ep8_compiled_step_fits_one_chip`'s, `slow`). The grouped
    products of the held experts lower to `lax.ragged_dot`, under TEN
    `lax.cond`s: the five expert layers' first forwards and their
    backwards, and no third run for the hyper-connection's backward, whose
    operand the checkpoint saves by name (`ops.moe.MOE_SAVED`, ISSUE 40:
    jax's own dead-code pass drops the rest before the text is lowered;
    fifteen without the name); the twelve hyper-connections call the four
    `veles_hc_*` kernels (ISSUE 34), the six latent-attention sites the
    three `veles_flash_*` (ISSUE 38). A shape fault shows here before a
    chip is asked. The units hold zeros (`init_std` 0: no draw), nothing is
    put on a device.

    What Python pays before XLA sees the step is held to COUNTS, which do
    not wobble under xdist as seconds do (PR 33 inlined a `pallas_call` a
    site, 72 bodies, and was refused for 15 s of `setup_s` that no compile
    clock held): each kernel is jitted once and called a site. The seconds
    are printed; PERF.md quotes them."""
    one = xing4_lowered
    assert (one["hc"], one["flash_attn"]) == ("pallas_one_pass", "pallas")
    print("trace_cost", {k: one[k] for k in (
        "hc", "flash_attn", "trace_s", "lower_s", "equations",
        "stablehlo_bytes", "kernels", "conds")})
    assert one["conds"] == 10, one["conds"]
    # a backward kernel's body once; a forward kernel's at most twice: the
    # plain one of the first forward and the one `jax.checkpoint`'s partial
    # evaluation stages for the recomputed forward (derived once, cached)
    hc = {k: v for k, v in one["kernels"].items() if k.startswith("veles_hc")}
    assert {k: v["bodies"] for k, v in hc.items()} == {
        "veles_hc_pre_fwd": 2, "veles_hc_post_fwd": 2,
        "veles_hc_post_bwd": 1, "veles_hc_pre_bwd": 1}, one["kernels"]
    assert all(v["sites"] >= 12 for v in hc.values()), one["kernels"]
    # the held experts' combine (ISSUE 43), whatever forms the products:
    # a body a buffer (the fast rows, the whole one) and direction, a site
    # a branch of the five layers' forward and backward `cond`s
    assert one["kernels"]["veles_seg_sum"] == {"bodies": 4, "sites": 20}
    paths = set(re.findall(
        r'loc\("([^"]*/experts/cond/[^"]*jit\(seg_sum_pallas\))"',
        one["debug_text"]))
    units = {m for p_ in paths for m in re.findall(r"L\d\d\.\w+", p_)}
    # (four expert blocks and the MTP module's expert layer)
    assert len(units) == 5 and all("moe" in re.split(r"[/()]", p_)
                                   for p_ in paths), sorted(paths)[:3]
    cfg, step = one["config"], one["step"]
    assert step.has_aux and step.unit_loss
    assert _n_leaves(one) == cfg["n_params"]
    txt = one["debug_text"]
    assert "ragged_dot" in txt and "tpu_custom_call" in txt
    for scope in ("/mla/", "/moe/experts/", "/hc_pre/", "/hc_post/",
                  "update/balance", "rematted_computation"):
        assert scope in txt, scope
    # every site's call of a kernel stands under the scope `step_hc_ms`
    # reads (XLA inlines the calls: the compiled text's paths are the twin's)
    for fn, side in (("hc_pre_forward_pallas", "hc_pre"),
                     ("hc_pre_backward_pallas", "hc_pre"),
                     ("hc_post_forward_pallas", "hc_post"),
                     ("hc_post_backward_pallas", "hc_post")):
        units, paths = _units_calling(txt, side, fn)
        assert len(units) >= 5, (fn, sorted(paths)[:3])


@pytest.mark.slow
def test_xing4_ep8_compiled_step_fits_one_chip(xing4_step):
    """The step `test_xing4_ep8_train_step_compiles_and_fits_one_chip`
    lowers, compiled for the described v5e: the grouped products are the
    TPU's grouped-matmul kernel, every site's kernel carries its unit's
    path, and the arguments, results and temporaries fit one chip. A
    memory fault shows here before a chip is asked; on every PR the cell
    `xing4_ep8.step` holds it on the chip (`hbm_peak_gb`). The traced
    step is no larger than under the XLA lowerings, traced here too."""
    xla, one = xing4_step["xla"], xing4_step["one"]
    assert (xla["hc"], one["hc"]) == ("xla", "pallas_one_pass")
    assert (xla["flash_attn"], one["flash_attn"]) == ("xla_blocked",
                                                      "pallas")
    for row in (xla, one):
        print("trace_cost", {k: row[k] for k in (
            "hc", "flash_attn", "trace_s", "lower_s", "equations",
            "stablehlo_bytes", "kernels")})
    # (the held experts' combine is no registry op: it stays a kernel
    # under the XLA lowerings of `hc` and `flash_attn`, ISSUE 43)
    assert set(xla["kernels"]) == {"veles_seg_sum"}, xla["kernels"]
    assert one["equations"] <= xla["equations"]
    assert one["stablehlo_bytes"] <= xla["stablehlo_bytes"]
    cfg = one["config"]
    compiled, txt = xing4_step["compiled"], xing4_step["text"]
    assert "ragged-dot" in txt and "tpu_custom_call" in txt
    for scope in ("/mla/", "/moe/experts/", "/hc_pre/", "/hc_post/",
                  "update/balance", "rematted_computation"):
        assert scope in txt, scope
    # the held experts' ten `cond`s of the lowered step are the compiled
    # one's: XLA merges none and none stands in the recomputed forward
    conds = re.findall(r'[^\n]* conditional\([^\n]*', txt)
    assert len(conds) == one["conds"] == 10, len(conds)
    assert not any("rematted_computation" in c for c in conds)
    # after XLA inlines the calls every site's kernel carries its own path
    # under the scope `step_hc_ms` reads
    for kernel, side in (("veles_hc_pre_fwd", "hc_pre"),
                         ("veles_hc_pre_bwd", "hc_pre"),
                         ("veles_hc_post_fwd", "hc_post"),
                         ("veles_hc_post_bwd", "hc_post")):
        paths = set(re.findall(
            r'op_name="([^"]*/%s/[^"]*%s[^"]*)"' % (side, kernel), txt))
        units = {m for p_ in paths for m in re.findall(r"L\d\d\.\w+", p_)}
        assert len(units) >= 5, (kernel, sorted(paths)[:3])
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print("xing4 step: compiled in", round(xing4_step["compile_s"], 1),
          "s; generated code", mem.generated_code_size_in_bytes,
          "B, temporaries", mem.temp_size_in_bytes, "B, in all", total, "B")
    # parameters and velocity, float32: 8 B a parameter of arguments
    assert mem.argument_size_in_bytes > 8 * cfg["n_params"]
    # what a v5e's allocator offers: `bytes_limit` of its memory
    # statistics (chip runs of PR 32)
    assert total < 16909336064, total


def test_xing4_ep8_attention_core_is_three_kernels_traced_once(xing4_lowered):
    """The six latent-attention sites (five blocks and the MTP block) call
    ONE body of each `veles_flash_*` kernel: each is a module-level jit,
    and the blocks and the head share ONE `jax.checkpoint` policy object.
    The policy saves the heads' outputs and the logsumexps, so no forward
    kernel is called in the recomputed forward; every call stands under
    `mla`, which `step_attn_ms` reads. (That no score block is left in the
    compiled step, and its temporaries, is
    `test_xing4_ep8_compiled_attention_core_leaves_no_score_block`'s,
    `slow`.)"""
    one = xing4_lowered
    flash = {k: v for k, v in one["kernels"].items()
             if k.startswith("veles_flash")}
    assert flash == {k: {"bodies": 1, "sites": 6} for k in (
        "veles_flash_fwd", "veles_flash_dq", "veles_flash_dkv")}, flash
    for fn in ("flash_forward_pallas", "flash_dq_pallas", "flash_dkv_pallas"):
        units, paths = _units_calling(one["debug_text"], "mla", fn)
        assert len(paths) == len(units) == 6, sorted(paths)[:3]
        assert not any("rematted_computation" in p_ for p_ in paths), fn


@pytest.mark.slow
def test_xing4_ep8_compiled_attention_core_leaves_no_score_block(xing4_step):
    """In the compiled step the three `veles_flash_*` kernels stand at six
    paths each, under `mla`, none in the recomputed forward; no (heads,
    queries, keys) score block is left in the step; and the step's
    temporaries, 7.13 GB with the blocked XLA form (compiled here for a
    v5e, PR 38), are 6.11."""
    one, txt = xing4_step["one"], xing4_step["text"]
    for kernel in ("veles_flash_fwd", "veles_flash_dq", "veles_flash_dkv"):
        paths = set(re.findall(r'op_name="([^"]*%s[^"]*)"' % kernel, txt))
        assert len(paths) == 6 and all("mla" in re.split(r"[/()]", p_)
                                       for p_ in paths), sorted(paths)[:3]
        assert not any("rematted_computation" in p_ for p_ in paths), kernel
    seq = one["config"]["seq_len"]
    assert not re.search(r"f32\[\d+,\d+,(1024|%d),(1024|2048|3072|%d)\]"
                         % (seq, seq), txt)
    mem = xing4_step["compiled"].memory_analysis()
    assert mem.temp_size_in_bytes < 6_600_000_000, mem.temp_size_in_bytes


#: `keye2_ep8`'s kernels and the bodies of each in the lowered module:
#: every kernel's body once, called a site (one jit each, ONE checkpoint
#: policy object for the six blocks); the mean-head probabilities by band
#: of queries, four shapes, forward and backward; the index scores likewise
#: (the selection's and the loss's calls of a band share one body), their
#: gradient in the backward alone; the grouped products either width
#: first, on the fast rows and on the whole buffer: forward, again where
#: the backward recomputes, and the other way round; the combine
#: (ISSUE 43) on either buffer, forward and as the rows' gather's transpose
KEYE2_BODIES = (("veles_dsa_attend_fwd", 1), ("veles_dsa_pmean", 8),
                ("veles_dsa_index_fwd", 8), ("veles_dsa_index_bwd", 4),
                ("veles_dsa_attend_dq", 1), ("veles_dsa_attend_dkv", 1),
                ("veles_gmm", 12), ("veles_tgmm", 4), ("veles_seg_sum", 4))
# (a block of queries is the body of a `lax.map`: `dsa/while/body/
# closed_call/select/...`; the readers match whole components)
KEYE2_SCOPES = ("/dsa/qkv/", "/dsa/indexer/", "/select/", "/attend/",
                "/index_loss/", "/moe/router/", "/moe/experts/",
                "/moe/balance_loss/", "rematted_computation",
                "veles_dsa_attend_fwd", "veles_dsa_pmean",
                "veles_dsa_attend_dq", "veles_dsa_attend_dkv",
                "veles_dsa_index_fwd", "veles_dsa_index_bwd",
                "veles_gmm", "veles_tgmm", "veles_seg_sum")


@pytest.fixture(scope="module")
def keye2_lowered(one_chip):
    """`keye2_ep8`'s step traced and lowered under what the described chip
    resolves: ONE trace for the tier-1 test below and for its slow twin."""
    return _lowered(None, one_chip, "keye2_ep8")


@pytest.fixture(scope="module")
def keye2_step(keye2_lowered):
    return {"row": keye2_lowered, **_compiled(keye2_lowered)}


def test_keye2_ep8_train_step_compiles_and_fits_one_chip(keye2_lowered):
    """`benchmark/configs/keye2_ep8.json` through the sample's layer table,
    `StandardWorkflow` and `FusedTrainStep`: ONE sequence of 16,384
    tokens, bfloat16, one `jax.checkpoint` a block that saves the indexed
    attention's thresholds, logsumexps and outputs; the main attention as
    the four `veles_dsa_*` kernels (`dsa: pallas_flash`), the indexer's
    scores and their gradient as two more (ISSUE 36), the held experts'
    products as `veles_gmm` / `veles_tgmm` (`grouped: pallas`). Traced and
    lowered for a described v5e, ONE trace (the compile of what is lowered
    here, and its memory, is `test_keye2_ep8_compiled_step_fits_one_chip`'s,
    `slow`). The units hold zeros (`init_std` 0: no draw), nothing is put
    on a device."""
    row = keye2_lowered
    cfg, step = row["config"], row["step"]
    assert step.has_aux and step.unit_loss
    assert row["dsa"] == "pallas_flash"
    assert _n_leaves(row) == cfg["n_params"] == 659190016
    print("keye2 step: traced and lowered in",
          round(row["trace_s"] + row["lower_s"], 1), "s")
    txt = row["debug_text"]
    assert "ragged_dot" not in txt and "tpu_custom_call" in txt
    for scope in KEYE2_SCOPES:
        assert scope in txt, scope
    for kernel, bodies in KEYE2_BODIES:
        assert row["kernels"][kernel]["bodies"] == bodies, row["kernels"]
    # the recomputed forward neither selects nor attends again: the
    # thresholds and outputs are saved (`ops.attention.DSA_SAVED`)
    assert not re.search(r'rematted_computation[^"]*/dsa/while', txt)
    assert cfg["query_block"] == 256
    assert cfg["sa_config"]["indexer_num_heads"] == 16


@pytest.mark.slow
def test_keye2_ep8_compiled_step_fits_one_chip(keye2_step):
    """The step `test_keye2_ep8_train_step_compiles_and_fits_one_chip`
    lowers, compiled for the described v5e. ONE compile: memory is known
    before the first chip call (ISSUE 35); nothing of (16 heads, queries,
    keys) is left in the step (ISSUE 36). On every PR the cell
    `keye2_ep8.long16k` holds the memory on the chip (`hbm_peak_gb`)."""
    row, compiled, txt = (keye2_step[k] for k in ("row", "compiled", "text"))
    cfg = row["config"]
    assert "ragged-dot" not in txt and "tpu_custom_call" in txt
    for scope in KEYE2_SCOPES:
        assert scope in txt, scope
    for kernel, bodies in KEYE2_BODIES:
        assert row["kernels"][kernel]["bodies"] == bodies, row["kernels"]
        paths = set(re.findall(r'op_name="([^"]*%s[^"]*)"' % kernel, txt))
        assert len({m for p_ in paths for m in re.findall(
            r"L\d\d\.\w+", p_)}) == 6, (kernel, sorted(paths)[:3])
    # the recomputed forward neither selects nor attends again: the
    # thresholds and outputs are saved (`ops.attention.DSA_SAVED`)
    assert not re.search(r'op_name="[^"]*rematted_computation[^"]*/dsa/while',
                         txt)
    # the index heads' scores of a block of queries exist in VMEM only: no
    # (16 heads, queries, keys) tensor is left under the indexer's scope
    # (a band's mean-head probabilities cut into its 16 blocks are (16, 256,
    # keys) too, under `index_loss`)
    heads = cfg["sa_config"]["indexer_num_heads"]
    assert cfg["query_block"] == 256 and heads == 16
    for line in txt.splitlines():
        if "/dsa/" in line and "/indexer/" in line:
            assert not re.search(r"\b(f32|pred|bf16)\[16,(256|4096),"
                                 r"(4096|8192|12288|16384)\]", line), line[:300]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print("keye2 step: traced and lowered in",
          round(row["trace_s"] + row["lower_s"], 1), "s, compiled in",
          round(keye2_step["compile_s"], 1), "s; generated code",
          mem.generated_code_size_in_bytes, "B, arguments",
          mem.argument_size_in_bytes, "B, temporaries",
          mem.temp_size_in_bytes, "B, in all", total, "B")
    # parameters and velocity, float32: 8 B a parameter of arguments
    assert mem.argument_size_in_bytes > 8 * cfg["n_params"]
    # what a v5e's allocator offers: `bytes_limit` of its memory
    # statistics (chip runs of PR 32)
    assert total < 16909336064, total
    # the step's temporaries: 6.45 GB at PR 35, 6.53 with the indexer's
    # kernels a block of queries a call; a band a call read 10.79 here and
    # 16.1 GB of peak on the chip where 12.6 were (PR 36): XLA kept four
    # bands' scores alive across the attention
    assert mem.temp_size_in_bytes < 7 << 30, mem.temp_size_in_bytes


#: `qwen3next_ep16`'s kernels and the bodies of each in the lowered module:
#: the full layer's three flash kernels once each (one site: one period
#: has one full layer); the grouped products either width first on the
#: fast rows, forward, again where the backward recomputes, and the other
#: way round, the whole-buffer branch being the same rows a window at a
#: time (`ops.moe._WHOLE_BUFFER_MAX`); the operand stage of the linear
#: layers' scan forward as the forward pass traces it and as a group's
#: `jax.checkpoint` traces it again (6 sites, 2 bodies), backward once;
#: the combine (ISSUE 43) on the fast rows, which are a window's rows too
QWEN3NEXT_BODIES = (("veles_flash_fwd", 1), ("veles_flash_dq", 1),
                    ("veles_flash_dkv", 1), ("veles_gmm", 10),
                    ("veles_tgmm", 4), ("veles_gdn_chunk_fwd", 2),
                    ("veles_gdn_chunk_bwd", 1), ("veles_seg_sum", 3))
# (a linear layer walks its sequences in `scan_groups` groups, the body of
# a `lax.map`: `gdn/while/body/.../proj/...`; the chain along the sequence
# is the body of a `lax.scan` inside it: `.../scan/while/body/...`; its
# backward opens `gdn/scan` again)
QWEN3NEXT_SCOPES = ("/gdn/while/", "/proj/", "/conv/", "/scan/out/",
                    "/scan/while/", "/gdn/scan/", "/out/", "/attn/",
                    "/moe/router/", "/moe/experts/", "/moe/shared/",
                    "/moe/balance_loss/", "rematted_computation",
                    "veles_flash_fwd", "veles_flash_dq", "veles_flash_dkv",
                    "veles_gmm", "veles_tgmm", "veles_gdn_chunk_fwd",
                    "veles_gdn_chunk_bwd", "veles_seg_sum")


@pytest.fixture(scope="module")
def qwen3next_lowered(one_chip):
    """`qwen3next_ep16`'s step traced and lowered under what the described
    chip resolves: ONE trace for the tier-1 test below and its slow twin."""
    return _lowered(None, one_chip, "qwen3next_ep16")


@pytest.fixture(scope="module")
def qwen3next_step(qwen3next_lowered):
    return {"row": qwen3next_lowered, **_compiled(qwen3next_lowered)}


def test_qwen3next_ep16_train_step_compiles_and_fits_one_chip(
        qwen3next_lowered):
    """`benchmark/configs/qwen3next_ep16.json` through the sample's layer
    table, `StandardWorkflow` and `FusedTrainStep`: 4 sequences of 8,192
    tokens, bfloat16, one `jax.checkpoint` a block; three Gated DeltaNet
    blocks whose chunked scan is two kernels and plain XLA around one
    `lax.scan` and its hand-written backward, one gated full-attention
    block whose core is
    the three `veles_flash_*` kernels (ISSUE 41: a key-value head repeated
    to its 8 query heads), the held experts' products as `veles_gmm` /
    `veles_tgmm`. Traced and lowered for a described v5e, ONE trace (the
    compile of what is lowered here, and its memory, is
    `test_qwen3next_ep16_compiled_step_fits_one_chip`'s, `slow`). Since
    ISSUE 42 the chunks' operand stage is `veles_gdn_chunk_fwd` / `_bwd`,
    each traced once and called under `gdn/.../scan`. The units
    hold zeros (`init_std` 0: no draw), nothing is put on a device."""
    row = qwen3next_lowered
    cfg, step = row["config"], row["step"]
    assert step.has_aux and step.unit_loss
    assert row["flash_attn"] == "pallas" and row["dsa"] is None
    assert _n_leaves(row) == cfg["n_params"] == 625667136
    print("qwen3next step: traced and lowered in",
          round(row["trace_s"] + row["lower_s"], 1), "s")
    txt = row["debug_text"]
    assert "ragged_dot" not in txt and "tpu_custom_call" in txt
    for scope in QWEN3NEXT_SCOPES:
        assert scope in txt, scope
    for kernel, bodies in QWEN3NEXT_BODIES:
        assert row["kernels"][kernel]["bodies"] == bodies, row["kernels"]
    assert set(row["kernels"]) == {k for k, _ in QWEN3NEXT_BODIES}
    # the chunks' operand stage is the two kernels (ISSUE 42): a site a
    # linear layer in the forward pass, one where its group's checkpoint
    # forms the layer again, one backward under the scope the backward
    # opens again (`gdn_scan_ms` reads all three); a site is the body of
    # the groups' loop: two calls a step
    assert row["kernels"]["veles_gdn_chunk_fwd"]["sites"] == 6
    assert row["kernels"]["veles_gdn_chunk_bwd"]["sites"] == 3
    for path in ("checkpoint/scan/jit(gdn_chunk_forward_pallas)",
                 "rematted_computation/scan/jit(gdn_chunk_forward_pallas)",
                 "gdn/scan/jit(gdn_chunk_backward_pallas)"):
        assert path in txt, path
    # the fast rows and the walk in windows, forward and backward, of four
    # expert layers
    assert row["conds"] == 8, row["conds"]
    assert (cfg["chunk"], cfg["scan_groups"]) == (64, 2)


@pytest.mark.slow
def test_qwen3next_ep16_compiled_step_fits_one_chip(qwen3next_step):
    """The step `test_qwen3next_ep16_train_step_compiles_and_fits_one_chip`
    lowers, compiled for the described v5e. ONE compile: memory is known
    before the first chip call. On every PR the cell
    `qwen3next_ep16.seq8k` holds the memory on the chip (`hbm_peak_gb`)."""
    row, compiled, txt = (qwen3next_step[k]
                          for k in ("row", "compiled", "text"))
    cfg = row["config"]
    assert "ragged-dot" not in txt and "tpu_custom_call" in txt
    for kernel, _bodies in QWEN3NEXT_BODIES:
        assert kernel in txt, kernel
    for scope in ("/gdn/", "/scan/", "/conv/", "/attn/", "/moe/experts/"):
        assert scope in txt, scope
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print("qwen3next step: traced and lowered in",
          round(row["trace_s"] + row["lower_s"], 1), "s, compiled in",
          round(qwen3next_step["compile_s"], 1), "s; generated code",
          mem.generated_code_size_in_bytes, "B, arguments",
          mem.argument_size_in_bytes, "B, temporaries",
          mem.temp_size_in_bytes, "B, in all", total, "B")
    # parameters and velocity, float32: 8 B a parameter of arguments
    assert mem.argument_size_in_bytes > 8 * cfg["n_params"]
    # what a v5e's allocator offers: `bytes_limit` of its memory
    # statistics (chip runs of PR 32)
    assert total < 16909336064, total
