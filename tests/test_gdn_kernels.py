"""The two kernels of the chunked Gated DeltaNet's operand stage
(`veles_gdn_chunk_fwd`, `veles_gdn_chunk_bwd`; ISSUE 42), interpreted on
the CPU at small sizes (chunks of 64, heads of 128, 2-4 chunks, one or two
heads), against their XLA twin and against the token recurrence; and the
rule that says which shapes they take.

On the CPU the twin's inverse multiplies float32 operands exactly (this
suite runs at `highest`), the kernels read the same operands in ONE
bfloat16 pass, as a TPU's default precision has the twin do: results that
are rounded to bfloat16 anyway agree to a few parts in a thousand, not to
float32's last bits. On the chip the two agree to the bit (PERF.md, PR 42)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import qwen3next_reference  # noqa: E402
from veles_tpu.ops import linear_attention as la  # noqa: E402
from veles_tpu.ops import pallas_kernels as pk  # noqa: E402
from veles_tpu.ops import variants  # noqa: E402

OUTPUTS = ("w", "u0", "kd", "last", "attn", "qg")
#: log-decays a token: weak ones barely decay inside a chunk, strong ones
#: take the cumulative log-decay of a chunk far past -80, where a quotient
#: of exponentials would overflow float32
DECAYS = {"weak": (-7.0, -3.0), "strong": (0.5, 2.5), "mixed": (-5.0, 2.5)}


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(pk, "_FORCE_INTERPRET", True)


def _scan(*a, **kw):
    """`gated_delta_chunked` as a block calls it: with the package's word
    on whether kernels may be traced (here: where interpret mode is on)."""
    return la.gated_delta_chunked(*a, kernels=variants.kernels_ok(), **kw)


def _inputs(seq, heads, decay="mixed", n=1, dk=128, dv=128, seed=0,
            dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.key(seed), 6)
    lo, hi = DECAYS[decay]
    q = la.l2_normalize(jax.random.normal(ks[0], (n, seq, heads, dk))) \
        * dk ** -0.5
    k = la.l2_normalize(jax.random.normal(ks[1], (n, seq, heads, dk)) + 0.3)
    v = jax.random.normal(ks[2], (n, seq, heads, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (n, seq, heads), minval=lo,
                                    maxval=hi))
    beta = jax.nn.sigmoid(2 * jax.random.normal(ks[4], (n, seq, heads)))
    ct = jax.random.normal(ks[5], (n, seq, heads, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), ct


def _rel(a, b):
    a, b = (jnp.asarray(x, jnp.float32) for x in (a, b))
    # (a chunk's last decay under strong decays underflows to 0 in both)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) or 1.0))


def _chunked(args, chunk=64):
    """(chunks, B, C, ...) operands of the stage, as `gated_delta_chunked`
    lays them out."""
    n, s, h = args[3].shape
    nc = s // chunk

    def chunks(a):
        a = a.reshape((n, nc, chunk, h) + a.shape[3:])
        a = jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)
        return a.reshape((nc, n * h, chunk) + a.shape[4:])
    return tuple(chunks(a) for a in args)


# -- (a) the forward kernel's six outputs -------------------------------------------

@pytest.fixture(scope="module")
def stage_outputs():
    """{(decay, heads): (the kernel's results, the twin's)}, formed once a
    case for the six tests that read them."""
    made = {}

    def of(decay, heads):
        if (decay, heads) not in made:
            ops = _chunked(_inputs(128, heads, decay)[0])
            prev, pk._FORCE_INTERPRET = pk._FORCE_INTERPRET, True
            try:
                got = la._operands_kernels(*ops)
            finally:
                pk._FORCE_INTERPRET = prev
            made[decay, heads] = (got, la._operands_xla(jnp.bfloat16, *ops))
        return made[decay, heads]
    return of


@pytest.mark.parametrize("output", OUTPUTS)
@pytest.mark.parametrize("decay,heads", [("weak", 2), ("strong", 2),
                                         ("mixed", 1)])
def test_the_forward_kernels_outputs_are_the_twins(stage_outputs, decay,
                                                   heads, output):
    got, want = stage_outputs(decay, heads)
    at = OUTPUTS.index(output)
    assert got[at].shape == want[at].shape
    assert got[at].dtype == want[at].dtype
    assert bool(jnp.all(jnp.isfinite(got[at].astype(jnp.float32))))
    assert _rel(got[at], want[at]) < 6e-3, output
    # the seventh, the lowest cumulative log-decay, is XLA's either way
    assert float(got[6]) == float(want[6])
    if decay == "strong":
        assert float(got[6]) < -80.0


# -- (b), (c) the gradients through both kernels ------------------------------------

def _value_and_grads(args, ct, chunk=64):
    def loss(*a):
        o, state, _ = _scan(*a, chunk=chunk)
        return jnp.sum(o * ct), (o, state)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                              has_aux=True)(*args)


@pytest.fixture(scope="module")
def scan_gradients():
    """{(seq, heads): (through the kernels, through the twin, by the token
    recurrence)}: outputs, final state and the five gradients of a scalar
    of `gated_delta_chunked`."""
    made = {}

    def of(seq, heads):
        if (seq, heads) not in made:
            args, ct = _inputs(seq, heads)
            prev, pk._FORCE_INTERPRET = pk._FORCE_INTERPRET, True
            try:
                # (a lambda a trace: jax caches a function's jaxpr by
                # its arguments' shapes, whatever mode it was traced in)
                assert "pallas_call" in str(jax.make_jaxpr(
                    lambda *a: _scan(*a))(*args))
                kernels = _value_and_grads(args, ct)
            finally:
                pk._FORCE_INTERPRET = prev
            twin = _value_and_grads(args, ct)

            def tokens(*a):
                o, state = jax.vmap(qwen3next_reference.delta_rule)(
                    *(x.astype(jnp.float32) for x in a))
                return jnp.sum(o * ct), (o, state)
            rule = jax.value_and_grad(tokens, argnums=(0, 1, 2, 3, 4),
                                      has_aux=True)(*args)
            made[seq, heads] = tuple(
                (o, state) + tuple(grads)
                for (_, (o, state)), grads in (kernels, twin, rule))
        return made[seq, heads]
    return of


READ = ("o", "state", "dq", "dk", "dv", "dg", "dbeta")


@pytest.mark.parametrize("what", READ)
@pytest.mark.parametrize("seq,heads", [(128, 2), (256, 1), (160, 2)],
                         ids=["2x2", "4x1", "filled-up"])
def test_the_scan_through_the_kernels_is_the_twins_and_the_recurrence(
        scan_gradients, seq, heads, what):
    """Whole chunks, and a sequence that is no multiple of 64 (160 tokens:
    the third chunk filled up with tokens that neither write nor decay).
    Against the twin: bfloat16's rounding of the inverse's operands. Against
    the recurrence in float32: what the chunked form in bfloat16 is off by,
    kernels or none."""
    kernels, twin, rule = scan_gradients(seq, heads)
    at = READ.index(what)
    assert kernels[at].dtype == twin[at].dtype
    assert _rel(kernels[at], twin[at]) < 1e-2, what
    assert _rel(kernels[at], rule[at]) < 2e-2 > _rel(twin[at], rule[at])


# -- (d) which shapes the kernels take -----------------------------------------------

def test_the_view_takes_the_cells_shape_and_blocks_it_within_its_budget():
    f32, bf16 = jnp.float32, jnp.bfloat16
    g = pk.gdn_view(8192, 64, 128, 128, f32, bf16)
    assert g and g % 16 == 0 and 8192 % g == 0
    # the backward's blocks, double-buffered: 11 arrays a chunk-head
    assert 2 * g * 64 * 2 * (7 * 128 + 3 * 128 + 128) \
        <= pk._GDN_BLOCK_BUDGET < pk._GDN_VMEM_LIMIT
    assert pk.gdn_view(4, 64, 128, 128, f32, bf16) == 4      # whole
    assert pk.gdn_view(8192, 64, 256, 128, f32, bf16) < g    # wider keys


@pytest.mark.parametrize("refused", ["chunk32", "head64", "bf16_scan",
                                     "f32_operands", "odd"])
def test_what_the_view_refuses_still_runs_the_twin(interpreted, refused):
    """A chunk of 32, a head of 64, a scan held in bfloat16, float32
    operands (the kernels' products are written for bfloat16 ones), an odd
    number of chunk-heads: no kernel in the trace, and the XLA form's
    numbers."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    view = {"chunk32": (4, 32, 128, 128, f32, bf16),
            "head64": (4, 64, 64, 64, f32, bf16),
            "bf16_scan": (4, 64, 128, 128, bf16, bf16),
            "f32_operands": (4, 64, 128, 128, f32, f32),
            "odd": (3, 64, 128, 128, f32, bf16)}[refused]
    assert pk.gdn_view(*view) is None
    assert pk.gdn_view(4, 64, 128, 128, f32, bf16) == 4
    kw = {"chunk": 32 if refused == "chunk32" else 64}
    if refused == "bf16_scan":
        kw["scan_dtype"] = bf16
    d = 64 if refused == "head64" else 128
    args, _ = _inputs(192 if refused == "odd" else 128,
                      1 if refused == "odd" else 2, dk=d, dv=d,
                      dtype=f32 if refused == "f32_operands" else bf16)

    def run(*a):
        return _scan(*a, **kw)
    assert "pallas_call" not in str(jax.make_jaxpr(run)(*args))
    o, state, _ = run(*args)
    assert bool(jnp.all(jnp.isfinite(o))) and o.shape == args[2].shape
    with pytest.raises(ValueError, match="gdn_view"):
        ops = _chunked(args, kw["chunk"])
        pk.gdn_chunk_forward_pallas(
            *(a.reshape((-1,) + a.shape[2:]) for a in ops[:3]),
            *(a.reshape(-1, a.shape[-1]).astype(kw.get("scan_dtype", f32))
              for a in ops[3:]), inverse_block=la.INVERSE_BLOCK,
            interpret=True)


def test_the_kernels_are_asked_for_never_fallen_into():
    """Off a TPU with no interpret mode asked for, the layer traces the
    XLA form: nothing interprets by itself."""
    assert not pk._interpret()
    args, _ = _inputs(128, 2)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda *a: _scan(*a))(*args))
    assert {pk.KERNEL_NAMES[k] for k in ("_gdn_chunk_fwd_kernel",
                                         "_gdn_chunk_bwd_kernel")} \
        == {"veles_gdn_chunk_fwd", "veles_gdn_chunk_bwd"}
