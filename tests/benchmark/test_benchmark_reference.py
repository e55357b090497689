"""`correct` is decided against the plain reference: it agrees with
`FusedTrainStep` at the stated precision, a lower precision than stated
fails, the float8 control fails, and a timed path broken underneath
fails."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench_paths import FIXTURES

from benchmark import reference, seeded


def tiny():
    with open(os.path.join(FIXTURES, "bench_fixture", "configs",
                           "tiny.json")) as f:
        return json.load(f)


def limits():
    with open(os.path.join(FIXTURES, "bench_fixture", "limits",
                           "tiny.step.json")) as f:
        return json.load(f)


def failed(lines):
    return [ln.split("=")[0].replace("check:", "").strip()
            for ln in lines if ln.startswith("check:") and "NOT OK" in ln]


def test_the_reference_agrees_with_the_fused_step_at_float32(
        run_fixture_cell):
    """Tolerance: the fixture's limits, 1e-4 on the loss and 1e-3 on the
    norms; float32 against float32 at `highest` reads about 1e-7."""
    result, lines = run_fixture_cell("tiny.step")
    assert result["correct"] is True
    gaps = [float(ln.split("=")[1].split("(")[0]) for ln in lines
            if ln.startswith("check:") and ("_gap" in ln or "_err" in ln)]
    assert len(gaps) == 5 and max(gaps) < 1e-5, lines


def test_a_step_computed_below_the_stated_precision_is_not_correct(
        run_fixture_cell):
    """The configuration states float32; the step computes in bfloat16."""
    def in_bfloat16(step):
        step.compute_dtype = "bfloat16"
        return step
    result, lines = run_fixture_cell("tiny.step", sabotage=in_bfloat16)
    assert result["correct"] is False
    assert failed(lines), lines


class Wrapped:
    """A step with its `train` replaced; everything else is the step's."""

    def __init__(self, step, train):
        self._step, self.train = step, train

    def __getattr__(self, name):
        return getattr(self._step, name)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        run_fixture_cell):
    def frozen(step):
        def train(state, x, y, w=None):
            copy = jax.tree.map(jnp.copy, state)
            _, out = step.train(copy, x, y, w)
            return state, out
        return Wrapped(step, train)
    result, lines = run_fixture_cell("tiny.step", sabotage=frozen)
    assert result["correct"] is False
    assert "dparam_norm_gap" in failed(lines)
    assert "grad_norm_gap" in failed(lines)


def test_a_step_that_leaves_out_part_of_the_batch_is_not_correct(
        run_fixture_cell):
    def half(step):
        def train(state, x, y, w=None):
            n = x.shape[0]
            w = jnp.where(jnp.arange(n) < n // 2, 1.0, 0.0)
            return step.train(state, x, y, w)
        return Wrapped(step, train)
    result, lines = run_fixture_cell("tiny.step", sabotage=half)
    assert result["correct"] is False
    assert "loss_rel_gap" in failed(lines)


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_the_float8_control_comes_out_as_not_correct(seed):
    """The control, at a size a test run can hold: the reference in
    float8_e4m3, put in the program's place, fails the fixture's limits
    (on the chip, at the cells' own sizes: PERF.md section 2)."""
    cfg = tiny()
    key = seeded.stream_key(seed, "weights")
    x, y = seeded.make_resident_batch(cfg, 8, seeded.stream_key(seed, "inputs"),
                                      seeded.stream_key(seed, "labels"))
    batches = [(x, y, None)] * 3

    def steps(precision, **kw):
        return reference.reference_steps(
            cfg, seeded.make_params(cfg, key),
            seeded.stream_key(seed, "dropout"), batches, block_rows=4,
            precision=precision, **kw)
    low = steps("float8", keep_first_grad=True)
    ref = steps("float32", first_grad_of_program=low.pop("first_grad"))
    rows = reference.compare(low, ref, limits())
    assert not all(r["ok"] for r in rows), rows
    assert not next(r for r in rows
                    if r["name"] == "head_grad_rel_err")["ok"]
    twin = steps("float32", keep_first_grad=True)
    ref = steps("float32", first_grad_of_program=twin.pop("first_grad"))
    same = reference.compare(twin, ref, limits())
    assert all(r["ok"] and r["value"] == 0 for r in same)


def test_the_worst_leaf_is_held_against_the_median_leaf():
    ref = {"a": 1.0, "b": 1e-9, "c": 2.0}
    gap, leaf = reference.worst_leaf_gap({"a": 1.01, "b": 2e-9, "c": 2.0},
                                         ref)
    assert leaf == "a" and gap == pytest.approx(0.01)
    gap, leaf = reference.worst_leaf_gap(
        {"a": 1.0, "b": float("nan"), "c": 2.0}, ref)
    assert leaf == "b" and gap == float("inf")
    with pytest.raises(ValueError, match="leaves differ"):
        reference.worst_leaf_gap({"a": 1.0}, ref)


def test_the_reference_imports_nothing_of_the_program():
    import benchmark
    for name in ("reference.py", "seeded.py", "ops_count.py"):
        with open(os.path.join(os.path.dirname(benchmark.__file__),
                               name)) as f:
            assert "veles_tpu" not in f.read().replace(
                "It imports nothing of `veles_tpu`", "")


def test_weights_and_inputs_follow_the_seed():
    cfg = tiny()
    a = seeded.make_params(cfg, seeded.stream_key(7, "weights"))
    b = seeded.make_params(cfg, seeded.stream_key(7, "weights"))
    c = seeded.make_params(cfg, seeded.stream_key(2 ** 31 + 7, "weights"))
    assert jnp.array_equal(a[0]["weights"], b[0]["weights"])
    assert not jnp.array_equal(a[0]["weights"], c[0]["weights"])
    d1, l1 = seeded.make_pack(cfg, 16, 2 ** 31 + 9)
    d2, l2 = seeded.make_pack(cfg, 16, 2 ** 31 + 9)
    assert (d1 == d2).all() and (l1 == l2).all() and d1.dtype == "uint8"
    assert len(set(seeded.row_tags(d1).tolist())) == 16
