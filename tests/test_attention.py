"""Attention + sequence parallelism: ring and Ulysses forms on the
8-device CPU mesh must match single-device attention exactly (the golden
model), causal and non-causal; plus the MultiHeadAttention unit family
trains (SURVEY.md §4 multi-device test strategy)."""

import jax

from jax import shard_map
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from veles_tpu import prng
from veles_tpu.ops import attention as oa

B, S, H, D = 2, 32, 4, 8


def make_qkv(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(rng.randn(B, S, H, D).astype(np.float32)
                 for _ in range(3))


@pytest.fixture(scope="module")
def seq_mesh(eight_devices):
    return Mesh(np.asarray(eight_devices[:4]), ("seq",))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_golden(seq_mesh, causal):
    q, k, v = make_qkv(0)
    gold = np.asarray(oa.mha_forward(q, k, v, causal=causal))

    ring = jax.jit(shard_map(
        lambda q_, k_, v_: oa.ring_attention(q_, k_, v_, "seq",
                                             causal=causal),
        mesh=seq_mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))
    got = np.asarray(ring(q, k, v))
    np.testing.assert_allclose(got, gold, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_golden(seq_mesh, causal):
    q, k, v = make_qkv(1)
    gold = np.asarray(oa.mha_forward(q, k, v, causal=causal))
    uly = jax.jit(shard_map(
        lambda q_, k_, v_: oa.ulysses_attention(q_, k_, v_, "seq",
                                                causal=causal),
        mesh=seq_mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))
    got = np.asarray(uly(q, k, v))
    np.testing.assert_allclose(got, gold, rtol=2e-4, atol=2e-5)


def test_ring_attention_differentiable(seq_mesh):
    """Gradients flow through the ring (ppermute transposes cleanly) and
    match single-device attention gradients."""
    q, k, v = make_qkv(2)

    def loss_local(q_, k_, v_):
        return (oa.mha_forward(q_, k_, v_, causal=True) ** 2).sum()

    def loss_ring(q_, k_, v_):
        f = shard_map(
            lambda a, b, c: oa.ring_attention(a, b, c, "seq", causal=True),
            mesh=seq_mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"))
        return (f(q_, k_, v_) ** 2).sum()

    g_gold = jax.grad(loss_local, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_gold):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_kv_block_tiling(seq_mesh, causal):
    """kv_block < S_local tiles each hop with an inner scanned flash
    recurrence (checkpointed): forward AND gradients must match the
    single-device golden exactly like the untiled ring."""
    q, k, v = make_qkv(4)

    def loss_local(q_, k_, v_):
        return (oa.mha_forward(q_, k_, v_, causal=causal) ** 2).sum()

    def loss_ring(q_, k_, v_):
        f = shard_map(
            lambda a, b, c: oa.ring_attention(a, b, c, "seq",
                                              causal=causal, kv_block=2),
            mesh=seq_mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"))
        return (f(q_, k_, v_) ** 2).sum()

    # forward
    ring = jax.jit(shard_map(
        lambda a, b, c: oa.ring_attention(a, b, c, "seq", causal=causal,
                                          kv_block=2),
        mesh=seq_mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))
    np.testing.assert_allclose(
        np.asarray(ring(q, k, v)),
        np.asarray(oa.mha_forward(q, k, v, causal=causal)),
        rtol=2e-4, atol=2e-5)
    # backward (through checkpointed inner scan + ppermute)
    g_gold = jax.grad(loss_local, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_gold):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
    # a non-dividing kv_block falls back to one block per hop
    ring_nd = jax.jit(shard_map(
        lambda a, b, c: oa.ring_attention(a, b, c, "seq", causal=causal,
                                          kv_block=3),
        mesh=seq_mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))
    np.testing.assert_allclose(
        np.asarray(ring_nd(q, k, v)),
        np.asarray(oa.mha_forward(q, k, v, causal=causal)),
        rtol=2e-4, atol=2e-5)


def test_attention_unit_trains():
    """MultiHeadAttention + GD twin in a tiny seq-classification graph:
    loss decreases over updates."""
    from veles_tpu.backends import XLADevice
    from veles_tpu.loader.synthetic import SyntheticClassifierLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    prng.seed_all(1234)
    loader = SyntheticClassifierLoader(
        n_classes=4, sample_shape=(8, 16), n_validation=40, n_train=160,
        minibatch_size=40, noise=0.3)
    wf = StandardWorkflow(
        layers=[
            {"type": "attention", "n_heads": 2, "causal": False,
             "weights_stddev": 0.1},
            {"type": "softmax", "output_sample_shape": 4,
             "weights_stddev": 0.05},
        ],
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": 4, "fail_iterations": 50},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        name="AttnTest")
    wf.initialize(device=XLADevice())
    wf.run()
    assert wf.decision.epoch_number == 4
    # 40 validation samples, chance = 30 errors
    assert wf.decision.best_validation_err < 20, \
        wf.decision.best_validation_err


def test_attention_unit_fused_ring_on_mesh(eight_devices):
    """The fused step can run the attention layer in ring mode over a seq
    mesh axis via shard_map (the long-context path end-to-end)."""
    from veles_tpu.ops import attention as oa_
    q, k, v = make_qkv(3)
    mesh = Mesh(np.asarray(eight_devices).reshape(2, 4), ("data", "seq"))

    def fwd(q_, k_, v_):
        return oa_.ring_attention(q_, k_, v_, "seq", causal=True)

    f = jax.jit(shard_map(
        fwd, mesh=mesh, in_specs=(P("data", "seq"),) * 3,
        out_specs=P("data", "seq")))
    got = np.asarray(f(q, k, v))
    gold = np.asarray(oa_.mha_forward(q, k, v, causal=True))
    np.testing.assert_allclose(got, gold, rtol=2e-4, atol=2e-5)
