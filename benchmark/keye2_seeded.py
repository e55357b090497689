"""What a `keye2_lm` run draws from `--seed`: the weights and the token
stream. Nothing here imports the program; the keys are `seeded.py`'s.

The initial values are the configuration file's `assumed.init`: matrices
normal at `init_std`, norm scales 1, the indexer's LayerNorm bias 0.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import keye2_ops_count as counts


def make_params(cfg: Dict[str, Any], key):
    """The weights from `stream_key(seed, "weights")`, as a tuple with one
    dict per unit of the layer table. Traceable: called under one jit, on
    the device, with the key as an argument."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg.get("master_dtype", "float32"))
    out = []
    for i, shapes in enumerate(counts.shapes_of(cfg)):
        layer = {}
        for j, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith("norm"):
                value = jnp.ones(shape, dtype)
            elif name.endswith("_bias"):
                value = jnp.zeros(shape, dtype)
            else:
                value = cfg["init_std"] * jax.random.normal(
                    jax.random.fold_in(jax.random.fold_in(key, i), j),
                    shape, dtype)
            layer[name] = value
        out.append(layer)
    return tuple(out)


def make_batch(cfg: Dict[str, Any], n: int, key, step):
    """(ids (n, S) int32, targets (n, S) int32: the next token) of step
    `step`: S + 1 ids a sequence, i.i.d. uniform over the held
    vocabulary, from `fold_in(stream_key(seed, "inputs"), step)`.
    Traceable, `step` included."""
    import jax
    import jax.numpy as jnp
    s = cfg["seq_len"]
    ids = jax.random.randint(jax.random.fold_in(key, step), (n, s + 1), 0,
                             cfg["vocab_size"], jnp.int32)
    return ids[:, :s], ids[:, 1:]
