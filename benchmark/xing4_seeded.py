"""What a `xing4_lm` run draws from `--seed`: the weights and the token
stream. Nothing here imports the program; the keys are `seeded.py`'s.

The initial values are the configuration file's `assumed.init`: matrices
normal at `init_std`, norm scales 1, and the hyper-connections so that at
the start every stream is read at 1/n, the sub-layer's output is written
at 1 to every stream and the streams stay apart (`a` 0.01, `b_res` 8 on
the diagonal).
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark import xing4_ops_count as counts


def make_params(cfg: Dict[str, Any], key):
    """The weights from `stream_key(seed, "weights")`, as a tuple with one
    dict per unit of the layer table. Traceable: called under one jit, on
    the device, with the key as an argument."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg.get("master_dtype", "float32"))
    n = cfg["hc_mult"]
    out = []
    for i, shapes in enumerate(counts.shapes_of(cfg)):
        layer = {}
        for j, (name, shape) in enumerate(sorted(shapes.items())):
            stem = name.rsplit("_", 2)
            if name.endswith("norm") or "_norm_" in name:
                value = jnp.ones(shape, dtype)
            elif stem[-2:] in (["a", "pre"], ["a", "post"], ["a", "res"]):
                value = jnp.full(shape, 0.01, dtype)
            elif stem[-2:] == ["b", "pre"]:
                value = jnp.full(shape, math.log(1.0 / max(n - 1, 1e-13)),
                                 dtype)
            elif stem[-2:] == ["b", "post"]:
                value = jnp.zeros(shape, dtype)
            elif stem[-2:] == ["b", "res"]:
                value = 8.0 * jnp.eye(n, dtype=dtype)
            else:
                value = cfg["init_std"] * jax.random.normal(
                    jax.random.fold_in(jax.random.fold_in(key, i), j),
                    shape, dtype)
            layer[name] = value
        out.append(layer)
    return tuple(out)


def make_batch(cfg: Dict[str, Any], n: int, key, step):
    """(ids (n, S) int32, targets (n, S, 2) int32: the next and the
    next-next token) of step `step`: S + 2 ids a sequence, i.i.d. uniform
    over the held vocabulary, from `fold_in(stream_key(seed, "inputs"),
    step)`. Traceable, `step` included."""
    import jax
    import jax.numpy as jnp
    s = cfg["seq_len"]
    ids = jax.random.randint(jax.random.fold_in(key, step), (n, s + 2), 0,
                             cfg["vocab_size"], jnp.int32)
    return ids[:, :s], jnp.stack([ids[:, 1:s + 1], ids[:, 2:s + 2]], axis=-1)
