"""What a `qwen3next_lm` run draws from `--seed`: the weights and the token
stream. Nothing here imports the program; the keys are `seeded.py`'s.

The initial values are the configuration file's `assumed.init`: matrices
(the convolution's taps among them) normal at `init_std`; the zero-centred
norms' scales 0; the gated norm's scale of a linear layer 1; `dt_bias` 1;
`A_log` the logarithm of a rate uniform on (0, 16).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import qwen3next_ops_count as counts
# (the token stream is the other language-model cells')
from benchmark.keye2_seeded import make_batch  # noqa: F401


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
            k = jax.random.fold_in(jax.random.fold_in(key, i), j)
            if name in ("attn_o_norm", "attn_dt_bias"):
                value = jnp.ones(shape, dtype)
            elif name.endswith("norm"):
                value = jnp.zeros(shape, dtype)
            elif name == "attn_a_log":
                value = jnp.log(jax.random.uniform(
                    k, shape, dtype, minval=1e-3, maxval=16.0))
            else:
                value = cfg["init_std"] * jax.random.normal(k, shape, dtype)
            layer[name] = value
        out.append(layer)
    return tuple(out)
