"""Everything a run draws from `--seed`: weights, inputs, labels, keys.

The benchmark makes the weights and the inputs itself and hands them to
the program; the plain reference draws the same ones from the same seed.
Nothing here imports the program.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import numpy as np

from benchmark import ops_count

#: independent streams of one seed
STREAMS = {"weights": 1, "inputs": 2, "labels": 3, "dropout": 4, "pack": 5}


def host_seed(seed: int) -> int:
    """A seed the program's host generators take (numpy wants < 2**32,
    jax.random.key a signed 32-bit int): the driver's seeds run a little
    over 2**31."""
    return int(seed) % (2 ** 31 - 1)


def stream_key(seed: int, stream: str):
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, STREAMS[stream])


#: the classifier starts small, so that the first loss is near ln(classes)
#: and the first steps are smooth enough to compare across precisions
HEAD_SCALE = 0.1


def make_params(config: Dict[str, Any], key):
    """He-normal weights (the `softmax` head's a tenth of that) and zero
    biases in the master dtype from `stream_key(seed, "weights")`, as a
    tuple with one dict per layer (empty for a layer without parameters). Traceable: the driver calls it
    under one jit, on the device, with the key as an argument (a seed baked
    into the program would compile anew for every seed)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(config.get("master_dtype", "float32"))
    out = []
    for i, shapes in enumerate(ops_count.shapes_of(config)):
        if not shapes:
            out.append({})
            continue
        wshape = shapes["weights"]
        fan_in = math.prod(wshape[:-1])
        w = jax.random.normal(jax.random.fold_in(key, i), wshape, dtype)
        std = math.sqrt(2.0 / fan_in)
        if config["layers"][i]["type"] == "softmax":
            std *= HEAD_SCALE
        out.append({"weights": w * dtype.type(std),
                    "bias": jnp.zeros(shapes["bias"], dtype)})
    return tuple(out)


#: rms of a resident image: about that of mean-subtracted images scaled to
#: [-1, 1]. At unit rms the FC activations are large enough that three
#: steps at lr 0.01 on VGG-16's batch of 64 are chaotic (the loss went
#: 7.17 -> 4.94 -> 6.30 and the precisions could not be told apart, PR 23).
INPUT_RMS = 0.25


def make_resident_batch(config: Dict[str, Any], n: int, key_x, key_y):
    """(x, y): n float32 images of rms INPUT_RMS and n labels, from the
    seed's "inputs" and "labels" keys. Traceable."""
    import jax
    import jax.numpy as jnp
    x = INPUT_RMS * jax.random.normal(
        key_x, (n,) + tuple(config["input_shape"]), jnp.float32)
    y = jax.random.randint(key_y, (n,), 0, config["n_classes"], jnp.int32)
    return x, y


def make_pack(config: Dict[str, Any], n: int,
              seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(images uint8 (n, H, W, C), labels int64 (n,)) on the host."""
    rng = np.random.Generator(np.random.PCG64([int(seed), STREAMS["pack"]]))
    data = rng.integers(0, 256, (n,) + tuple(config["input_shape"]),
                        dtype=np.uint8)
    labels = rng.integers(0, config["n_classes"], n, dtype=np.int64)
    return data, labels


def row_tags(data: np.ndarray) -> np.ndarray:
    """A 64-bit tag per row from its first bytes: random rows collide with
    a chance of about n**2 / 2**64."""
    flat = data.reshape(len(data), -1)[:, :8]
    return np.ascontiguousarray(flat).view(np.uint64)[:, 0]
