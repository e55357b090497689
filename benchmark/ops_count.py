"""Shapes, parameters and MXU operations of a configuration's layer list.

Computed from the configuration file alone (no program object), so that
no later PR can move the count. One training step needs, per sample:
2 x MACs of every conv and matmul forward, the same again for each weight
gradient, and the same again for each input gradient EXCEPT the first
trainable layer's (nobody computes the gradient of the images).
Elementwise work, LRN, pooling and dropout count nothing: the share
built from this is a share of the MXU peak and stays under 100 % by
construction.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

CONV = "conv_strictrelu"
FC = ("all2all_strictrelu", "softmax")
POOL = "max_pooling"
PARAMLESS = ("norm", "dropout", POOL)


def _exact(n: int, k: int, s: int, what: str) -> int:
    if (n - k) % s:
        raise ValueError(f"{what}: ({n} - {k}) is not a multiple of the "
                         f"stride {s}; the reference has no partial windows")
    return (n - k) // s + 1


def layer_table(config: Dict[str, Any]) -> List[Dict[str, Any]]:
    """One row per layer: type, input and output sample shapes, the shapes
    of its parameters and its forward MACs per sample."""
    shape: Tuple[int, ...] = tuple(config["input_shape"])
    rows = []
    for i, spec in enumerate(config["layers"]):
        kind = spec["type"]
        row = {"index": i, "type": kind, "in": shape, "params": {},
               "macs": 0}
        if kind == CONV:
            h, w, cin = shape
            ky, kx, co = spec["ky"], spec["kx"], spec["n_kernels"]
            (sy, sx), (py, px) = spec["stride"], spec["padding"]
            oh = (h + 2 * py - ky) // sy + 1
            ow = (w + 2 * px - kx) // sx + 1
            row["params"] = {"weights": (ky, kx, cin, co), "bias": (co,)}
            row["macs"] = oh * ow * ky * kx * cin * co
            shape = (oh, ow, co)
        elif kind == POOL:
            h, w, c = shape
            (ky, kx), (sy, sx) = spec["ksize"], spec["stride"]
            shape = (_exact(h, ky, sy, f"layer {i}"),
                     _exact(w, kx, sx, f"layer {i}"), c)
        elif kind in FC:
            fan_in = 1
            for d in shape:
                fan_in *= d
            out = int(spec["output_sample_shape"])
            row["params"] = {"weights": (fan_in, out), "bias": (out,)}
            row["macs"] = fan_in * out
            shape = (out,)
        elif kind not in PARAMLESS:
            raise ValueError(f"layer {i}: unknown type {kind!r}")
        row["out"] = shape
        rows.append(row)
    return rows


def n_params(config: Dict[str, Any]) -> int:
    total = 0
    for row in layer_table(config):
        for shp in row["params"].values():
            n = 1
            for d in shp:
                n *= d
            total += n
    return total


def forward_macs(config: Dict[str, Any]) -> int:
    return sum(r["macs"] for r in layer_table(config))


def train_flops_per_sample(config: Dict[str, Any]) -> int:
    """Forward + weight gradients + input gradients (not the first
    trainable layer's), 2 operations a MAC."""
    rows = [r for r in layer_table(config) if r["macs"]]
    total = 0
    for j, r in enumerate(rows):
        total += 2 * r["macs"] * (2 if j == 0 else 3)
    return total


def mxu_share_percent(flops_per_step: float, step_device_s: float,
                      peak_flops_per_s: float) -> float:
    return 100.0 * flops_per_step / (step_device_s * peak_flops_per_s)


def peak_for(peaks: Dict[str, Any], device_kind: str) -> Dict[str, float]:
    if device_kind not in peaks:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(k for k in peaks if k[0] != '_')})")
    return peaks[device_kind]


def shapes_of(config: Dict[str, Any]) -> Sequence[Dict[str, Tuple[int, ...]]]:
    """Parameter shapes per layer, in the order of the layer list (empty
    dict for a layer without parameters)."""
    return [r["params"] for r in layer_table(config)]
