"""ImageNet AlexNet workflow — config 3 of BASELINE.json:7, the primary
benchmark config (north star: samples/sec/chip + all-reduce scaling).

Parity: the reference's znicz imagenet workflow (`veles/znicz/samples/`
AlexNet dirs): 5 conv blocks with LRN + overlapping max-pooling, two
4096-wide fully-connected layers with dropout, 1000-way softmax —
Krizhevsky et al. 2012 geometry expressed as a declarative layer list.

TPU-first: NHWC layouts; training runs through the fused sharded step
(`run_fused` / FusedTrainStep), bf16 compute on the MXU with f32 master
weights; data-parallel gradient all-reduce over the mesh "data" axis, and
optional tensor parallelism over "model" for the wide FC layers.

Data note: zero-egress environment — trains on the deterministic synthetic
ImageNet-shaped dataset (loader/synthetic.py) by default; set
`root.alexnet.loader.data_path` to a class-per-directory image tree and
create_workflow builds a prefetching ImageDirectoryLoader instead.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from veles_tpu.config import root
from veles_tpu.loader.synthetic import SyntheticClassifierLoader
from veles_tpu.znicz.standard_workflow import StandardWorkflow

root.alexnet.loader.minibatch_size = 128
root.alexnet.loader.n_validation = 128
root.alexnet.loader.n_train = 512
root.alexnet.loader.input_hw = 227
root.alexnet.loader.data_path = ""
#: set a directory to get improvement-gated snapshots (Snapshotter)
root.alexnet.snapshotter.directory = ""
root.alexnet.snapshotter.prefix = "alexnet"
root.alexnet.n_classes = 1000
root.alexnet.decision.max_epochs = 10
root.alexnet.decision.fail_iterations = 10
root.alexnet.gd.learning_rate = 0.01
root.alexnet.gd.gradient_moment = 0.9
root.alexnet.gd.weights_decay = 0.0005


def alexnet_layers(n_classes: int = 1000, width_mult: float = 1.0,
                   fc_width: int = 4096,
                   init: str = "reference") -> List[Dict[str, Any]]:
    """The Krizhevsky-2012 layer list (single-tower). `width_mult`/
    `fc_width` scale the net down for tiny-shape dry runs and tests.

    init="reference": the faithful fixed stddevs (0.01 conv / 0.005 fc,
    drawn with the unit's default uniform filling at matched std) —
    correct for the full 90-epoch recipe, but they VANISH at reduced
    width (activation std shrinks ~5x per layer; measured in
    tests/test_alexnet_functional.py's history). init="scaled": Kaiming
    √(2/fan_in) for the convs (fan-ins are static here) and the LeCun
    fan-in default for the FC tail (fan-in depends on input_hw, so it is
    left to init_params) — use for any width_mult < 1 run that must
    actually learn."""
    if init not in ("reference", "scaled"):
        raise ValueError(f"unknown init {init!r}")
    w = lambda n: max(int(n * width_mult), 1)  # noqa: E731

    def conv_std(kx: int, cin: int, ref: float) -> Optional[float]:
        if init == "reference":
            return ref
        return float(np.sqrt(2.0 / (kx * kx * cin)))

    fc_std = 0.005 if init == "reference" else None
    head_std = 0.01 if init == "reference" else None
    return [
        {"type": "conv_strictrelu", "n_kernels": w(96), "kx": 11, "ky": 11,
         "stride": (4, 4), "padding": (0, 0),
         "weights_stddev": conv_std(11, 3, 0.01)},
        {"type": "norm", "k": 2.0, "alpha": 1e-4, "beta": 0.75, "n": 5},
        {"type": "max_pooling", "ksize": (3, 3), "stride": (2, 2)},
        {"type": "conv_strictrelu", "n_kernels": w(256), "kx": 5, "ky": 5,
         "stride": (1, 1), "padding": (2, 2),
         "weights_stddev": conv_std(5, w(96), 0.01)},
        {"type": "norm", "k": 2.0, "alpha": 1e-4, "beta": 0.75, "n": 5},
        {"type": "max_pooling", "ksize": (3, 3), "stride": (2, 2)},
        {"type": "conv_strictrelu", "n_kernels": w(384), "kx": 3, "ky": 3,
         "stride": (1, 1), "padding": (1, 1),
         "weights_stddev": conv_std(3, w(256), 0.01)},
        {"type": "conv_strictrelu", "n_kernels": w(384), "kx": 3, "ky": 3,
         "stride": (1, 1), "padding": (1, 1),
         "weights_stddev": conv_std(3, w(384), 0.01)},
        {"type": "conv_strictrelu", "n_kernels": w(256), "kx": 3, "ky": 3,
         "stride": (1, 1), "padding": (1, 1),
         "weights_stddev": conv_std(3, w(384), 0.01)},
        {"type": "max_pooling", "ksize": (3, 3), "stride": (2, 2)},
        {"type": "all2all_strictrelu", "output_sample_shape": fc_width,
         "weights_stddev": fc_std},
        {"type": "dropout", "dropout_ratio": 0.5},
        {"type": "all2all_strictrelu", "output_sample_shape": fc_width,
         "weights_stddev": fc_std},
        {"type": "dropout", "dropout_ratio": 0.5},
        {"type": "softmax", "output_sample_shape": n_classes,
         "weights_stddev": head_std},
    ]


class AlexNetWorkflow(StandardWorkflow):
    """loader → 5 conv blocks → FC 4096×2 (dropout) → softmax 1000."""


def create_workflow(minibatch_size: Optional[int] = None,
                    input_hw: Optional[int] = None,
                    n_classes: Optional[int] = None,
                    width_mult: float = 1.0, fc_width: int = 4096,
                    n_train: Optional[int] = None,
                    n_validation: Optional[int] = None,
                    init: str = "reference") -> AlexNetWorkflow:
    cfg = root.alexnet
    mb = minibatch_size or cfg.loader.minibatch_size
    hw = input_hw or cfg.loader.input_hw
    nc = n_classes or cfg.n_classes
    if cfg.loader.get("data_path"):
        import os
        path = cfg.loader.data_path
        if os.path.exists(os.path.join(path, "manifest.json")):
            # packed memmap format (loader/memmap.py): the ImageNet-scale
            # path — pack once with pack_image_dataset, train many times
            from veles_tpu.loader.memmap import MemmapImageLoader
            loader = MemmapImageLoader(data_path=path, minibatch_size=mb)
        else:
            from veles_tpu.loader.image import ImageDirectoryLoader
            loader = ImageDirectoryLoader(
                data_path=path, size_hw=(hw, hw),
                n_validation=(n_validation if n_validation is not None
                              else cfg.loader.n_validation),
                minibatch_size=mb)
    else:
        loader = SyntheticClassifierLoader(
            n_classes=min(nc, 64),  # prototype count, not the head width
            sample_shape=(hw, hw, 3),
            n_validation=(n_validation if n_validation is not None
                          else cfg.loader.n_validation),
            n_train=n_train if n_train is not None else cfg.loader.n_train,
            minibatch_size=mb, noise=0.5)
    snap = cfg.snapshotter.to_dict()
    return AlexNetWorkflow(
        layers=alexnet_layers(nc, width_mult, fc_width, init=init),
        loader=loader, loss="softmax", n_classes=nc,
        decision_config=cfg.decision.to_dict(),
        gd_config=cfg.gd.to_dict(),
        snapshot_config=snap if snap.get("directory") else None,
        name="AlexNetWorkflow")


def run(load, main):
    load(create_workflow)
    main()
