"""Real-image on-chip train smoke (VERDICT r4 item 6).

Closes the last untested seam of the reference's `Loader -> train` path
(SURVEY.md §2.7 image loaders, §3.1): a REAL on-disk PNG class tree goes
through `ImageDirectoryLoader` (PIL decode -> threaded prefetch ->
device) into a fused narrow-AlexNet train step on whatever device jax
resolves (`JAX_PLATFORMS=cpu` for a host smoke), and the loss must
fall.

Usage: python tools/image_tree_smoke.py [epochs]
Prints one JSON line: {"first_train_err": ..., "last_train_err": ...,
"best_validation_err": ..., "fell": true, "epochs": ...,
"device_kind": ...}.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_tree(base: str, n_classes: int = 4, per_class: int = 96,
               hw: int = 72) -> str:
    """Solid-color+noise PNG classes: trivially learnable, real decode.
    The geometry is part of the directory name so a parameter change
    can never silently reuse a stale cached tree."""
    from PIL import Image
    base = f"{base}_{n_classes}x{per_class}x{hw}"
    if os.path.exists(os.path.join(base, "class_0")):
        return base
    rng = np.random.RandomState(42)
    colors = rng.randint(40, 216, (n_classes, 3))
    for ci in range(n_classes):
        d = os.path.join(base, f"class_{ci}")
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            arr = np.clip(colors[ci][None, None, :]
                          + rng.randint(-30, 30, (hw, hw, 3)), 0,
                          255).astype(np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"img_{i}.png"))
    return base


def main() -> None:
    epochs = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    tree = build_tree("/tmp/veles_image_tree")

    import jax

    from veles_tpu import prng
    from veles_tpu.loader.image import ImageDirectoryLoader
    from veles_tpu.samples.alexnet import alexnet_layers
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    prng.seed_all(1234)
    loader = ImageDirectoryLoader(
        data_path=tree, size_hw=(67, 67), n_validation=32,
        minibatch_size=32, shuffle_train=True, prefetch=3, n_workers=2,
        hflip=True)
    wf = StandardWorkflow(
        layers=alexnet_layers(4, width_mult=0.125, fc_width=128,
                              init="scaled"),
        loader=loader, loss="softmax", n_classes=4,
        decision_config={"max_epochs": epochs, "fail_iterations": 999},
        gd_config={"learning_rate": 0.05, "gradient_moment": 0.9},
        name="ImageTreeSmoke")
    # the fused path: decode/prefetch on host threads, one XLA dispatch
    # per minibatch on device — exactly the production AlexNet shape
    wf.initialize(device=None)
    wf.run_fused(compute_dtype="bfloat16")

    hist = wf.decision.history
    first, last = hist[0]["train_err"], hist[-1]["train_err"]
    best = wf.decision.best_validation_err
    # learned = train error fell across the run, or validation clearly
    # beats chance (random = 3/4 of the 32 validation rows wrong)
    learned = last < first or best < 0.6 * 32
    print(json.dumps({
        "first_train_err": first, "last_train_err": last,
        "best_validation_err": best,
        "fell": learned,
        "epochs": len(hist),
        "device_kind": jax.devices()[0].device_kind,
    }))
    assert learned, hist


if __name__ == "__main__":
    main()
