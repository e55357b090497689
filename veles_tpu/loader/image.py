"""Image loaders: directory ingestion + ImageNet-style streaming pipeline.

Parity: reference `veles/loader/image.py` + `veles/znicz/loader/` imagenet
pipeline (SURVEY.md §2.7) — directory/file-list ingestion, scaling/cropping
to a fixed geometry, mean normalization, class-labeled from directory
names.

TPU-first: the decode path is a host-CPU concern; what matters for the
chip is that input preparation OVERLAPS device compute. `ImageDirectory
Loader` therefore prefetches the next minibatches on background threads
(schedule and train order are known ahead, across the epoch boundary too,
so lookahead is exact) —
the analog of the reference's jpegtran-cffi fast path, built on PIL +
a thread pool instead of a C extension.
"""

from __future__ import annotations

import os
from typing import Any, List, Optional, Tuple

import numpy as np

from veles_tpu.loader.base import PrefetchingLoader

IMAGE_EXTS = (".png", ".jpg", ".jpeg", ".bmp", ".gif", ".ppm")


def list_image_tree(root: str) -> Tuple[List[str], List[int], List[str]]:
    """Scan `<root>/<class_name>/*` -> (paths, labels, class_names)."""
    classes = sorted(d for d in os.listdir(root)
                     if os.path.isdir(os.path.join(root, d)))
    paths: List[str] = []
    labels: List[int] = []
    for ci, cname in enumerate(classes):
        cdir = os.path.join(root, cname)
        for fname in sorted(os.listdir(cdir)):
            if fname.lower().endswith(IMAGE_EXTS):
                paths.append(os.path.join(cdir, fname))
                labels.append(ci)
    return paths, labels, classes


def decode_image(path: str, size_hw: Tuple[int, int],
                 crop: str = "center") -> np.ndarray:
    """Decode + resize-shorter-side + crop to (H, W, 3) float32 in [-1, 1]
    (the reference's scale-then-crop ImageNet recipe)."""
    from PIL import Image
    h, w = size_hw
    with Image.open(path) as im:
        im = im.convert("RGB")
        iw, ih = im.size
        scale = max(h / ih, w / iw)
        nw, nh = max(w, int(round(iw * scale))), max(h, int(round(ih * scale)))
        im = im.resize((nw, nh))
        if crop == "random":
            from veles_tpu import prng
            gen = prng.get("image_crop")
            x0 = int(gen.randint(0, nw - w + 1))
            y0 = int(gen.randint(0, nh - h + 1))
        else:
            x0, y0 = (nw - w) // 2, (nh - h) // 2
        im = im.crop((x0, y0, x0 + w, y0 + h))
        arr = np.asarray(im, np.float32)
    return arr / 127.5 - 1.0


class ImageDirectoryLoader(PrefetchingLoader):
    """Streaming minibatch loader over a class-per-directory image tree.

    The dataset index (paths + labels) lives in memory; pixels are decoded
    per minibatch on the PrefetchingLoader's background threads, so decode
    overlaps device compute.
    """

    def __init__(self, workflow=None, data_path: str = "",
                 size_hw: Tuple[int, int] = (227, 227),
                 n_validation: int = 0,
                 mean_normalize: bool = True,
                 emit: str = "float32",
                 n_workers: int = 4, prefetch: int = 2,
                 **kwargs: Any) -> None:
        super().__init__(workflow, n_workers=n_workers, prefetch=prefetch,
                         **kwargs)
        self.data_path = data_path
        self.size_hw = tuple(size_hw)
        self.n_validation = n_validation
        self.mean_normalize = mean_normalize
        #: "float32" — decoded, mean-subtracted floats leave the host
        #: (the golden path); "uint8" — decoded pixels re-quantized to
        #: raw bytes (rint, the pack_image_dataset convention) and the
        #: float conversion + mean subtraction run ON DEVICE via the
        #: step's input_normalize prologue (wire_format): 4x less H2D
        #: traffic for ~0.4% quantization noise. Unlike the memmap
        #: loader (whose source IS uint8) the re-quantization is lossy,
        #: so the uint8 wire is opt-in here, never auto-negotiated.
        self.emit = emit
        self.paths: List[str] = []
        self.path_labels: np.ndarray = np.empty(0, np.int64)
        self.class_names: List[str] = []
        self.mean_image: Optional[np.ndarray] = None

    # -- dataset index -------------------------------------------------------

    def load_data(self) -> None:
        paths, labels, self.class_names = list_image_tree(self.data_path)
        if not paths:
            raise FileNotFoundError(
                f"no images under {self.data_path!r} (expect "
                "<root>/<class>/<image> layout)")
        labels = np.asarray(labels, np.int64)
        # deterministic split: last n_validation (stratified by stride)
        n = len(paths)
        n_valid = min(self.n_validation, n - 1)
        from veles_tpu import prng
        perm = prng.get("image_split").permutation(n)
        valid_idx = perm[:n_valid]
        train_idx = perm[n_valid:]
        order = np.concatenate([valid_idx, train_idx])
        self.paths = [paths[i] for i in order]
        self.path_labels = labels[order]
        self.class_lengths = [0, n_valid, n - n_valid]
        if self.mean_normalize:
            self._compute_mean(min(64, n))

    def _compute_mean(self, n_sample: int) -> None:
        """Mean image over a deterministic subset (the reference shipped a
        precomputed ImageNet mean; we derive one cheaply)."""
        step = max(1, len(self.paths) // n_sample)
        acc = np.zeros(self.size_hw + (3,), np.float64)
        cnt = 0
        for p in self.paths[::step][:n_sample]:
            acc += decode_image(p, self.size_hw)
            cnt += 1
        self.mean_image = (acc / max(cnt, 1)).astype(np.float32)

    # -- decode + prefetch ----------------------------------------------------

    def train_labels(self):
        """Class labels of the train split (pristine order) — enables
        `balanced_train` for imbalanced image directories."""
        if not len(self.path_labels):
            return None
        return self.path_labels[self._train_base]

    def _produce_rows(self, indices: np.ndarray, epoch: int):
        """Decode + seeded hflip + normalize, with augmentation applied
        to the RAW pixels BEFORE normalization — the memmap.py
        convention (a flipped training image is normalized exactly like
        any other; the mean image is not flipped with it), so the uint8
        wire and the float path train the same trajectory. Supersedes
        the base post-normalize `_augment` hook."""
        return self._decode_batch(indices, self._flip_mask(indices, epoch))

    def _produce_batch(self, indices: np.ndarray) -> Tuple[np.ndarray,
                                                           np.ndarray]:
        return self._decode_batch(indices, None)

    def _decode_batch(self, indices: np.ndarray, flip):
        h, w = self.size_hw
        x = np.zeros((len(indices), h, w, 3), np.float32)
        for i, idx in enumerate(indices):
            x[i] = decode_image(self.paths[int(idx)], self.size_hw)
        if flip is not None and flip.any():
            x[flip] = x[flip, :, ::-1]
        if self.emit == "uint8":
            # raw bytes leave the host; the mean moves into the step's
            # on-device prologue (wire_format) — subtracting it here
            # would corrupt the affine the device re-applies
            return (np.rint((x + 1.0) * 127.5).astype(np.uint8),
                    self.path_labels[indices])
        if self.mean_image is not None:
            x -= self.mean_image
        return x, self.path_labels[indices]

    def wire_format(self):
        """uint8-wire spec for the device feed — offered only when the
        operator already chose `emit="uint8"` (the re-quantization is
        lossy; see the `emit` docstring), so a step built from this
        loader normalizes on device without needing an explicit
        `input_normalize` layer in the graph."""
        if self.emit != "uint8":
            return None
        return {"emit": "uint8",
                "normalize": {"scale": 1.0 / 127.5, "offset": -1.0,
                              "mean": self.mean_image}}
