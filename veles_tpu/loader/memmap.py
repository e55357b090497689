"""Packed memmap dataset format + loader (the ImageNet-scale path).

Parity: the reference's ImageNet pipeline pre-processed images into an
on-disk intermediate consumed by the training loader (reference
`veles/znicz/loader/` imagenet pipeline, SURVEY.md §2.7) so the hot loop
never touches JPEG decode. TPU-first equivalent: `pack_image_dataset`
writes fixed-geometry uint8 tensors into SHARDED binary files plus a
JSON manifest (labels + mean image as sidecar .npy) — and
`MemmapImageLoader` memmaps the shards, gathers minibatch rows, and
converts uint8 -> normalized float32 on background prefetch threads.

Why this layout:
- uint8 on disk is 4x smaller than float32 and converts to bf16-ready
  float on the fly at memory bandwidth;
- shards keep single files <~1 GB so packing can stream and copies/
  rsyncs parallelize (each data-parallel HOST can also mount a subset);
- memmap gathers mean the OS page cache, not Python, decides residency —
  a second epoch reads RAM, and random access costs one page fault per
  row, not a decode.
"""

from __future__ import annotations

import json
import os
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from veles_tpu.loader.base import PrefetchingLoader

MANIFEST = "manifest.json"


def pack_arrays(out_dir: str, data_u8: np.ndarray, labels: np.ndarray,
                class_lengths: Sequence[int],
                shard_mb: float = 512.0,
                mean_image: Optional[np.ndarray] = None) -> str:
    """Write an already-materialized uint8 dataset (N, H, W, C) into the
    packed format. Layout: test|validation|train row order (the Loader
    class convention). Returns out_dir."""
    assert data_u8.dtype == np.uint8, data_u8.dtype
    assert len(data_u8) == sum(class_lengths)
    os.makedirs(out_dir, exist_ok=True)
    row_bytes = int(np.prod(data_u8.shape[1:]))
    rows_per_shard = max(1, int(shard_mb * 2 ** 20) // row_bytes)
    shards = []
    for si, lo in enumerate(range(0, len(data_u8), rows_per_shard)):
        rows = data_u8[lo:lo + rows_per_shard]
        fname = f"shard_{si:05d}.bin"
        rows.tofile(os.path.join(out_dir, fname))
        shards.append({"file": fname, "rows": int(len(rows))})
    np.save(os.path.join(out_dir, "labels.npy"), labels)
    if mean_image is not None:
        np.save(os.path.join(out_dir, "mean.npy"),
                mean_image.astype(np.float32))
    with open(os.path.join(out_dir, MANIFEST), "w") as f:
        json.dump({
            "sample_shape": list(data_u8.shape[1:]),
            "dtype": "uint8",
            "n_samples": int(len(data_u8)),
            "class_lengths": [int(c) for c in class_lengths],
            "shards": shards,
        }, f, indent=1)
    return out_dir


def pack_image_dataset(src_tree: str, out_dir: str,
                       size_hw: Tuple[int, int] = (227, 227),
                       n_validation: int = 0,
                       shard_mb: float = 512.0,
                       mean_sample: int = 64) -> str:
    """Decode a class-per-directory image tree once into the packed
    format (the pre-processing step of the reference's pipeline). Split
    and ordering match ImageDirectoryLoader.load_data. STREAMING: images
    are decoded shard-by-shard and written as they go, so resident memory
    is one shard (~shard_mb), never the dataset — ImageNet-scale packing
    on a normal host."""
    from veles_tpu import prng
    from veles_tpu.loader.image import decode_image, list_image_tree

    paths, labels, class_names = list_image_tree(src_tree)
    if not paths:
        raise FileNotFoundError(f"no images under {src_tree!r}")
    labels = np.asarray(labels, np.int64)
    n = len(paths)
    n_valid = min(n_validation, n - 1)
    perm = prng.get("image_split").permutation(n)
    order = np.concatenate([perm[:n_valid], perm[n_valid:]])
    h, w = size_hw
    os.makedirs(out_dir, exist_ok=True)
    row_bytes = h * w * 3
    rows_per_shard = max(1, int(shard_mb * 2 ** 20) // row_bytes)
    shards = []
    acc = np.zeros((h, w, 3), np.float64)
    mean_step = max(1, n // mean_sample)
    mean_cnt = 0
    for si, lo in enumerate(range(0, n, rows_per_shard)):
        chunk_idx = order[lo:lo + rows_per_shard]
        chunk = np.zeros((len(chunk_idx), h, w, 3), np.uint8)
        for j, src_i in enumerate(chunk_idx):
            img = decode_image(paths[int(src_i)], size_hw)  # [-1, 1] f32
            # rint, not truncation: float32 round-trip lands epsilon below
            # the integer for ~25% of values and astype would store v-1
            chunk[j] = np.rint((img + 1.0) * 127.5).astype(np.uint8)
            if (lo + j) % mean_step == 0 and mean_cnt < mean_sample:
                acc += img
                mean_cnt += 1
        fname = f"shard_{si:05d}.bin"
        chunk.tofile(os.path.join(out_dir, fname))
        shards.append({"file": fname, "rows": int(len(chunk))})
    np.save(os.path.join(out_dir, "labels.npy"), labels[order])
    np.save(os.path.join(out_dir, "mean.npy"),
            (acc / max(mean_cnt, 1)).astype(np.float32))
    with open(os.path.join(out_dir, MANIFEST), "w") as f:
        json.dump({
            "sample_shape": [h, w, 3], "dtype": "uint8",
            "n_samples": n,
            "class_lengths": [0, n_valid, n - n_valid],
            "shards": shards,
        }, f, indent=1)
    with open(os.path.join(out_dir, "classes.json"), "w") as f:
        json.dump(class_names, f)
    return out_dir


class MemmapImageLoader(PrefetchingLoader):
    """Minibatch loader over the packed format: memmapped uint8 shards,
    background-thread gather + uint8->float32 normalize on the
    PrefetchingLoader machinery (decode is replaced by a bandwidth-bound
    gather, so the host pipeline sustains AlexNet-rate input prep —
    measured by loader_throughput below)."""

    def __init__(self, workflow=None, data_path: str = "",
                 mean_normalize: bool = True, emit: str = "float32",
                 preload="auto", native: str = "auto",
                 n_workers: int = 2, prefetch: int = 2,
                 **kwargs: Any) -> None:
        super().__init__(workflow, n_workers=n_workers, prefetch=prefetch,
                         **kwargs)
        self.data_path = data_path
        self.mean_normalize = mean_normalize
        #: "auto": use the C++ multithreaded gather (native/host_gather
        #: .cpp) when the toolchain builds it — row copies + flip +
        #: normalize fan out over threads instead of numpy's single-
        #: threaded fancy-index path; "off" forces numpy (golden twin)
        self.native = native
        #: "float32" — normalized floats leave the host (golden path);
        #: "uint8"  — RAW bytes leave the host and normalization runs ON
        #: DEVICE (pair with a leading {"type": "input_normalize"}
        #: layer, znicz/normalization.py): 4x less host conversion work
        #: and 4x less H2D traffic — the ImageNet-rate configuration
        #: (see loader_throughput numbers in tests/test_memmap_loader.py)
        self.emit = emit
        #: load shards fully into RAM ("auto": when the packed set is
        #: < ~4 GB). memmap page-cache gathers top out near disk/page
        #: speed; RAM-resident uint8 gathers run at memcpy speed — the
        #: difference between trailing and outrunning the device step
        #: rate (loader_throughput numbers in the tests)
        self.preload = preload
        self.mean_image: Optional[np.ndarray] = None
        self._maps: List[np.memmap] = []
        self._shard_lo: Optional[np.ndarray] = None   # row offsets
        self._labels: Optional[np.ndarray] = None

    def load_data(self) -> None:
        with open(os.path.join(self.data_path, MANIFEST)) as f:
            man = json.load(f)
        shape = tuple(man["sample_shape"])
        row_bytes = int(np.prod(shape))
        total = man["n_samples"] * row_bytes
        preload = (total < 4 * 2 ** 30 if self.preload == "auto"
                   else bool(self.preload))
        self._maps = []
        offsets = [0]
        for sh in man["shards"]:
            path = os.path.join(self.data_path, sh["file"])
            if preload:
                m = np.fromfile(path, np.uint8).reshape(
                    (sh["rows"],) + shape)
            else:
                m = np.memmap(path, dtype=np.uint8, mode="r",
                              shape=(sh["rows"],) + shape)
            self._maps.append(m)
            offsets.append(offsets[-1] + sh["rows"])
        self._shard_lo = np.asarray(offsets)
        assert offsets[-1] == man["n_samples"]
        self._labels = np.load(os.path.join(self.data_path, "labels.npy"))
        mean_path = os.path.join(self.data_path, "mean.npy")
        if self.mean_normalize and os.path.exists(mean_path):
            self.mean_image = np.load(mean_path)
        self.class_lengths = list(man["class_lengths"])

    def train_labels(self):
        if self._labels is None or not np.issubdtype(
                self._labels.dtype, np.integer):
            return None
        return self._labels[self._train_base]

    def wire_format(self):
        """uint8-wire offer (loader/device_feed.py): the packed source
        IS uint8, so shipping raw bytes and running `_normalize`'s
        affine on device is lossless — always offered. The returned
        normalize spec mirrors `_normalize` (scale, offset, then the
        mean image); a step built with it can consume `emit="uint8"`
        batches with no `input_normalize` layer in the graph."""
        return {"emit": "uint8",
                "normalize": {"scale": 1.0 / 127.5, "offset": -1.0,
                              "mean": self.mean_image}}

    # -- gather ----------------------------------------------------------------

    def _use_native(self) -> bool:
        if self.native == "off":
            return False
        from veles_tpu import native_gather
        use = native_gather.available()
        if not getattr(self, "_gather_logged", False):
            self._gather_logged = True
            self.info("row gather: %s", "native (native/build/"
                      "libhostgather.so, built by this checkout)"
                      if use else "numpy")
        return use

    def _produce_rows(self, indices: np.ndarray, epoch: int):
        """Gather + seeded hflip + normalize, with augmentation applied
        to the RAW BYTES before normalization (a flipped training image
        must be normalized exactly like any other image — the mean image
        is not flipped with it; both emit modes and both gather paths
        agree on this order). The generic `_augment` post-hook is
        superseded, so it must not run again. Overriding THIS hook (not
        `_produce`) keeps the base's multi-host local-rows sharding and
        decode accounting."""
        return self._gather(indices, self._flip_mask(indices, epoch))

    def _produce_batch(self, indices: np.ndarray):
        return self._gather(indices, None)

    def _normalize(self, u8: np.ndarray) -> np.ndarray:
        x = u8.astype(np.float32) / 127.5 - 1.0
        if self.mean_image is not None:
            x -= self.mean_image
        return x

    def _gather(self, indices: np.ndarray,
                flip: Optional[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
        shape = self._maps[0].shape[1:]
        # flips are defined for (H, W) / (H, W, C) samples only — on any
        # other rank the native w/c derivation below would disagree with
        # the numpy twin's axis-1-of-sample flip, so turn them off
        if len(shape) not in (2, 3):
            flip = None
        shard = np.searchsorted(self._shard_lo, indices, "right") - 1
        rows = indices - self._shard_lo[shard]
        if self._use_native():
            from veles_tpu import native_gather
            row_bytes = int(np.prod(shape))
            bases = np.asarray([m.ctypes.data for m in self._maps],
                               np.int64)
            src = bases[shard] + rows.astype(np.int64) * row_bytes
            w, c = ((shape[1], shape[2]) if len(shape) == 3
                    else (shape[-1], 1))
            if self.emit == "uint8":
                out = np.empty((len(indices),) + shape, np.uint8)
                native_gather.gather_u8(src, row_bytes, out, flip, w, c)
            else:
                out = np.empty((len(indices),) + shape, np.float32)
                native_gather.gather_f32(src, row_bytes, out,
                                         self.mean_image, 127.5, -1.0,
                                         flip, w, c)
            return out, self._labels[indices]
        # numpy twin: vectorized per-shard fancy-index gather (C-level row
        # copies that release the GIL, so prefetch workers truly
        # parallelize), then scatter back to minibatch order
        u8 = np.empty((len(indices),) + shape, np.uint8)
        for s in np.unique(shard):
            sel = shard == s
            u8[sel] = self._maps[s][rows[sel]]
        if flip is not None and flip.any():
            u8[flip] = u8[flip, :, ::-1]
        if self.emit == "uint8":
            return u8, self._labels[indices]
        return self._normalize(u8), self._labels[indices]

    def __getstate__(self):
        d = super().__getstate__()
        d["_maps"] = []
        d.pop("_gather_logged", None)   # process-local: log once per run
        return d

    def __setstate__(self, d):
        super().__setstate__(d)   # sets the _restored marker
        if self.data_path and os.path.exists(
                os.path.join(self.data_path, MANIFEST)):
            self.load_data()   # re-establish memmaps after unpickle


def loader_throughput(loader, n_batches: int = 50) -> dict:
    """Host input-pipeline rate (samples/sec) over `n_batches` fills —
    the number to compare against the fused step's device rate: prefetch
    sustains overlap iff loader_rate >= device_rate."""
    import time
    loader.run()   # warm the prefetch window
    t0 = time.perf_counter()
    n = 0
    for _ in range(n_batches):
        loader.run()
        n += loader.minibatch_size
    dt = time.perf_counter() - t0
    out = {"samples_per_sec": n / dt, "batches": n_batches,
           "minibatch_size": loader.minibatch_size}
    # overlap observability: when a DeviceFeed wraps this loader, its
    # counters (bytes/batch, uint8 wire, time blocked on loader vs
    # device, lookahead health) ride along with the host rate
    feed = getattr(loader, "feed_stats", None)
    if feed:
        out["feed"] = dict(feed)
    return out
