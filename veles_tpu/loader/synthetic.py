"""Deterministic synthetic datasets.

This environment has zero egress, so the reference's downloadable datasets
(MNIST/CIFAR — reference `veles/znicz/loader/` pipelines) cannot be
fetched. Samples and functional tests therefore run on seeded synthetic
data that is *learnable* (class-prototype + noise), which preserves the
reference's test strategy — pinned seeds, asserted error trajectories
(SURVEY.md §4) — without the bytes. Loaders for on-disk data remain
available (`FullBatchLoader.bind_arrays`, image loaders) for real use.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from veles_tpu.loader.fullbatch import FullBatchLoader


def make_classification(n_per_class: Tuple[int, int, int], n_classes: int,
                        sample_shape: Tuple[int, ...], noise: float = 0.35,
                        seed: int = 4242) -> Tuple[np.ndarray, np.ndarray]:
    """Class-prototype + gaussian-noise dataset laid out test|valid|train.
    Deterministic for a given seed regardless of split sizes."""
    rng = np.random.RandomState(seed)
    protos = rng.randn(n_classes, *sample_shape).astype(np.float32)
    datas, labels = [], []
    for count in n_per_class:  # (test, validation, train) per class
        if count == 0:
            datas.append(np.empty((0,) + tuple(sample_shape), np.float32))
            labels.append(np.empty(0, np.int64))
            continue
        lab = np.tile(np.arange(n_classes), -(-count // n_classes))[:count]
        x = protos[lab] + noise * rng.randn(count, *sample_shape
                                            ).astype(np.float32)
        perm = rng.permutation(count)
        datas.append(x[perm].astype(np.float32))
        labels.append(lab[perm])
    return np.concatenate(datas), np.concatenate(labels)


class SyntheticClassifierLoader(FullBatchLoader):
    """FullBatchLoader over make_classification data (the stand-in for the
    reference's MNIST FullBatchLoader in samples and functional tests)."""

    def __init__(self, workflow=None, n_classes: int = 10,
                 sample_shape: Tuple[int, ...] = (28, 28),
                 n_test: int = 0, n_validation: int = 200,
                 n_train: int = 1000, noise: float = 0.35,
                 data_seed: int = 4242, autoencoder: bool = False,
                 **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.n_classes = n_classes
        self.sample_shape = tuple(sample_shape)
        self.split = (n_test, n_validation, n_train)
        self.noise = noise
        self.data_seed = data_seed
        #: targets = inputs (MSE reconstruction workflows)
        self.autoencoder = autoencoder

    def load_data(self) -> None:
        data, labels = make_classification(
            self.split, self.n_classes, self.sample_shape, self.noise,
            self.data_seed)
        self.bind_arrays(data, data.copy() if self.autoencoder else labels,
                         *self.split)


class RandomTokenLoader(FullBatchLoader):
    """Sequences of i.i.d. uniform token ids with, for every position,
    the next `n_targets` tokens as its targets: (N, S, n_targets), or
    (N, S) for one. The language-model samples' data (`samples/xing4.py`
    trains on the next and the next-next token, `samples/keye2.py` on the
    next)."""

    def __init__(self, workflow=None, vocab: int = 64, seq_len: int = 16,
                 n_train: int = 32, n_validation: int = 8,
                 n_targets: int = 2, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.vocab, self.seq_len, self.n_targets = vocab, seq_len, n_targets
        self.n_train, self.n_validation = n_train, n_validation

    def load_data(self) -> None:
        from veles_tpu import prng
        n, s = self.n_validation + self.n_train, self.seq_len
        ids = prng.get().fill_uniform((n, s + 2), 0, self.vocab,
                                      np.float32).astype(np.int32)
        ids = np.clip(ids, 0, self.vocab - 1)
        targets = np.stack([ids[:, 1 + i:s + 1 + i]
                            for i in range(self.n_targets)], axis=-1)
        if self.n_targets == 1:
            targets = targets[..., 0]
        self.bind_arrays(ids[:, :s], targets, 0, self.n_validation,
                         self.n_train)
