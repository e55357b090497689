"""Pooling forward units.

Parity: reference `veles/znicz/pooling.py` — `MaxPooling`, `MaxAbsPooling`
(keeps the signed value of the max-|·| element), `AvgPooling`,
`StochasticPooling` (Zeiler & Fergus sampling; device RNG). Edge windows
truncate (ceil-mode geometry), and max variants record flat argmax offsets
for the backward scatter (SURVEY.md §2.8).

TPU-first: forward is `lax.reduce_window` under jit; the backward in
gd_pooling uses `jax.vjp` (max/avg) or the recorded offsets (stochastic)
instead of the reference's hand-written scatter kernels.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Tuple

import numpy as np

from veles_tpu import prng
from veles_tpu.memory import Array
from veles_tpu.ops import reference as ref
from veles_tpu.ops import variants
from veles_tpu.ops import xla as ox
from veles_tpu.znicz.nn_units import Forward


class Pooling(Forward):
    """Common geometry: ksize (ky, kx), stride defaults to ksize
    (non-overlapping), ceil-mode output size. No trainable parameters —
    weights/bias Arrays stay empty."""

    def __init__(self, workflow=None, ksize: Tuple[int, int] = (2, 2),
                 stride: Optional[Tuple[int, int]] = None,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.ksize = tuple(ksize)
        self.stride = tuple(stride) if stride is not None else self.ksize

    def output_hw(self) -> Tuple[int, int]:
        _, h, w, _ = self.input.shape
        return ref._pool_windows(self.input.mem, *self.ksize, *self.stride)

    def param_arrays(self):
        return {}

    def initialize(self, device=None, **kwargs: Any):
        if not self.input:
            return False
        n, _, _, c = self.input.shape
        oh, ow = self.output_hw()
        if not self.output or self.output.shape != (n, oh, ow, c):
            self.output.reset(np.zeros((n, oh, ow, c), np.float32))
        return super().initialize(device=device, **kwargs)


class MaxPooling(Pooling):
    """Cross-op fusion note (ISSUE 13): when the searched `lrn_maxpool`
    winner is a FUSED point and this unit immediately follows an LRN in
    the fused chain (max flavor only — MaxAbsPooling never fuses — and
    no per-layer overrides on either side), the NORMALIZATION unit
    claims this unit's work: FusedTrainStep traces the one-pass fused
    kernel for the pair and this unit becomes a pass-through for that
    trace. Granular mode and every composed selection are untouched."""

    use_abs = False

    #: lowering-variant registry op (candidates: "reduce_window" —
    #: backward = select_and_scatter — or "slices" — max-fold over
    #: shifted strided slices, backward = selects + pads). The layer
    #: dict key "lowering" stays a per-layer override; the global
    #: choice is the registry's (tools/autotune.py).
    variant_op = "maxpool"

    #: class-level default so instances restored from PRE-registry
    #: pickled snapshots (whose __dict__ lacks the attribute) still
    #: resolve/report instead of raising AttributeError
    variant_override = None

    def __init__(self, workflow=None,
                 lowering: Optional[str] = None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        #: explicit per-layer lowering (wins over the registry selection)
        self.variant_override = None
        if lowering is not None:
            variants.get("maxpool", lowering)   # validates
            self.variant_override = lowering
        #: flat winner offsets into input (numpy path; backward scatter)
        self.input_offset = Array()

    @property
    def lowering(self) -> str:
        return self.variant_override or variants.effective("maxpool")

    def variant_signature(self):
        # batch dim excluded: tune-then-inherit across batch sizes
        if self.variant_override is not None or not self.input:
            return None
        return {"sample_shape": list(self.input.shape[1:]),
                "dtype": str(np.asarray(self.input.mem).dtype),
                "params": {"ksize": list(self.ksize),
                           "stride": list(self.stride),
                           "use_abs": bool(self.use_abs)}}

    def xla_init(self):
        self._fn = self.jit(partial(ox.maxpool_forward_with_idx,
                                    ksize=self.ksize, stride=self.stride,
                                    use_abs=self.use_abs))
        return None

    def fused_apply(self, params, x, *, key=None, train=True):
        v = variants.resolve("maxpool", unit=self)
        return v.apply(x, self.ksize, self.stride, self.use_abs)

    def numpy_run(self) -> None:
        y, idx = ref.maxpool_forward(self.input.mem, self.ksize, self.stride,
                                     self.use_abs)
        self.output.mem = y
        self.input_offset.mem = idx

    def xla_run(self) -> None:
        y, idx = self._fn(self.input.devmem(self.device))
        self.output.set_devmem(y)
        self.input_offset.set_devmem(idx)


class MaxAbsPooling(MaxPooling):
    use_abs = True


class AvgPooling(Pooling):
    def xla_init(self):
        self._fn = self.jit(partial(ox.avgpool_forward, ksize=self.ksize,
                                    stride=self.stride))
        return None

    def fused_apply(self, params, x, *, key=None, train=True):
        return ox.avgpool_forward(x, self.ksize, self.stride)

    def numpy_run(self) -> None:
        self.output.mem = ref.avgpool_forward(self.input.mem, self.ksize,
                                              self.stride)

    def xla_run(self) -> None:
        self.output.set_devmem(self._fn(self.input.devmem(self.device)))


class StochasticPooling(Pooling):
    """Sampling pooling; the winner offsets recorded at forward time drive
    the backward scatter on BOTH paths (unlike max pooling, re-running the
    forward in backward would re-sample)."""

    def __init__(self, workflow=None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.input_offset = Array()

    fused_needs_key = True

    def xla_init(self):
        self._fn = self.jit(partial(ox.stochastic_pool_forward_with_idx,
                                    ksize=self.ksize, stride=self.stride))
        return None

    def fused_apply(self, params, x, *, key=None, train=True):
        if not train:  # deterministic at eval: average pooling stand-in
            return ox.avgpool_forward(x, self.ksize, self.stride)
        return ox.stochastic_pool_forward(x, key, self.ksize, self.stride)

    def numpy_run(self) -> None:
        y, idx = ref.stochastic_pool_forward(
            self.input.mem, prng.get().state, self.ksize, self.stride)
        self.output.mem = y
        self.input_offset.mem = idx

    def xla_run(self) -> None:
        y, idx = self._fn(self.input.devmem(self.device),
                          prng.get().next_key())
        self.output.set_devmem(y)
        self.input_offset.set_devmem(idx)


# -- layer-type registration --------------------------------------------------
from veles_tpu.znicz import standard_workflow as _sw  # noqa: E402

_sw.LAYER_TYPES.update({
    "max_pooling": MaxPooling,
    "maxabs_pooling": MaxAbsPooling,
    "avg_pooling": AvgPooling,
    "stochastic_pooling": StochasticPooling,
})
