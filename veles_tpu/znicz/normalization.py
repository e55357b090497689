"""Local response normalization units (AlexNet-style, across channels).

Parity: reference `veles/znicz/normalization.py` — forward + dedicated
backward kernel (SURVEY.md §2.8; "normalization" named in BASELINE.json:4).

TPU-first: the window sum is a banded matmul on the MXU and the backward
a closed-form custom VJP (`ops.xla.lrn_forward`). Inside the fused step
the lowering is the `lrn` registry op's: on a TPU one streaming Pallas
pass each way where the activation has a lane-dense view
(`pallas_kernels.lrn_view`), that XLA form for any other shape, backend
or partitioning.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import numpy as np

from veles_tpu.ops import reference as ref
from veles_tpu.ops import variants
from veles_tpu.ops import xla as ox
from veles_tpu.znicz.nn_units import Forward, GradientDescentBase, register_gd


class LRNormalizerForward(Forward):
    """y = x · (k + α·Σ_window x²)^(−β), window of n channels.

    Cross-op fusion (ISSUE 13): when the searched `lrn_maxpool` winner
    is a FUSED point and this unit's immediate successor in the fused
    chain is a max pooling (max flavor, no per-layer overrides on either
    side), this unit CLAIMS the pooling's work — FusedTrainStep traces
    the one-pass `lrn_maxpool_pallas` kernel for the pair and the
    pooling unit passes through for that trace (fusion_pairs() names the
    claim; variant_table reports the fused winner for both member ops).
    Symmetrically, a `conv_stem` winner with `epi=lrn` lets the
    PRECEDING stem conv claim THIS unit's work as its epilogue."""

    #: lowering-variant registry op this unit consults at fused trace
    #: time (banded_matmul | pallas_one_pass)
    variant_op = "lrn"

    def __init__(self, workflow=None, k: float = 2.0, alpha: float = 1e-4,
                 beta: float = 0.75, n: int = 5, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        if n % 2 == 0:
            # all four twins (XLA shifted-adds, Pallas, numpy reference,
            # C++ engine) use a ±n//2 window; even n would mean n+1 taps
            raise ValueError(f"LRN window n must be odd, got {n}")
        self.k = k
        self.alpha = alpha
        self.beta = beta
        self.n = n

    def param_arrays(self):
        return {}

    def initialize(self, device=None, **kwargs: Any):
        if not self.input:
            return False
        if not self.output or self.output.shape != self.input.shape:
            self.output.reset(np.zeros(self.input.shape, np.float32))
        return super().initialize(device=device, **kwargs)

    def xla_init(self):
        self._fn = self.jit(partial(ox.lrn_forward, k=self.k,
                                    alpha=self.alpha, beta=self.beta,
                                    n=self.n))
        return None

    def variant_signature(self):
        """Autotune cache-key payload (None = not tunable as configured).
        Batch dim excluded ON PURPOSE: winners tuned at one batch must
        apply when bench/training runs at another (tune-then-inherit)."""
        if getattr(self, "variant_override", None) is not None \
                or not self.input:
            return None
        return {"sample_shape": list(self.input.shape[1:]),
                "dtype": str(np.asarray(self.input.mem).dtype),
                "params": {"k": self.k, "alpha": self.alpha,
                           "beta": self.beta, "n": self.n}}

    def variant_effective(self):
        """The lrn lowering this unit traces, for variant_table(): the
        Pallas variant falls back by SHAPE inside `lrn_pallas`, so where
        the input has no lane-dense view the report names the XLA form
        that runs."""
        v = variants.resolve("lrn", unit=self)
        if v.pallas and self.input:
            from veles_tpu.ops import pallas_kernels as pk
            if not pk.lrn_view(self.input.shape,
                               np.asarray(self.input.mem).dtype.itemsize):
                return "banded_matmul"
        return v.name

    def fused_apply(self, params, x, *, key=None, train=True):
        v = variants.resolve("lrn", unit=self)
        return v.apply(x, k=self.k, alpha=self.alpha, beta=self.beta,
                       n=self.n)

    def numpy_run(self) -> None:
        self.output.mem = ref.lrn_forward(self.input.mem, self.k, self.alpha,
                                          self.beta, self.n)

    def xla_run(self) -> None:
        self.output.set_devmem(self._fn(self.input.devmem(self.device)))


@register_gd(LRNormalizerForward)
class LRNormalizerBackward(GradientDescentBase):
    def __init__(self, workflow=None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.k = 2.0
        self.alpha = 1e-4
        self.beta = 0.75
        self.n = 5

    def link_forward(self, fwd):
        self.k, self.alpha, self.beta, self.n = (fwd.k, fwd.alpha, fwd.beta,
                                                 fwd.n)
        self.link_attrs(fwd, "input", "output")
        return self

    def initialize(self, device=None, **kwargs: Any):
        if not self.err_output or not self.input:
            return False
        if not self.err_input or self.err_input.shape != self.input.shape:
            self.err_input.reset(np.zeros(self.input.shape, np.float32))
        return super().initialize(device=device, **kwargs)

    def xla_init(self):
        fwd = partial(ox.lrn_forward, k=self.k, alpha=self.alpha,
                      beta=self.beta, n=self.n)

        def step(x, err_y):
            _, vjp = jax.vjp(fwd, x)
            (err_x,) = vjp(err_y)
            return err_x

        self._fn = self.jit(step)
        return None

    def numpy_run(self) -> None:
        self.err_input.mem = ref.lrn_backward(
            self.input.mem, self.err_output.mem, self.k, self.alpha,
            self.beta, self.n)

    def xla_run(self) -> None:
        d = self.device
        self.err_input.set_devmem(
            self._fn(self.input.devmem(d), self.err_output.devmem(d)))


class InputNormalize(Forward):
    """On-device input normalization: y = x·scale + offset − mean_image.

    The ImageNet-rate input path (loader/memmap.py `emit="uint8"`): the
    loader ships RAW uint8 minibatches (4x less host conversion + H2D
    traffic) and this paramless leading layer does the float conversion,
    scaling and mean subtraction ON DEVICE, where it fuses into the first
    conv's HBM read. Works identically in granular and fused modes; the
    backward is the constant `scale` (affine transform)."""

    def __init__(self, workflow=None, scale: float = 1.0 / 127.5,
                 offset: float = -1.0, use_loader_mean: bool = True,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.scale = scale
        self.offset = offset
        self.use_loader_mean = use_loader_mean
        self._mean = None

    def param_arrays(self):
        return {}

    def link_loader(self, loader) -> None:
        self._loader = loader

    def initialize(self, device=None, **kwargs: Any):
        if not self.input:
            return False
        if self.use_loader_mean and self._mean is None:
            self._mean = getattr(getattr(self, "_loader", None),
                                 "mean_image", None)
        if not self.output or self.output.shape != self.input.shape:
            self.output.reset(np.zeros(self.input.shape, np.float32))
        return super().initialize(device=device, **kwargs)

    def _apply(self, params, x):
        import jax.numpy as jnp
        # keep an already-cast compute dtype (the fused step's bf16 entry
        # cast); only integer inputs are promoted
        dt = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.float32
        y = x.astype(dt) * jnp.asarray(self.scale, dt) \
            + jnp.asarray(self.offset, dt)
        if self._mean is not None:
            y = y - jnp.asarray(self._mean, dt)
        return y

    def fused_apply(self, params, x, *, key=None, train=True):
        return self._apply(params, x)

    def xla_init(self):
        self._fn = self.jit(lambda x: self._apply({}, x))
        return None

    def numpy_run(self) -> None:
        y = self.input.mem.astype(np.float32) * self.scale + self.offset
        if self._mean is not None:
            y = y - self._mean
        self.output.mem = y

    def xla_run(self) -> None:
        self.output.set_devmem(self._fn(self.input.devmem(self.device)))

    def __getstate__(self):
        d = super().__getstate__()
        d["_loader"] = None   # re-linked by link_loader on restore
        return d


from veles_tpu.znicz.nn_units import GradientDescentVJP, register_gd \
    # noqa: E402


@register_gd(InputNormalize)
class GDInputNormalize(GradientDescentVJP):
    """err_input = err_output · scale — the closed-form vjp of the affine
    transform, used directly because the granular input may be uint8
    (non-differentiable primal); paramless, so there is no update."""

    def xla_init(self):
        scale = self._fwd.scale
        self._fn = self.jit(lambda e: e * scale)
        return None

    def numpy_run(self) -> None:
        self.err_input.mem = self.err_output.mem * self._fwd.scale

    def xla_run(self) -> None:
        self.err_input.set_devmem(
            self._fn(self.err_output.devmem(self.device)))


# -- layer-type registration --------------------------------------------------
from veles_tpu.znicz import standard_workflow as _sw  # noqa: E402

_sw.LAYER_TYPES.update({
    "norm": LRNormalizerForward,
    "lrn": LRNormalizerForward,
    "input_normalize": InputNormalize,
})
