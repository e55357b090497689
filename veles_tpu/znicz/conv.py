"""Convolutional forward units.

Parity: reference `veles/znicz/conv.py` — `Conv` (linear), `ConvTanh`,
`ConvRELU` (softplus flavor), `ConvStrictRELU`, `ConvSigmoid`; stride /
padding "sliding window" semantics, implicit-GEMM kernels (SURVEY.md §2.8).

TPU-first: layouts are NHWC/HWIO (what XLA tiles best onto the MXU) and the
whole conv+bias+activation is one jitted `lax.conv_general_dilated` call —
the reference's hand-blocked OpenCL/CUDA implicit-GEMM kernels have no
analog here by design.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import numpy as np

from veles_tpu.ops import reference as ref
from veles_tpu.ops import variants
from veles_tpu.ops import xla as ox
from veles_tpu.znicz.nn_units import Forward


class Conv(Forward):
    """y = act(conv2d(x, W) + b); x: (N,H,W,C), W: (ky,kx,C,n_kernels)."""

    activation = "linear"

    #: lowering-variant registry op for the strided thin-channel stem
    #: decision (candidates "direct" | "s2d"); consulted only when the
    #: layer's s2d knob is "auto" — explicit "on"/"off" stays a
    #: per-layer override, exactly like MaxPooling's `lowering` key.
    variant_op = "conv_stem"

    def __init__(self, workflow=None, n_kernels: int = 16,
                 kx: int = 3, ky: int = 3,
                 stride: Tuple[int, int] = (1, 1),
                 padding: Tuple[int, int] = (0, 0),
                 s2d: str = "auto",
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.n_kernels = n_kernels
        self.kx = kx
        self.ky = ky
        self.stride = tuple(stride)
        self.padding = tuple(padding)
        #: space-to-depth rewrite for thin-channel strided stems
        #: (ops.xla.conv2d_space_to_depth — exact, MXU-tile-friendly):
        #: "auto" = on when stride is square >1 and cin < 8; "on"/"off"
        #: force. Numerics identical either way (equivalence-tested).
        if s2d not in ("off", "on", "auto"):
            raise ValueError(f"s2d must be 'off'|'on'|'auto', got {s2d!r}")
        if s2d == "on" and not (self.stride[0] == self.stride[1]
                                and self.stride[0] > 1):
            raise ValueError(
                f"s2d='on' needs a square stride > 1 (got "
                f"{self.stride}): the rewrite repacks stride blocks")
        self.s2d = s2d

    def _s2d_applicable(self, cin: int) -> bool:
        """The auto heuristic's applicability test: a square-strided
        thin-channel stem (cin < 8 fills under 8/128 of an MXU tile)."""
        sy, sx = self.stride
        return sy == sx and sy > 1 and cin < 8

    def _use_s2d(self, cin: int) -> bool:
        if self.s2d == "on":
            return True         # applicability validated in __init__
        if self.s2d == "off":
            return False
        # "auto": the registry owns the decision for applicable stems
        # (default "s2d" — the r4 on-chip winner; tools/autotune.py can
        # re-measure and flip it per device/shape). A GENERATED winner
        # (gen[pack=..,acc=..], ops.templates) carries its packing in
        # the pack axis — the fused path consumes the full variant
        # apply; this boolean serves the granular xla_init path.
        if not self._s2d_applicable(cin):
            return False
        name = variants.resolve("conv_stem", unit=self).name
        if name in ("s2d", "direct"):
            return name == "s2d"
        from veles_tpu.ops import templates
        for t in templates.templates_for("conv_stem"):
            cfg = t.parse(name)
            if cfg is not None:
                return cfg.get("pack") == "s2d"
        return False

    def variant_effective(self):
        """The conv_stem lowering THIS layer actually traces, for
        variant_table() reporting: the per-layer s2d="on"/"off" override
        bypasses the registry, and an auto layer the rewrite can't apply
        to (stride 1 / wide cin) traces direct regardless of the
        selection — reporting the raw registry resolution for those
        would name a variant the step never traced. None = this layer
        carries no stem decision worth reporting. An `epi=lrn` winner
        reports its epi=none TWIN here: this method serves UNCLAIMED
        layers (FusedTrainStep skips claimed pairs and reports them
        itself), and an unclaimed stem passes no epilogue — the traced
        program is the epilogue-less one (the attention drop=0-twin
        rule)."""
        if self.s2d == "on":
            return "s2d"
        if self.s2d == "off":
            return "direct"
        if not self.input or not self._s2d_applicable(self.input.shape[-1]):
            return None
        name = variants.resolve("conv_stem", unit=self).name
        from veles_tpu.ops import templates
        if templates.fusion_config("conv_stem", name) is not None:
            for t in templates.templates_for("conv_stem"):
                cfg = t.parse(name)
                if cfg is not None and t.fuse_axis is not None:
                    return t.name({**cfg, t.fuse_axis: "none"})
        return name

    def variant_signature(self):
        """Tunable only when s2d='auto' AND the rewrite applies here."""
        if self.s2d != "auto" or not self.input \
                or not self._s2d_applicable(self.input.shape[-1]):
            return None
        # batch dim excluded: tune-then-inherit across batch sizes
        return {"sample_shape": list(self.input.shape[1:]),
                "dtype": str(np.asarray(self.input.mem).dtype),
                "params": {"n_kernels": self.n_kernels,
                           "kx": self.kx, "ky": self.ky,
                           "stride": list(self.stride),
                           "padding": list(self.padding),
                           "activation": self.activation}}

    def output_hw(self) -> Tuple[int, int]:
        _, h, w, _ = self.input.shape
        sy, sx = self.stride
        ph, pw = self.padding
        return ((h + 2 * ph - self.ky) // sy + 1,
                (w + 2 * pw - self.kx) // sx + 1)

    def initialize(self, device=None, **kwargs: Any):
        if not self.input:
            return False
        n, h, w, c = self.input.shape
        fan_in = self.kx * self.ky * c
        self.init_params((self.ky, self.kx, c, self.n_kernels), fan_in)
        oh, ow = self.output_hw()
        if not self.output or self.output.shape != (n, oh, ow, self.n_kernels):
            self.output.reset(np.zeros((n, oh, ow, self.n_kernels),
                                       np.float32))
        return super().initialize(device=device, **kwargs)

    def xla_init(self):
        self._fn = self.jit(partial(
            ox.conv2d_forward, stride=self.stride, padding=self.padding,
            activation=self.activation,
            s2d=self._use_s2d(self.input.shape[-1])))
        return None

    def fused_apply(self, params, x, *, key=None, train=True):
        if self.s2d == "auto" and self._s2d_applicable(x.shape[-1]):
            # the registry owns auto-mode applicable stems END TO END:
            # a generated winner's extra axes (the f32-accumulator
            # pin) trace here, not just its packing bit. Hand-written
            # names resolve to exactly the previous lowering.
            v = variants.resolve("conv_stem", unit=self)
            return v.apply(x, params["weights"], params["bias"],
                           self.stride, self.padding, self.activation)
        return ox.conv2d_forward(x, params["weights"], params["bias"],
                                 self.stride, self.padding,
                                 self.activation,
                                 s2d=self._use_s2d(x.shape[-1]))

    def numpy_run(self) -> None:
        self.output.mem = ref.conv2d_forward(
            self.input.mem, self.weights.mem, self.bias.mem,
            self.stride, self.padding, self.activation)

    def xla_run(self) -> None:
        d = self.device
        self.output.set_devmem(self._fn(
            self.input.devmem(d), self.weights.devmem(d),
            self.bias.devmem(d)))


class ConvTanh(Conv):
    activation = "tanh"


class ConvRELU(Conv):
    activation = "relu"


class ConvStrictRELU(Conv):
    activation = "strictrelu"


class ConvSigmoid(Conv):
    activation = "sigmoid"


# -- layer-type registration (import-time side effect; see standard_workflow
#    docstring for the cycle-avoidance rationale) -----------------------------
from veles_tpu.znicz import standard_workflow as _sw  # noqa: E402

_sw.LAYER_TYPES.update({
    "conv": Conv,
    "conv_tanh": ConvTanh,
    "conv_relu": ConvRELU,
    "conv_strictrelu": ConvStrictRELU,
    "conv_sigmoid": ConvSigmoid,
})
