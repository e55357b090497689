"""Fully-connected forward units.

Parity: reference `veles/znicz/all2all.py` — `All2All` (linear),
`All2AllTanh` (scaled LeCun tanh), `All2AllRELU` (softplus-style RELU),
`All2AllStrictRELU`, `All2AllSigmoid`, `All2AllSoftmax` (linear + fused
max-subtracted softmax; named in BASELINE.json:4).

TPU-first: the matmul + bias + activation is one jitted XLA function
(ops.xla.all2all_forward) hitting the MXU; the reference's BLOCK_SIZE-tuned
OpenCL/CUDA matmul kernels have no analog here by design.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Sequence, Union

import jax
import numpy as np

from veles_tpu.memory import Array
from veles_tpu.ops import reference as ref
from veles_tpu.ops import xla as ox
from veles_tpu.znicz.nn_units import Forward


class All2All(Forward):
    """y = act(x·W + b); W: (fan_in, units)."""

    activation = "linear"

    #: the mesh axis over which `fused_apply`'s backward gathers its
    #: operands to form the GLOBAL weight gradient (ops.xla.
    #: dense_gathered_grad), or None for plain autodiff. Set by
    #: FusedTrainStep._forward at trace time, as `seq_axis_name` and
    #: `ep_axis_name` are on the units that have them.
    grad_gather_axis_name = None

    def __init__(self, workflow=None,
                 output_sample_shape: Union[int, Sequence[int]] = 10,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        if isinstance(output_sample_shape, int):
            output_sample_shape = (output_sample_shape,)
        self.output_sample_shape = tuple(output_sample_shape)

    @property
    def n_output(self) -> int:
        return int(np.prod(self.output_sample_shape))

    def initialize(self, device=None, **kwargs: Any):
        if not self.input:
            return False  # deferred until the upstream unit allocates
        fan_in = int(np.prod(self.input.shape[1:]))
        self.init_params((fan_in, self.n_output), fan_in)
        n = self.input.shape[0]
        if not self.output or self.output.shape[0] != n:
            self.output.reset(np.zeros((n,) + self.output_sample_shape,
                                       np.float32))
        return super().initialize(device=device, **kwargs)

    def xla_init(self):
        self._fn = self.jit(partial(ox.all2all_forward,
                                    activation=self.activation))
        return None

    def fused_apply(self, params, x, *, key=None, train=True):
        y = ox.all2all_forward(x, params["weights"], params["bias"],
                               self.activation,
                               grad_gather_axis=self.grad_gather_axis_name)
        return y.reshape((-1,) + self.output_sample_shape)

    def numpy_run(self) -> None:
        self.output.mem = ref.all2all_forward(
            self.input.mem, self.weights.mem, self.bias.mem,
            self.activation).reshape((-1,) + self.output_sample_shape)

    def xla_run(self) -> None:
        d = self.device
        y = self._fn(self.input.devmem(d), self.weights.devmem(d),
                     self.bias.devmem(d))
        self.output.set_devmem(y.reshape((-1,) + self.output_sample_shape))


class All2AllTanh(All2All):
    activation = "tanh"


class All2AllRELU(All2All):
    activation = "relu"


class All2AllStrictRELU(All2All):
    activation = "strictrelu"


class All2AllSigmoid(All2All):
    activation = "sigmoid"


class All2AllSoftmax(All2All):
    """Linear layer fused with max-subtracted softmax; `output` holds
    probabilities and `max_idx` the per-sample argmax (the reference kernel
    emitted it for the evaluator)."""

    activation = "linear"

    def __init__(self, workflow=None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.max_idx = Array()

    def xla_init(self):
        def fwd(x, w, b):
            probs = ox.all2all_softmax_forward(x, w, b)
            return probs, probs.argmax(axis=-1)

        self._fn = self.jit(fwd)
        return None

    def numpy_run(self) -> None:
        x2 = self.input.mem.reshape(len(self.input), -1)
        probs = ref.softmax(x2 @ self.weights.mem + self.bias.mem)
        self.output.mem = probs
        self.max_idx.mem = probs.argmax(axis=1)

    def xla_run(self) -> None:
        d = self.device
        probs, idx = self._fn(self.input.devmem(d), self.weights.devmem(d),
                              self.bias.devmem(d))
        self.output.set_devmem(probs)
        self.max_idx.set_devmem(idx)

    #: the fused train step takes logits and uses log-softmax CE directly
    #: (numerically identical gradient to the granular probs path).
    fused_emits_logits = True

    def fused_apply(self, params, x, *, key=None, train=True):
        return ox.all2all_forward(
            x, params["weights"], params["bias"],
            grad_gather_axis=self.grad_gather_axis_name)
