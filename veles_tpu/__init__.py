"""veles_tpu — a TPU-native deep-learning workflow framework.

A from-scratch rebuild of the capabilities of the reference framework
(tfwu/veles, i.e. the Samsung VELES platform + Znicz NN engine): a
model/experiment is a *Workflow* — a graph of *Units* wired by control links
(`link_from`) and data links (`link_attrs`) — but the execution substrate is
JAX/XLA on TPU instead of hand-written OpenCL/CUDA kernels, and distributed
training is a synchronous ICI all-reduce inside a sharded, jit-compiled train
step instead of Twisted/ZeroMQ master–slave parameter averaging.

Layer map (mirrors SURVEY.md §1):
  L0 foundation      — config, logger, mutable (Bool/links), prng
  L1 device/memory   — backends (Device/XLADevice/NumpyDevice), memory (Array)
  L2 runtime         — units, workflow, accelerated_units, distributable
  L3 parallel        — mesh/sharding/collectives/ring-attention (parallel/)
  L4 services        — snapshotter, plotting, results
  L5 data            — loader/
  L6 NN engine       — znicz/ (ops in ops/, units in znicz/)
  L7 entry           — __main__, launcher, znicz/samples/

Reference parity citations use `veles/<path> (Symbol)` form: the reference
mount was empty at survey time (SURVEY.md §"Evidence & Provenance"), so no
file:line numbers exist to cite.
"""

from veles_tpu.telemetry import tracer as _tracer  # stdlib only

# set-up is measured from here (docs/OBSERVABILITY.md): the phase is this
# file, first line to last, and the gauge is what came before it
_tracer.mark_import_age()
with _tracer.phase("setup.import"):
    __version__ = "0.1.0"

    from veles_tpu.config import root, Config  # noqa: F401
    from veles_tpu.mutable import Bool  # noqa: F401
