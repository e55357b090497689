"""Device backends: the execution substrates units dispatch onto.

Parity: reference `veles/backends.py` (`Device` → `OpenCLDevice`/`CUDADevice`
/`NumpyDevice`, selected by config/flag, with per-device tuned kernel
parameters). TPU-first replacement: `XLADevice` wraps jax devices — kernel
compilation, tiling, and tuning all belong to XLA, so the per-device
parameter database of the reference has no equivalent here by design. The
`NumpyDevice` remains the golden reference backend for numeric tests.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import numpy as np

from veles_tpu.config import root
from veles_tpu.logger import Logger
from veles_tpu.telemetry import tracer as _tracer


def _devices():
    """`jax.devices()`: the program's own first device query starts the
    backend, unless a caller has started it first."""
    with _tracer.phase("setup.backend"):
        return jax.devices()


class Device(Logger):
    """Base device. `backend_name` selects which `<backend>_init`/
    `<backend>_run` methods AcceleratedUnit dispatches to."""

    backend_name = "abstract"

    def __init__(self) -> None:
        self.pid = None

    def sync(self) -> None:
        """Block until outstanding device work completes."""

    def __repr__(self) -> str:
        return f"<{type(self).__name__}>"


class NumpyDevice(Device):
    """Pure-host golden backend (parity: reference `NumpyDevice`)."""

    backend_name = "numpy"


class XLADevice(Device):
    """JAX/XLA device (TPU, or CPU when no accelerator is present).

    Holds the jax devices this process drives and, when more than one is
    used, the `jax.sharding.Mesh` the workflow's train step is sharded over
    (built by `veles_tpu.parallel`).
    """

    backend_name = "xla"

    def __init__(self, devices: Optional[Sequence[Any]] = None,
                 mesh: Optional["jax.sharding.Mesh"] = None) -> None:
        super().__init__()
        self.devices = list(devices) if devices is not None else _devices()
        self.mesh = mesh
        self.platform = self.devices[0].platform if self.devices else "cpu"

    @property
    def device(self):
        return self.devices[0]

    def sync(self) -> None:
        # Any tiny computation's block_until_ready flushes the async queue.
        jax.block_until_ready(jax.device_put(np.zeros(()), self.device))

    # jaxlib Device handles are not picklable; snapshots rebind to the
    # current process's devices on load (parity: reference snapshots are
    # device-free and re-acquire a Device at resume).
    def __getstate__(self):
        return {"mesh_axes": None if self.mesh is None
                else dict(zip(self.mesh.axis_names, self.mesh.devices.shape))}

    def __setstate__(self, state):
        self.pid = None
        self.devices = _devices()
        self.platform = self.devices[0].platform if self.devices else "cpu"
        self.mesh = None
        axes = state.get("mesh_axes")
        if axes:
            try:
                from veles_tpu.parallel.mesh import make_mesh
                self.mesh = make_mesh(axes)
            except Exception:
                self.warning("could not rebuild mesh %r at unpickle; "
                             "re-initialize the workflow's device", axes)

    def __repr__(self) -> str:
        mesh = f", mesh={self.mesh.shape}" if self.mesh is not None else ""
        return f"<XLADevice {self.platform}×{len(self.devices)}{mesh}>"


def make_device(backend: Optional[str] = None, **kwargs: Any) -> Device:
    """Factory honoring `root.common.engine.backend` (parity: reference
    backend selection by config/CLI flag)."""
    backend = backend or root.common.engine.backend
    if backend == "numpy":
        return NumpyDevice()
    if backend == "xla":
        return XLADevice(**kwargs)
    raise ValueError(f"unknown backend {backend!r} (expected xla|numpy)")
