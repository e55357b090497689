"""Per-device memory accounting: the measured side of every memory
claim (the ZeRO optimizer-state cut, feed wire savings, batch sizing).

Two sources, both cheap and safe to sample at epoch boundaries:

- `jax.live_arrays()` — every live jax.Array this process holds,
  attributed per device through its addressable shards. Backend-
  independent (works on the CPU test mesh), measures WHAT THE PROGRAM
  KEEPS, not allocator internals.
- `device.memory_stats()` — the allocator's own view where the backend
  provides one (TPU: bytes_in_use / peak_bytes_in_use). The peak is the
  number OOMs are made of; absent on CPU.

`device_memory_stats()` returns a compact JSON-able dict that rides
bench records, the device feed's heartbeat payload and the supervisor's
exit report — so "ZeRO cut optimizer memory N×" is a recorded
measurement, not a claim. Never initializes jax: a jax-free process
(the resilience supervisor) gets None and embeds nothing.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, Optional

#: one-shot flag for the private-probe warning in device_memory_stats
_PROBE_WARNED = False


def _attribute(arrays):
    """({device_id: bytes}, n_counted) over `arrays` through their
    addressable shards — the ONE accounting rule every per-device
    memory number in the codebase goes through (live-array snapshots
    here, FusedTrainStep.optimizer_state_bytes, bench records), so the
    ledgers can never silently disagree. An array that fails shard
    inspection (deleted, donated mid-flight etc.) is skipped from BOTH
    the bytes and the count."""
    out: Dict[int, int] = {}
    n = 0
    for a in arrays:
        try:
            if getattr(a, "is_deleted", lambda: False)():
                continue
            for sh in a.addressable_shards:
                d = sh.device.id
                out[d] = out.get(d, 0) + int(sh.data.nbytes)
            n += 1
        except Exception:  # noqa: BLE001 — one odd array never costs
            continue       # the caller's snapshot
    return out, n


def bytes_per_device(arrays) -> Dict[int, int]:
    """{device_id: bytes} attribution of `arrays` (see _attribute)."""
    return _attribute(arrays)[0]


def _backend_ready() -> bool:
    """True when jax is imported AND a backend has been created — the
    shared never-initializes guard (see device_memory_stats)."""
    if "jax" not in sys.modules:
        return False
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None or not hasattr(xb, "_backends"):
        return False
    return bool(xb._backends)


def refuse_spawn_if_chip_held(what: str) -> None:
    """A chip belongs to ONE process at a time: once this process has
    created an accelerator backend it holds the chip, and a child
    process that needs it fails or hangs. Parents that fan work out to
    child processes (Ensemble.train(parallel=True), genetics workers)
    call this before they spawn; a CPU backend, or no backend yet, is
    fine."""
    if not _backend_ready():
        return
    import jax
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"{what}: this process has initialised the {platform!r} "
            "backend and holds the chip — a child process that needs it "
            "would fail or hang. Spawn before touching jax, run the "
            "work serially in this process, or lease it to other hosts "
            "over the cluster queue (-l/-m)")


def device_memory_limits() -> Optional[Dict[str, int]]:
    """{device_id: bytes_limit} where the backend's allocator reports
    one (TPU) — the denominator of every static-HBM-model comparison
    (analysis pass 6: the Launcher pre-flight, --verify-workflow=
    resources, the serving capacity hint). None on CPU (no allocator
    limit) and in backendless processes; same never-initializes
    contract as device_memory_stats."""
    if not _backend_ready():
        return None
    import jax
    out: Dict[str, int] = {}
    try:
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — backendless process: no limits
        return None
    for dev in devices:
        try:
            ms = dev.memory_stats()
        except Exception:  # noqa: BLE001 — backend without stats
            ms = None
        if ms and "bytes_limit" in ms:
            out[str(dev.id)] = int(ms["bytes_limit"])
    return out or None


def device_memory_stats() -> Optional[Dict[str, Any]]:
    """Compact per-device memory snapshot, or None when jax is not
    even imported — or imported but no backend has been CREATED yet —
    in this process (never initializes a backend: live_arrays /
    local_devices would otherwise trigger initialization inside a
    heartbeat hook, stalling on a device another process holds)."""
    if "jax" not in sys.modules:
        return None
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None:
        return None     # jax imported, bridge module never loaded
    if not hasattr(xb, "_backends"):
        # the initialized-probe is a PRIVATE jax attribute (no public
        # "is a backend created" API exists that doesn't create one) —
        # if a jax upgrade renames it, say so ONCE instead of silently
        # dropping every memory snapshot from bench records/heartbeats
        global _PROBE_WARNED
        if not _PROBE_WARNED:
            _PROBE_WARNED = True
            import logging
            logging.getLogger("veles.memstats").warning(
                "jax._src.xla_bridge._backends is gone (jax upgrade?) "
                "— cannot tell whether a backend exists without "
                "creating one; memory snapshots disabled")
        return None
    if not xb._backends:
        return None     # jax imported, backend never initialized
    import jax
    try:
        arrays = jax.live_arrays()
    except Exception:  # noqa: BLE001 — backendless process: no stats
        return None
    live, n = _attribute(arrays)
    out: Dict[str, Any] = {
        "n_live_arrays": n,
        "live_bytes": {str(d): b for d, b in sorted(live.items())},
        "live_bytes_max": max(live.values(), default=0),
    }
    peak: Dict[str, int] = {}
    in_use: Dict[str, int] = {}
    for dev in jax.local_devices():
        try:
            ms = dev.memory_stats()
        except Exception:  # noqa: BLE001 — backend without allocator stats
            ms = None
        if not ms:
            continue
        if "peak_bytes_in_use" in ms:
            peak[str(dev.id)] = int(ms["peak_bytes_in_use"])
        if "bytes_in_use" in ms:
            in_use[str(dev.id)] = int(ms["bytes_in_use"])
    if peak:
        out["peak_bytes"] = peak
        out["peak_bytes_max"] = max(peak.values())
    if in_use:
        out["bytes_in_use"] = in_use
    return out
