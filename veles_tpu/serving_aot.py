"""AOT-persisted serving executables: replica cold-start = O(load).

The template is arxiv 2203.04015's compilation flow (PAPERS.md): treat
inference as a SEPARATELY COMPILED, PERSISTED artifact, so a replica
restart pays deserialization (milliseconds) instead of an XLA compile
(tens of seconds for the flagship). The serving step is AOT-lowered and
compiled once per **(model, mesh, ring shape, quantize variant)**, the
executable serialized through ``jax.experimental.serialize_executable``
and stored next to the autotune cache, under the SAME persistence
discipline as ``ops/autotune.py``:

- an explicitly schema-tagged atomic-JSON index (``{"schema", "version",
  "entries"}``) plus one binary blob per executable, both written
  tmp-then-``os.replace`` so readers never see a torn file;
- a corrupt index, an unknown schema, a version skew, a missing or
  sha256-mismatched blob, or a deserialization failure each log ONE
  warning and degrade to recompile — never an error;
- the full build signature (model layer/param geometry, mesh axis sizes
  + device kind, ring shape, quantize variant, jax version) is hashed
  into the key AND stored verbatim in the entry: a key hit whose stored
  signature does not match the request (a stale or forged artifact — a
  mesh-geometry change being the canonical case) is REFUSED with a
  warning, and the caller recompiles.

Trust model: the cache directory is operator-local state with the same
trust level as the autotune cache and the XLA compile cache — a
serialized executable IS code, so never point ``VELES_SERVING_AOT_CACHE``
at a directory less trusted than the python environment itself. The
sha256 in the index detects corruption, not tampering (whoever can edit
the blob can edit the index).
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Dict, Optional, Tuple

from veles_tpu.logger import Logger

__all__ = ["ServingAotCache", "default_aot_path", "model_signature",
           "serve_signature"]

#: env override for the cache location (the autotune-cache convention)
AOT_CACHE_ENV = "VELES_SERVING_AOT_CACHE"


def default_aot_path() -> str:
    """Index path — alongside the compile and autotune caches by design
    (veles_tpu/caches.py: one fixed in-checkout directory to warm, ship
    or wipe)."""
    from veles_tpu.caches import cache_path
    return os.environ.get(AOT_CACHE_ENV) or cache_path("serving_aot.json")


def model_signature(workflow) -> list:
    """The model-geometry block of the serving signature: per-layer
    param shapes + dtypes, exactly as the AOT executable was compiled
    for. A hot-swap candidate must produce THIS list verbatim — it is
    the one geometry contract shared by the AOT cache key and the
    `InferenceServer.swap_params` pre-flight (a swap that changed it
    would feed the compiled program arrays it was not traced for)."""
    layers = []
    for u in getattr(workflow, "forwards", ()):
        layers.append({
            "type": type(u).__name__,
            "params": {k: [list(getattr(a, "shape", ()) or ()),
                           str(getattr(getattr(a, "mem", None), "dtype",
                                       "f32"))]
                       for k, a in u.param_arrays().items()},
        })
    return layers


def serve_signature(workflow, mesh, ring_slots: int, quantize: str,
                    softmax: bool, sample_shape,
                    variants: Optional[Dict[str, str]] = None
                    ) -> Dict[str, Any]:
    """The FULL build signature of one serving executable — everything
    that changes the compiled program: model layer/param geometry, mesh
    axes + device kind, ring shape, wire variant, the registry
    lowering selections the forward would trace (`variants` — a
    re-autotuned lowering must not serve a stale program), and the jax
    version. One rule for the cache key, the stored entry and the
    load-time verification, so a stale artifact can never be keyed
    back in under a changed geometry."""
    import jax
    layers = model_signature(workflow)
    if mesh is not None:
        mesh_sig: Optional[Dict[str, Any]] = {
            "axes": {k: int(v) for k, v in dict(mesh.shape).items()},
            "n_devices": int(mesh.devices.size),
            "device_kind": mesh.devices.flat[0].device_kind,
        }
    else:
        mesh_sig = None
    return {
        "model": layers,
        "mesh": mesh_sig,
        "ring_slots": int(ring_slots),
        "sample_shape": [int(s) for s in sample_shape],
        "quantize": str(quantize),
        "softmax": bool(softmax),
        "variants": dict(variants or {}),
        "jax": jax.__version__,
    }


class ServingAotCache(Logger):
    """On-disk (index JSON + blob-per-executable) cache of serialized
    serving executables. `load` returns a ready-to-call executable or
    None (miss / refused / corrupt — one warning, caller recompiles);
    `store` persists a freshly compiled one atomically."""

    SCHEMA = "veles-serving-aot"
    VERSION = 1

    def __init__(self, path: Optional[str] = None) -> None:
        super().__init__()
        self.path = path or default_aot_path()
        self._data: Optional[Dict[str, Any]] = None

    # -- index ---------------------------------------------------------------

    def _load_index(self) -> Dict[str, Any]:
        if self._data is not None:
            return self._data
        try:
            with open(self.path) as f:
                raw = json.load(f)
            entries = raw.get("entries")
            if raw.get("schema", self.SCHEMA) != self.SCHEMA \
                    or raw.get("version") != self.VERSION \
                    or not isinstance(entries, dict):
                raise ValueError(
                    f"schema/version skew (want {self.SCHEMA} "
                    f"v{self.VERSION}, file says "
                    f"{raw.get('schema', '<none>')} "
                    f"v{raw.get('version')})")
            self._data = entries
        except FileNotFoundError:
            self._data = {}
        except (OSError, ValueError, AttributeError) as e:
            # once per cache object (the autotune-cache precedent):
            # _data caches the empty dict so a server start never spams
            self.warning("serving AOT cache %s unreadable (%s): "
                         "recompiling", self.path, e)
            self._data = {}
        return self._data

    def _write_index(self, data: Dict[str, Any]) -> None:
        tmp = f"{self.path}.tmp.{os.getpid()}"
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"schema": self.SCHEMA, "version": self.VERSION,
                       "entries": data}, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)  # atomic: readers never see a torn file

    # -- keys ----------------------------------------------------------------

    @staticmethod
    def key(signature: Dict[str, Any]) -> str:
        blob = json.dumps(signature, sort_keys=True, default=str)
        h = hashlib.sha256(blob.encode()).hexdigest()[:16]
        kind = ((signature.get("mesh") or {}).get("device_kind")
                or "local")
        return f"{kind}|serve|{h}"

    def _blob_path(self, key: str) -> str:
        base = os.path.splitext(self.path)[0]
        return f"{base}.{key.replace('|', '_').replace('/', '_')}.bin"

    # -- load / store --------------------------------------------------------

    def load(self, signature: Dict[str, Any], in_tree, out_tree):
        """The persisted executable for `signature`, deserialized and
        ready to call — or None after ONE warning (miss is silent;
        refusal/corruption warn). `in_tree`/`out_tree` are the call
        treedefs, reconstructed by the caller from the host-side arg
        structure (deterministic — nothing opaque is persisted)."""
        key = self.key(signature)
        entry = self._load_index().get(key)
        if not isinstance(entry, dict):
            return None
        stored = entry.get("signature")
        if stored != signature:
            # a key collision, a hand-edited index, or — the canonical
            # case — an artifact persisted under a different mesh
            # geometry / ring shape than this server is starting with:
            # running it would execute a stale program. Refuse.
            self.warning(
                "serving AOT cache: refusing stale artifact %s — stored "
                "signature does not match this (model, mesh, ring) "
                "build; recompiling", key)
            return None
        blob_path = entry.get("file") or self._blob_path(key)
        try:
            with open(blob_path, "rb") as f:
                blob = f.read()
        except OSError as e:
            self.warning("serving AOT cache: blob %s unreadable (%s): "
                         "recompiling", blob_path, e)
            return None
        digest = hashlib.sha256(blob).hexdigest()
        if digest != entry.get("sha256"):
            self.warning(
                "serving AOT cache: blob %s corrupt (sha256 mismatch): "
                "recompiling", blob_path)
            return None
        try:
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            return deserialize_and_load(blob, in_tree, out_tree)
        except Exception as e:  # noqa: BLE001 — a bad artifact must
            # degrade to recompile, never fail the server start
            self.warning("serving AOT cache: deserialize of %s failed "
                         "(%s): recompiling", blob_path, e)
            return None

    def store(self, signature: Dict[str, Any], compiled) -> Optional[str]:
        """Serialize `compiled` and persist blob + index entry
        atomically. Returns the blob path, or None when this backend
        cannot serialize executables (logged once, the server still
        runs — it just pays compile on every start)."""
        try:
            from jax.experimental.serialize_executable import serialize
            blob, _, _ = serialize(compiled)
        except Exception as e:  # noqa: BLE001 — persistence is an
            # optimization; the freshly compiled executable still serves
            self.warning("serving AOT cache: this backend cannot "
                         "serialize executables (%s): cold starts will "
                         "recompile", e)
            return None
        key = self.key(signature)
        blob_path = self._blob_path(key)
        tmp = f"{blob_path}.tmp.{os.getpid()}"
        try:
            os.makedirs(os.path.dirname(blob_path) or ".", exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, blob_path)
            data = self._load_index()
            data[key] = {
                "signature": signature,
                "file": blob_path,
                "sha256": hashlib.sha256(blob).hexdigest(),
                "bytes": len(blob),
            }
            self._write_index(data)
        except OSError as e:
            self.warning("serving AOT cache: persist to %s failed (%s)",
                         blob_path, e)
            return None
        return blob_path


def call_trees(args: Tuple) -> Tuple[Any, Any]:
    """(in_tree, out_tree) for a serving executable called as
    ``fn(*args) -> one array`` — reconstructed deterministically from
    the host-side argument structure, so nothing opaque needs to ride
    the persisted artifact (the treedefs a deserialized executable
    needs are a pure function of the call signature)."""
    import jax
    import numpy as np
    return (jax.tree_util.tree_structure((args, {})),
            jax.tree_util.tree_structure(np.zeros(1)))
