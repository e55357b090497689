"""Persistent autotuner over the lowering-variant registry, plus the
budgeted search over GENERATED candidates (ops.templates).

Two tiers, one cache:

1. Flat enumeration (PR 2): for each tunable op a workflow contains,
   time every registered hand-written candidate IN-GRAPH — a short
   donated `train_repeat` microbench of the whole fused step — pick
   the fastest.
2. Budgeted search (`budget=N` / CLI `--autotune-budget N`): ops with a
   registered `KernelTemplate` get coordinate descent over the template
   config space, seeded from the hand-written incumbents, spending a
   trial budget ordered by the per-op cost shares in LAYER_PROFILE.json
   (tools/layer_profile.py — where the roofline gap lives). Every
   generated candidate must carry a PASSING ops.reference equivalence
   record (ops.templates ledger) BEFORE it is timeable — `_timed_trial`
   refuses ungated candidates structurally. Trials route through the
   telemetry plane: `veles_autotune_trials_total{op,outcome}` and a
   per-trial span when `--trace` is live.

Decisions persist in an on-disk JSON cache keyed by (device_kind, op,
config-hash, compute_dtype), schema-versioned: a mismatched or corrupt
cache logs once and re-tunes, never errors. A cache hit selects the
stored winner with ZERO timing cost (generated winners re-materialize
from their name). On CPU the pallas candidates run in interpret mode, so
the whole subsystem — search included — is tier-1-testable without a
chip.

Entry points: `autotune_workflow(wf)` (= `StandardWorkflow.autotune()` =
CLI `--autotune [--autotune-budget N]`), `search_workflow` (budgeted
search incl. ops below the unit graph: flash_attn, sgd_update), and
`tools/autotune.py [--budget N]` for the flagship AlexNet step.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional

from veles_tpu.logger import Logger
from veles_tpu.ops import variants

__all__ = ["AutotuneCache", "autotune_workflow", "discover_tunables",
           "discover_fusions", "op_cache_key", "default_cache_path",
           "search_workflow", "search_op", "priority_order",
           "default_profile_path"]


def default_cache_path() -> str:
    from veles_tpu.caches import cache_path
    return (os.environ.get("VELES_AUTOTUNE_CACHE")
            or cache_path("autotune.json"))


class AutotuneCache(Logger):
    """On-disk JSON decision cache. Flat {key: record} mapping; records
    carry the winning variant plus the timings (and, for searched ops,
    the trial trace) that chose it. The file is explicitly schema-tagged
    (`{"schema": ..., "version": ...}`): a corrupt file, an unknown
    schema or a version skew (old cache under new code or vice versa)
    logs ONCE and behaves as empty — the tuner re-times and the next
    `put` rewrites the file atomically at the current version. Never an
    error."""

    SCHEMA = "veles-autotune"
    VERSION = 2

    def __init__(self, path: Optional[str] = None) -> None:
        super().__init__()
        self.path = path or default_cache_path()
        self._data: Optional[Dict[str, Any]] = None

    def _load(self) -> Dict[str, Any]:
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
            # once per cache object: _data caches the empty dict, so a
            # long tuning session doesn't spam this per get()
            self.warning("autotune cache %s unreadable (%s): re-tuning",
                         self.path, e)
            self._data = {}
        return self._data

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        rec = self._load().get(key)
        return dict(rec) if isinstance(rec, dict) else None

    def put(self, key: str, record: Dict[str, Any]) -> None:
        data = self._load()
        data[key] = record
        tmp = f"{self.path}.tmp.{os.getpid()}"
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"schema": self.SCHEMA, "version": self.VERSION,
                       "entries": data}, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)   # atomic: readers never see a torn file


def _resolve_compute_dtype(compute_dtype: Any) -> Any:
    """None means 'whatever the fused step would use' — resolve it the
    same way FusedTrainStep does (root.common.precision_type), so cache
    keys agree between a tuner passing None and a run passing None."""
    if compute_dtype is not None:
        return compute_dtype
    try:
        from veles_tpu.config import root
        pt = getattr(root.common, "precision_type", None)
    except Exception:  # noqa: BLE001
        pt = None
    return pt if pt and pt != "float32" else None


def op_cache_key(device_kind: str, op: str, signatures: List[Dict],
                 compute_dtype: Any = None) -> str:
    """One key per (device, op, workflow-op-configuration). The signature
    list covers EVERY instance of the op in the workflow (two LRN layers
    with different shapes are one joint decision — the registry selection
    is global per op), canonicalized so dict ordering can't split keys."""
    blob = json.dumps(signatures, sort_keys=True, default=str)
    h = hashlib.sha256(blob.encode()).hexdigest()[:16]
    cd = str(compute_dtype) if compute_dtype is not None else "f32"
    return f"{device_kind}|{op}|{cd}|{h}"


def discover_tunables(wf) -> Dict[str, List[Dict]]:
    """{op: [signature, ...]} for every tunable op present in the
    workflow. Units opt in by exposing `variant_signature()` (returning
    None when not tunable in this configuration — e.g. an explicit
    per-layer override, or a conv the s2d rewrite can't apply to)."""
    found: Dict[str, List[Dict]] = {}
    for u in getattr(wf, "forwards", ()):
        op = getattr(u, "variant_op", None)
        sig_fn = getattr(u, "variant_signature", None)
        if op is None or sig_fn is None:
            continue
        sig = sig_fn()
        if sig is not None:
            found.setdefault(op, []).append(sig)
    return found


def discover_fusions(wf) -> Dict[str, List[Dict]]:
    """{fusion_op: [signature, ...]} for every adjacent unit pair a
    fusion template could claim in this workflow (today: lrn followed by
    a max pooling — max flavor, no per-layer overrides on either side;
    the same gate FusedTrainStep.fusion_pairs applies at trace time).
    The signature joins BOTH members' variant signatures, so a fused
    winner's cache key covers the pair's full configuration."""
    found: Dict[str, List[Dict]] = {}
    fwds = list(getattr(wf, "forwards", ()))
    for a, b in zip(fwds, fwds[1:]):
        if getattr(a, "variant_op", None) != "lrn" \
                or getattr(b, "variant_op", None) != "maxpool" \
                or getattr(b, "use_abs", False):
            continue
        if getattr(a, "variant_override", None) is not None \
                or getattr(b, "variant_override", None) is not None:
            continue
        sig_a = a.variant_signature() if hasattr(a, "variant_signature") \
            else None
        sig_b = b.variant_signature() if hasattr(b, "variant_signature") \
            else None
        if sig_a is None or sig_b is None:
            continue
        found.setdefault("lrn_maxpool", []).append(
            {"lrn": sig_a, "maxpool": sig_b})
    return found


@contextlib.contextmanager
def _suspend_fusions(op: str):
    """While a MEMBER op's candidates time, any fusion op claiming it
    stands down: with a fused winner selected the pair is claimed and
    flipping the member's lowering would never change the traced
    program — every candidate would time within noise and a
    noise-picked "winner" would persist under the member's cache key.
    The member's decision is what the UNFUSED trace uses, so it is
    timed unfused; the fusion selection is restored even on error."""
    from veles_tpu.ops import templates
    suspended: Dict[str, str] = {}
    for fop in templates.template_ops():
        if op in templates.fusion_members(fop):
            prev = variants.selected(fop)
            if prev is not None:
                suspended[fop] = prev
            variants.clear_selection(fop)
    try:
        yield
    finally:
        for fop, prev in suspended.items():
            variants.select(fop, prev)


def _time_variant(wf, mesh, compute_dtype, steps: int, repeats: int,
                  batch: Optional[int]) -> float:
    """Seconds per training step for the CURRENT registry selection:
    build a fresh fused step (the selection is read at trace time), warm
    it, then time `train_repeat` — one dispatch per window, donated
    state, synthetic device-resident batch (nothing host-side in the
    measurement)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    loader = wf.loader
    b = int(batch or loader.minibatch_data.shape[0])
    in_shape = (b,) + tuple(loader.minibatch_data.shape[1:])
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.jit(lambda k: jax.random.normal(k, in_shape, jnp.float32))(k1)
    lbl = np.asarray(loader.minibatch_labels.mem)
    # flat (N*S,) sequence labels reveal tokens-per-sample as the row
    # blow-up over the loader's minibatch
    tokens = max(1, lbl.shape[0] // loader.minibatch_data.shape[0])
    if np.issubdtype(lbl.dtype, np.integer):
        hi = max(2, int(getattr(wf, "n_classes", 0) or lbl.max() + 1))
        y = jax.jit(lambda k: jax.random.randint(
            k, (b * tokens,), 0, hi))(k2)
    else:
        y = jax.jit(lambda k: jax.random.normal(
            k, (b,) + lbl.shape[1:], jnp.float32))(k2)

    step = wf.build_fused_step(mesh=mesh, compute_dtype=compute_dtype)
    state = step.init_state()
    state, _ = step.train_repeat(state, x, y, steps)   # compile + warm
    jax.block_until_ready(state)
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        state, _ = step.train_repeat(state, x, y, steps)
        jax.block_until_ready(state)
        best = min(best, time.perf_counter() - t0)
    return best / steps


def apply_cached(wf, *, compute_dtype=None,
                 cache: Optional[AutotuneCache] = None,
                 cache_path: Optional[str] = None) -> Dict[str, str]:
    """Select previously persisted winners for this workflow's tunable
    ops WITHOUT any timing (cache hits only; misses keep the current
    selection). The cheap way for bench/serving runs to inherit a
    tuning session's decisions — searched winners included: per op the
    SEARCHED key (workflow sigs + template space signature) is probed
    first, then the flat-tuner key, and the template-only ops below the
    unit graph (flash_attn, sgd_update) apply by their space key.
    Generated winners re-materialize from their cached name. Returns
    {op: variant} of what applied."""
    import jax

    from veles_tpu.ops import templates

    if not getattr(wf, "is_initialized", False):
        wf.initialize(device=None)
    cache = cache or AutotuneCache(cache_path)
    device_kind = jax.devices()[0].device_kind
    compute_dtype = _resolve_compute_dtype(compute_dtype)
    keys: Dict[str, List[str]] = {}
    # fusion ops (lrn_maxpool) key like workflow ops: their adjacent-
    # pair signatures join the probe so a searched fused winner applies
    tunables = dict(discover_tunables(wf))
    tunables.update(discover_fusions(wf))
    for op, sigs in tunables.items():
        ks = []
        space = templates.space_signature(op)
        if space:
            ks.append(op_cache_key(device_kind, op, sigs + space,
                                   compute_dtype))
        ks.append(op_cache_key(device_kind, op, sigs, compute_dtype))
        keys[op] = ks
    for op in templates.template_ops():
        sig_fn = EXTRA_OP_SIGS.get(op)
        base = sig_fn() if sig_fn else []
        keys.setdefault(op, [op_cache_key(
            device_kind, op, base + templates.space_signature(op),
            compute_dtype)])
    from veles_tpu.analysis import resources as vres
    applied: Dict[str, str] = {}
    for op, ks in keys.items():
        for key in ks:
            hit = cache.get(key)
            if hit is None or not variants.has(op, hit.get("variant")):
                continue
            # cache-refusal rule (ISSUE 14): a persisted winner whose
            # static VMEM footprint no longer fits THIS device_kind's
            # budget (the cache may have been tuned on a roomier chip,
            # or the budget overridden for a what-if run) is refused —
            # the current selection stands rather than selecting a
            # point that would fail at compile time on-chip
            ver = vres.kernel_verdict(
                op, hit["variant"],
                shapes=vres.shapes_from_signatures(op, tunables.get(op)),
                dtype=compute_dtype, device_kind=device_kind)
            if ver is not None:
                logging.getLogger("veles.autotune").warning(
                    "autotune cache: refusing %s winner %r — VMEM "
                    "footprint %d B exceeds the %s budget %d B",
                    op, hit["variant"], ver["footprint"], device_kind,
                    ver["vmem_budget"])
                continue
            variants.select(op, hit["variant"])
            applied[op] = hit["variant"]
            break
    return applied


def autotune_workflow(wf, *, mesh=None, compute_dtype=None,
                      steps: int = 4, repeats: int = 2,
                      batch: Optional[int] = None,
                      cache: Optional[AutotuneCache] = None,
                      cache_path: Optional[str] = None,
                      force: bool = False,
                      ops: Optional[List[str]] = None,
                      budget: Optional[int] = None,
                      profile_path: Optional[str] = None
                      ) -> Dict[str, Dict[str, Any]]:
    """Tune every tunable op the workflow contains; leave the winners
    selected in the registry; return a per-op report:

        {op: {"variant": name, "source": "cache"|"tuned"|"searched",
              "timings_s": {...}(tuned only), "key": cache-key}}

    Ops are tuned sequentially, each candidate timed with every OTHER op
    held at its current selection. `force=True` re-times cache hits.

    With `budget=N` (CLI `--autotune-budget N`), ops that have a
    registered template (ops.templates) switch from flat enumeration to
    the budgeted coordinate-descent search over GENERATED candidates,
    priority-ordered and budget-weighted by the per-op cost shares in
    LAYER_PROFILE.json; ops without a template keep the enumeration.
    """
    import jax

    if not getattr(wf, "is_initialized", False):
        wf.initialize(device=None)
    cache = cache or AutotuneCache(cache_path)
    device_kind = jax.devices()[0].device_kind
    compute_dtype = _resolve_compute_dtype(compute_dtype)
    tunables = discover_tunables(wf)
    if ops:
        tunables = {k: v for k, v in tunables.items() if k in ops}
    report: Dict[str, Dict[str, Any]] = {}
    searchable: List[str] = []
    if budget:
        from veles_tpu.ops import templates
        searchable = [op for op in tunables
                      if templates.templates_for(op)
                      and op in templates.CONTRACTS]
        if (not ops or "sgd_update" in ops) \
                and "sgd_update" in templates.CONTRACTS \
                and any(not getattr(g, "optimizer", "sgd") == "adam"
                        for g in getattr(wf, "gds", ())):
            # the fused step's SGD leg resolves the sgd_update registry
            # op (FusedTrainStep._sgd_variant), so its template space
            # belongs in this workflow's search even though no forward
            # unit names it — timed via the template microbench. An
            # explicit `ops` restriction that omits it still wins.
            searchable.append("sgd_update")
        if (not ops or "grad_reduce" in ops) \
                and "grad_reduce" in templates.CONTRACTS \
                and len(jax.devices()) > 1:
            # the dp-mode ZeRO update (on by default) resolves the
            # grad_reduce registry op, so its wire/geometry space rides
            # the budget too — microbench-timed over this host's link
            # geometry, cache-keyed by it (EXTRA_OP_SIGS). Skipped on a
            # single-device host (no axis to exchange over — the
            # microbench would time a degenerate identity) and under an
            # explicit `ops` restriction that omits it.
            searchable.append("grad_reduce")
        for fop in discover_fusions(wf):
            # cross-op fusion spaces (lrn_maxpool): searchable exactly
            # when the workflow contains a claimable adjacent pair —
            # timed IN-GRAPH (selecting a fused point changes what
            # FusedTrainStep traces for the pair)
            if (not ops or fop in ops) and fop in templates.CONTRACTS \
                    and fop not in searchable:
                searchable.append(fop)
    if searchable:
        # ONE search implementation: delegate the template-backed ops
        # to search_workflow (priority order, budget split, in-graph
        # timing) instead of re-implementing its loop here
        report.update(search_workflow(
            wf, ops=searchable, budget=budget, cache=cache,
            compute_dtype=compute_dtype, profile_path=profile_path,
            mesh=mesh, steps=steps, repeats=repeats, batch=batch,
            force=force))
    with _pallas_ctx():
        for op in sorted(set(tunables) - set(searchable)):
            key = op_cache_key(device_kind, op, tunables[op],
                               compute_dtype)
            hit = None if force else cache.get(key)
            if hit is not None and variants.has(op, hit.get("variant")):
                variants.select(op, hit["variant"])
                report[op] = {"variant": hit["variant"],
                              "source": "cache", "key": key}
                continue
            # the flat enumeration is the CLOSED hand-written set:
            # generated (template-materialized) variants only enter
            # through the budgeted search, never the enumeration — a
            # prior search in this process must not widen this path
            cands = [v.name for v in variants.variants_for(op)
                     if v.tunable and not v.generated
                     and (not v.pallas or variants.pallas_ok())]
            prev = variants.selected(op)
            timings: Dict[str, Any] = {}
            with _suspend_fusions(op):
                for name in cands:
                    variants.select(op, name)
                    try:
                        timings[name] = _time_variant(
                            wf, mesh, compute_dtype, steps, repeats,
                            batch)
                    except Exception as e:  # noqa: BLE001 — one broken
                        # candidate (e.g. a pallas kernel a backend
                        # rejects) must not abort the whole tune
                        timings[name] = f"error: {e!s:.200}"
            ok = {k: v for k, v in timings.items()
                  if isinstance(v, float)}
            if not ok:
                # nothing measurable: restore the pre-tune state
                if prev is None:
                    variants.clear_selection(op)
                else:
                    variants.select(op, prev)
                report[op] = {"variant": variants.effective(op),
                              "source": "error", "timings_s": timings,
                              "key": key}
                continue
            winner = min(ok, key=ok.get)
            variants.select(op, winner)
            rounded = {k: (round(v, 6) if isinstance(v, float) else v)
                       for k, v in timings.items()}
            cache.put(key, {"variant": winner, "timings_s": rounded,
                            "device_kind": device_kind,
                            "steps": steps, "tuned_at": time.time()})
            report[op] = {"variant": winner, "source": "tuned",
                          "timings_s": rounded, "key": key}
    return report


# ===========================================================================
# Budgeted search over generated candidates (ops.templates)
# ===========================================================================


def link_geometry_signature() -> List[Dict]:
    """Cache-key payload for cross-device collective ops (grad_reduce):
    the link geometry. A winner tuned on one (hosts x local) topology
    must not silently apply to another — the ISSUE-12 contract that the
    autotune cache is keyed by device/mesh shape for the collective
    family."""
    import jax

    from veles_tpu.ops import variants
    n = len(jax.devices())
    h, loc = variants.grad_reduce_geometry(n)
    return [{"link_geometry": {
        "n_devices": n, "n_processes": jax.process_count(),
        "hosts": h, "local": loc}}]


#: per-op extra cache-key signatures beyond the workflow's op configs —
#: consulted by search_workflow AND apply_cached so a searched winner's
#: key and a later run's probe can never disagree
EXTRA_OP_SIGS: Dict[str, Callable[[], List[Dict]]] = {
    "grad_reduce": link_geometry_signature,
}


def default_profile_path() -> str:
    return os.environ.get("VELES_LAYER_PROFILE_PATH",
                          "LAYER_PROFILE.json")


def priority_order(ops: List[str],
                   profile_path: Optional[str] = None
                   ) -> List[tuple]:
    """[(op, share), ...] most-expensive-first, from the per-op cost
    shares tools/layer_profile.py persists (LAYER_PROFILE.json, env
    VELES_LAYER_PROFILE_PATH; on chip the PR-7 `--profile-window`
    capture feeds the same file). Ops the profile doesn't name keep
    their relative order with share 0 — no profile degrades to the
    given order, never to an error. This is how the budget is spent on
    the ops that own the roofline gap."""
    shares: Dict[str, float] = {}
    path = profile_path or default_profile_path()
    try:
        with open(path) as f:
            prof = json.load(f)
        raw = prof.get("ops", {})
        shares = {str(k): float(v) for k, v in raw.items()
                  if isinstance(v, (int, float))}
    except (OSError, ValueError, AttributeError):
        pass

    def share_of(op: str) -> float:
        """A PURE fusion op (lrn_maxpool) is charged against the
        COMBINED share of its member ops — the profile attributes time
        per member (tools/layer_profile.py splits any fused kernel's
        time back), so the pair's candidate budget reflects everything
        a fused winner would replace."""
        from veles_tpu.ops import templates
        s = shares.get(op, 0.0)
        for m in templates.fusion_members(op):
            s += shares.get(m, 0.0)
        return s

    return sorted(((op, share_of(op)) for op in ops),
                  key=lambda kv: -kv[1])


def incumbent_floor(op: str) -> int:
    """Per-op minimum trials: every hand-written incumbent plus at
    least one generated point. Without this, an op with 2+ incumbents
    (flash_attn: xla_mha + pallas) at a zero profile share would spend
    its whole floor on incumbents and never probe its space."""
    hand = [v for v in variants.variants_for(op)
            if v.tunable and not v.generated]
    return len(hand) + 1


def allocate_budget(ordered: List[tuple], budget: int,
                    floors: Optional[Dict[str, int]] = None
                    ) -> Dict[str, int]:
    """Split a total trial budget across ops proportionally to their
    profile shares, with a per-op floor (`floors`, default 2; the
    search passes `incumbent_floor`) so a zero-share op still gets its
    incumbents timed AND at least one generated point probed."""
    if not ordered:
        return {}

    def floor_of(op: str) -> int:
        return max(1, (floors or {}).get(op, 2))

    total_share = sum(s for _, s in ordered)
    out: Dict[str, int] = {}
    remaining = budget - sum(floor_of(op) for op, _ in ordered)
    if remaining < 0:
        # budget too small to floor everyone: highest-share ops win
        left = budget
        for op, _ in ordered:
            out[op] = min(floor_of(op), left)
            left -= out[op]
        return out
    for op, share in ordered:
        frac = (share / total_share) if total_share > 0 \
            else 1.0 / len(ordered)
        out[op] = floor_of(op) + int(remaining * frac)
    # hand leftover integer-division trials to the highest-share op
    leak = budget - sum(out.values())
    if leak > 0:
        out[ordered[0][0]] += leak
    return out


def _trials_counter():
    """veles_autotune_trials_total{op,outcome} on the one PR-7 metrics
    registry; lazily bound (the search is not a hot path — velint's
    hot-metric rule does not apply here)."""
    from veles_tpu.telemetry import metrics as tm
    return tm.default_registry().counter(
        "veles_autotune_trials_total",
        "budgeted-search candidate evaluations by outcome "
        "(timed / equiv_fail / error / pruned)",
        labelnames=("op", "outcome"))


def _prune_verdict(op: str, template, cfg, shapes, compute_dtype,
                   vbudget: Optional[int]) -> Optional[Dict[str, Any]]:
    """The search's static-infeasibility pre-check (ISSUE 14,
    analysis/resources.py): None when the point fits (or no budget /
    footprint rule exists), else {"footprint", "vmem_budget"}. A
    module-level seam on purpose — the ledger-bypass property test
    monkeypatches it away and asserts `_timed_trial`'s independent
    re-check still refuses to time the point."""
    if vbudget is None or template.vmem_footprint is None:
        return None
    try:
        f = int(template.vmem_footprint(cfg, dict(shapes or {}),
                                        compute_dtype))
    except Exception:  # noqa: BLE001 — a broken rule must degrade to
        return None    # "unknown, don't prune", never abort the search
    if f > vbudget:
        return {"footprint": f, "vmem_budget": vbudget}
    return None


def _pallas_ctx():
    """How a tune/search runs its Pallas candidates: compiled on a TPU;
    on the CPU backend it ASKS for interpret mode (a functional proxy
    for the search mechanics, never a timing signal). The kernels never
    fall into interpret mode by themselves (pallas_kernels._interpret)."""
    import jax
    return (variants.pallas_interpret() if jax.default_backend() == "cpu"
            else contextlib.nullcontext())


def search_op(op: str, **kwargs) -> Dict[str, Any]:
    """`_search_op` under `_pallas_ctx()` — see there."""
    with _pallas_ctx():
        return _search_op(op, **kwargs)


def _search_op(op: str, *, budget: int,
              cache: Optional[AutotuneCache] = None,
              cache_path: Optional[str] = None,
              compute_dtype: Any = None,
              force: bool = False, repeats: int = 2,
              workflow_sigs: Optional[List[Dict]] = None,
              in_graph_timer: Optional[Callable[[], float]] = None,
              vmem_shapes: Optional[Dict[str, Any]] = None,
              vmem_budget: Optional[int] = None) -> Dict[str, Any]:
    """Budgeted coordinate-descent search over one op's candidate set:
    the hand-written tunable variants first (the incumbents), then the
    template config space, moving one axis at a time from the template
    seed. Every candidate is gated through the ops.reference equivalence
    ledger BEFORE timing — `_timed_trial` raises on an ungated name, so
    the search is structurally unable to time an unverified point.
    Winner is selected in the registry and persisted (with the full
    trial trace) under the same per-(device_kind, op, config-hash,
    compute_dtype) key family as the flat tuner.

    `in_graph_timer` times the CURRENT registry selection inside the
    caller's fused step (the PR-2 protocol — pass a closure over
    `_time_variant`); without one, the template's microbench times the
    candidate's `apply` directly (ops below the unit graph: flash_attn,
    sgd_update)."""
    import jax

    from veles_tpu.analysis import resources as vres
    from veles_tpu.ops import templates
    cache = cache or AutotuneCache(cache_path)
    device_kind = jax.devices()[0].device_kind
    compute_dtype = _resolve_compute_dtype(compute_dtype)
    sigs = list(workflow_sigs or []) + templates.space_signature(op)
    key = op_cache_key(device_kind, op, sigs, compute_dtype)
    hit = None if force else cache.get(key)
    if hit is not None and variants.has(op, hit.get("variant")):
        # the same cache-refusal rule as apply_cached (the budget is
        # NOT part of the cache key): a winner persisted under a
        # roomier budget must not short-circuit a tightened re-run —
        # fall through to the search, which prunes the point
        ver = vres.kernel_verdict(op, hit["variant"],
                                  shapes=vmem_shapes,
                                  dtype=compute_dtype,
                                  device_kind=device_kind,
                                  budget=vmem_budget)
        if ver is None:
            variants.select(op, hit["variant"])
            return {"variant": hit["variant"], "source": "cache",
                    "key": key, "trials": 0}
        logging.getLogger("veles.autotune").warning(
            "autotune cache: refusing %s winner %r — VMEM footprint "
            "%d B exceeds the %s budget %d B; re-searching", op,
            hit["variant"], ver["footprint"], device_kind,
            ver["vmem_budget"])
    if budget < 1:
        # a too-small total budget can allocate an op zero trials:
        # that is a SKIP (current selection stands), not an error —
        # the tool's report must not read like a failed tune
        return {"variant": variants.effective(op), "source": "skipped",
                "key": key, "trials": 0, "trace": [], "budget": budget}

    from veles_tpu.telemetry import tracer as vtrace
    counter = _trials_counter()
    prev = variants.selected(op)
    timings: Dict[str, float] = {}
    trace: List[Dict[str, Any]] = []
    state = {"trials": 0}
    #: per-device VMEM budget for static pruning (analysis pass 6):
    #: None (CPU / unknown device_kind, no override) = pruning inactive
    vbudget = vres.vmem_budget(device_kind, override=vmem_budget)
    pruned: set = set()

    def _timed_trial(name: str) -> float:
        """Time ONE gated candidate. The ledger check is the structural
        gate: no passing equivalence record, no timing — ever. The VMEM
        verdict is its twin (ISSUE 14): an over-budget point is refused
        HERE, independently of the prune branch, so a bypassed prune
        can never reach the timing path."""
        if not templates.passed(op, name):
            raise templates.UngatedCandidateError(
                f"{op}/{name}: refusing to time a candidate with no "
                "passing ops.reference equivalence record")
        ver = vres.kernel_verdict(op, name, shapes=vmem_shapes,
                                  dtype=compute_dtype, budget=vbudget)
        if ver is not None:
            raise vres.InfeasibleCandidateError(
                f"{op}/{name}: refusing to time a candidate whose "
                f"static VMEM footprint ({ver['footprint']} B) exceeds "
                f"the device budget ({ver['vmem_budget']} B)")
        if in_graph_timer is not None:
            variants.select(op, name)
            return in_graph_timer()
        return templates.bench_candidate(
            op, variants.get(op, name).apply, repeats)

    def trial(name: str) -> Optional[float]:
        """Evaluate one candidate (gate, then time). None = skipped
        (dup / budget exhausted / failed); seconds otherwise. Every
        evaluation — including equivalence failures — consumes budget:
        the budget bounds WORK, not successes."""
        if name in timings \
                or any(t["variant"] == name for t in trace):
            return timings.get(name)
        if state["trials"] >= budget:
            return None
        state["trials"] += 1
        rec: Dict[str, Any] = {"variant": name}
        with vtrace.span(f"autotune.trial:{op}/{name}", "autotune"):
            try:
                eq = templates.check_equivalence(op, name)
                if eq["status"] != "pass":
                    rec.update(outcome="equiv_fail",
                               error=eq.get("error", ""))
                    counter.labels(op=op, outcome="equiv_fail").inc()
                else:
                    t = _timed_trial(name)
                    timings[name] = t
                    rec.update(outcome="timed", time_s=round(t, 6))
                    counter.labels(op=op, outcome="timed").inc()
            except (templates.UngatedCandidateError,
                    vres.InfeasibleCandidateError):
                raise   # structural bug, never swallowed as a trial error
            except Exception as e:  # noqa: BLE001 — one broken candidate
                # (a backend-rejected kernel) must not abort the search
                rec.update(outcome="error", error=f"{e!s:.200}")
                counter.labels(op=op, outcome="error").inc()
        trace.append(rec)
        return timings.get(name)

    # 1. incumbents: the hand-written tunable variants seed the search
    for v in variants.variants_for(op):
        if v.tunable and not v.generated \
                and (not v.pallas or variants.pallas_ok()):
            trial(v.name)

    # 2. coordinate descent per template, from the template's seed.
    # Under microbench timing, configs that alias to the SAME effective
    # kernel at the bench shapes (template.bench_key — flash's fit()
    # clamp) are skipped after the first: the budget times distinct
    # kernels and the cached winner names a config that truly executed.
    seen_bench: Dict[Any, str] = {}

    def gen_trial(t, cfg) -> Optional[float]:
        name = t.name(cfg)
        if name in pruned:
            return None
        # static VMEM pruning (ISSUE 14): an over-budget point is
        # statically infeasible — skipped WITHOUT timing it or burning
        # budget, logged per point (the PR-8 no-silent-caps rule) and
        # counted as outcome="pruned" on the trials metric
        ver = _prune_verdict(op, t, cfg, vmem_shapes, compute_dtype,
                             vbudget)
        if ver is not None:
            pruned.add(name)
            counter.labels(op=op, outcome="pruned").inc()
            trace.append({"variant": name, "outcome": "pruned", **ver})
            logging.getLogger("veles.autotune").info(
                "pruned %s/%s: VMEM footprint %d B > %s budget %d B "
                "(never timed, no budget spent)", op, name,
                ver["footprint"], device_kind, ver["vmem_budget"])
            return None
        if in_graph_timer is None and t.bench_key is not None:
            bk = t.bench_key(cfg)
            if seen_bench.setdefault(bk, name) != name:
                return None          # aliases an already-tried point
        return trial(name)

    for t in templates.templates_for(op):
        cur = dict(t.seed)
        best_t = gen_trial(t, cur)
        improved = True
        while improved and state["trials"] < budget:
            improved = False
            for axis in t.axes:
                if state["trials"] >= budget:
                    break
                best_choice = cur[axis.name]
                for c in axis.choices:
                    if c == best_choice:
                        continue
                    tt = gen_trial(t, {**cur, axis.name: c})
                    if tt is not None and (best_t is None
                                           or tt < best_t):
                        best_t, improved = tt, True
                        cur[axis.name] = c
        # descent converged: spend the REMAINING budget exploring
        # still-unseen points of the space in deterministic order (the
        # budget bounds work; leaving trials unspent would just narrow
        # coverage for free) — duplicates/aliases skip without cost
        for cfg in t.configs():
            if state["trials"] >= budget:
                break
            gen_trial(t, cfg)

    if not timings:
        if prev is None:
            variants.clear_selection(op)
        else:
            variants.select(op, prev)
        return {"variant": variants.effective(op), "source": "error",
                "trace": trace, "key": key, "trials": state["trials"]}

    winner = min(timings, key=timings.get)
    variants.select(op, winner)
    win_v = variants.get(op, winner)
    cfg = None
    if win_v.generated:
        for t in templates.templates_for(op):
            cfg = t.parse(winner)
            if cfg is not None:
                break
    record = {
        "variant": winner, "config": cfg,
        "timings_s": {k: round(v, 6) for k, v in timings.items()},
        "trace": trace,
        "equivalence": {t_["variant"]: ("fail" if t_["outcome"]
                                        == "equiv_fail" else "pass")
                        for t_ in trace
                        if t_["outcome"] != "pruned"},
        "pruned": sorted(pruned),
        "budget": budget, "trials": state["trials"],
        "timer": "in_graph" if in_graph_timer is not None
        else "microbench",
        "device_kind": device_kind, "repeats": repeats,
        "tuned_at": time.time(),
    }
    cache.put(key, record)
    return {**record, "source": "searched", "key": key}


def search_workflow(wf=None, *, ops: Optional[List[str]] = None,
                    budget: int = 32,
                    cache: Optional[AutotuneCache] = None,
                    cache_path: Optional[str] = None,
                    compute_dtype: Any = None,
                    profile_path: Optional[str] = None,
                    mesh=None, steps: int = 4, repeats: int = 2,
                    batch: Optional[int] = None,
                    force: bool = False,
                    vmem_budget: Optional[int] = None
                    ) -> Dict[str, Dict[str, Any]]:
    """Budgeted search across every template-backed op: workflow ops
    (maxpool, …) time IN-GRAPH through `wf`'s fused step, ops below the unit
    graph (flash_attn, sgd_update) through their template microbench.
    Priority order and budget split come from LAYER_PROFILE.json. The
    per-op reports include the full trial trace; winners are selected
    and persisted like any autotune decision."""
    import jax

    from veles_tpu.ops import templates
    cache = cache or AutotuneCache(cache_path)
    # an explicitly EMPTY ops list means "search nothing" (an --ops
    # restriction that names no template op) — only None means "all"
    all_ops = templates.template_ops() if ops is None else list(ops)
    all_ops = [op for op in all_ops
               if templates.templates_for(op)
               and op in templates.CONTRACTS]
    wf_sigs: Dict[str, List[Dict]] = {}
    if wf is not None:
        if not getattr(wf, "is_initialized", False):
            wf.initialize(device=None)
        wf_sigs = discover_tunables(wf)
        # adjacent fused pairs are in-graph-timeable too: a selected
        # fused point changes what the step traces for the pair
        wf_sigs.update(discover_fusions(wf))
    #: ops the WORKFLOW names (in-graph-timeable) — before the extra
    #: signatures below widen wf_sigs for cache-keying only
    discovered = set(wf_sigs)
    for op, sig_fn in EXTRA_OP_SIGS.items():
        if op in all_ops:
            wf_sigs.setdefault(op, sig_fn())
    ordered = priority_order(all_ops, profile_path)
    # MEMBER ops tune before their fusion op (stable: share order kept
    # within each group): the fusion decision then competes against
    # tuned member lowerings, not their defaults
    ordered.sort(key=lambda kv: bool(templates.fusion_members(kv[0])))
    shares = allocate_budget(
        ordered, budget,
        floors={op: incumbent_floor(op) for op, _ in ordered})
    report: Dict[str, Dict[str, Any]] = {}
    with _pallas_ctx():
        for op, share in ordered:
            timer = None
            if wf is not None and op in discovered:
                timer = (lambda: _time_variant(
                    wf, mesh, compute_dtype, steps, repeats, batch))
            from veles_tpu.analysis import resources as vres
            with _suspend_fusions(op):   # see the contextmanager's doc
                report[op] = search_op(
                    op, budget=shares[op], cache=cache,
                    compute_dtype=compute_dtype, force=force,
                    repeats=repeats, workflow_sigs=wf_sigs.get(op),
                    in_graph_timer=timer,
                    # static VMEM pruning evaluates each point at the
                    # WORKFLOW's shapes when the op is in-graph (the
                    # kernel a winner would actually trace), else at
                    # the microbench's canonical shapes
                    vmem_shapes=vres.shapes_from_signatures(
                        op, wf_sigs.get(op)),
                    vmem_budget=vmem_budget)
            report[op]["priority_share"] = share
    return report
