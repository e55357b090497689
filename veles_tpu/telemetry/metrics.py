"""ONE metrics registry: counters / gauges / histograms behind a
Prometheus text-format exposition and a JSONL append sink.

Before this module the fleet's numbers were disjoint artifacts — feed
counters in `loader_throughput()`, `parallel/memstats.py` snapshots,
supervisor JSON exit reports, bench records — each with its own
producer. Everything now routes through a `MetricsRegistry`:

- the driver loop (`_run_with_step`) records step counts/time, examples
  and loss through PRE-BOUND handles (`step_handles()`; the velint
  ``hot-metric`` rule bans per-record name lookups in hot paths);
- the DeviceFeed and the prefetching loader write their own counters
  (`feed_handles()` / `loader_handles()`, bound once per feed / per
  produce pool), so a scrape sees them without a driver's help;
- memstats snapshots land as gauges (`mirror_mem()`);
- web_status, the cluster coordinator (fleet-aggregated from member
  heartbeats) and serving each mount ``GET /metrics`` rendering
  `exposition()`;
- every flush is mirrored to a JSONL sink (`install_jsonl()` /
  `flush_installed()`) for offline analysis next to bench records,
  with size-capped rotation.

Prometheus exposition follows the text format 0.0.4 contract the
strict-parser test enforces: ``# HELP``/``# TYPE`` per family, counter
names ending ``_total`` exposed as monotone non-negative values,
histograms with cumulative ``_bucket{le=...}`` rows ending at
``le="+Inf"`` == ``_count``, label values escaped.

Import-light on purpose (stdlib only): the resilience supervisor and
cluster member — jax-free parents — record restarts/generations here
too.
"""

from __future__ import annotations

import json
import math
import os
import re
import threading
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Sequence, Tuple

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

#: default step-time buckets (seconds): sub-ms TPU steps through
#: multi-second CPU smoke steps
STEP_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
#: serving latency buckets (seconds)
LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0)
#: serving ring occupancy buckets (rows per dispatched round)
RING_OCCUPANCY_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                          256.0, 512.0)

#: bound on distinct label-value children per family — a scrape target
#: must stay O(1) even if a caller labels by something unbounded
_MAX_CHILDREN = 1024


def _escape(value: str) -> str:
    return (str(value).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _fmt(v: float) -> str:
    if v != v:
        return "NaN"
    if v in (math.inf, -math.inf):
        return "+Inf" if v > 0 else "-Inf"
    if float(v).is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class _Child:
    """One (label-value) instrument. Float math under the family lock
    is overkill for CPython's GIL but keeps totals exact if that ever
    changes."""

    __slots__ = ("value", "sum", "count", "bucket_counts")

    def __init__(self, n_buckets: int = 0) -> None:
        self.value = 0.0
        self.sum = 0.0
        self.count = 0
        self.bucket_counts = [0] * n_buckets


class Family:
    """A named metric family; with no labelnames the family IS its
    single child and exposes the record methods directly (the
    pre-bound-handle idiom: `h = reg.counter(...)` then `h.inc()`)."""

    def __init__(self, name: str, kind: str, help_: str,
                 labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = ()) -> None:
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in labelnames:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r}")
        if kind == "counter" and not name.endswith("_total"):
            raise ValueError(
                f"counter {name!r} must end in _total (prometheus "
                "naming contract the exposition test enforces)")
        self.name = name
        self.kind = kind
        self.help = help_
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(float(b) for b in buckets)
        if self.buckets != tuple(sorted(set(self.buckets))):
            raise ValueError(f"buckets must be sorted/unique: {buckets}")
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], _Child] = {}
        if not self.labelnames:
            self._default = self._children.setdefault(
                (), _Child(len(self.buckets)))

    def labels(self, **labelvalues: str) -> "_BoundChild":
        if set(labelvalues) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: want labels {self.labelnames}, got "
                f"{tuple(labelvalues)}")
        key = tuple(str(labelvalues[ln])[:128]
                    for ln in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                if len(self._children) >= _MAX_CHILDREN:
                    # cardinality cap: fold overflow into one bucket
                    # rather than growing the scrape without bound
                    key = ("_overflow",) * len(self.labelnames)
                child = self._children.setdefault(
                    key, _Child(len(self.buckets)))
        return _BoundChild(self, child)

    # -- unlabeled record methods (proxy to the default child) ---------------

    def inc(self, amount: float = 1.0) -> None:
        _BoundChild(self, self._default).inc(amount)

    def set_total(self, total: float) -> None:
        _BoundChild(self, self._default).set_total(total)

    def set(self, value: float) -> None:
        _BoundChild(self, self._default).set(value)

    def observe(self, value: float) -> None:
        _BoundChild(self, self._default).observe(value)

    def set_histogram_totals(self, sum_: float, count: float) -> None:
        """Fleet aggregation: seed the unlabeled child's `_sum`/`_count`
        from flattened child snapshots. Bucket detail is unknown at the
        aggregator, so only the ``+Inf`` bucket (== count) carries —
        cumulative monotonicity holds (0, …, 0, count)."""
        if self.kind != "histogram":
            raise TypeError(f"{self.name} is a {self.kind}")
        with self._lock:
            self._default.sum = float(sum_)
            self._default.count = int(count)

    @property
    def value(self) -> float:
        return self._default.value

    # -- rendering ------------------------------------------------------------

    def _sample_lines(self) -> List[str]:
        out: List[str] = []
        with self._lock:
            items = sorted(self._children.items())
        for key, ch in items:
            lbl = ",".join(f'{ln}="{_escape(v)}"' for ln, v in
                           zip(self.labelnames, key))
            if self.kind == "histogram":
                cum = 0
                base = lbl + "," if lbl else ""
                for ub, n in zip(self.buckets, ch.bucket_counts):
                    cum += n
                    out.append(f'{self.name}_bucket{{{base}le='
                               f'"{_fmt(ub)}"}} {cum}')
                out.append(f'{self.name}_bucket{{{base}le="+Inf"}} '
                           f'{ch.count}')
                suffix = f"{{{lbl}}}" if lbl else ""
                out.append(f"{self.name}_sum{suffix} {_fmt(ch.sum)}")
                out.append(f"{self.name}_count{suffix} {ch.count}")
            else:
                suffix = f"{{{lbl}}}" if lbl else ""
                out.append(f"{self.name}{suffix} {_fmt(ch.value)}")
        return out

    def _snapshot_into(self, out: Dict[str, float]) -> None:
        """Flat unlabeled view for heartbeats/JSONL (labeled children
        ride the exposition only — the flat dict must stay small and
        key-stable)."""
        ch = self._children.get(())
        if ch is None:
            return
        if self.kind == "histogram":
            out[f"{self.name}_sum"] = ch.sum
            out[f"{self.name}_count"] = float(ch.count)
        else:
            out[self.name] = ch.value


class _BoundChild:
    """A (family, child) pair — the pre-bound handle hot paths hold."""

    __slots__ = ("_f", "_c")

    def __init__(self, family: Family, child: _Child) -> None:
        self._f = family
        self._c = child

    def inc(self, amount: float = 1.0) -> None:
        if self._f.kind not in ("counter", "gauge"):
            raise TypeError(f"{self._f.name} is a {self._f.kind}")
        if self._f.kind == "counter" and amount < 0:
            raise ValueError(f"counter {self._f.name} cannot decrease")
        with self._f._lock:
            self._c.value += amount

    def set_total(self, total: float) -> None:
        """Mirror an EXTERNAL cumulative accumulator (the feed's stats
        dict, a coordinator's restart count) — monotone enforced so the
        exposed counter never goes backwards mid-scrape."""
        if self._f.kind != "counter":
            raise TypeError(f"{self._f.name} is a {self._f.kind}")
        with self._f._lock:
            self._c.value = max(self._c.value, float(total))

    def set(self, value: float) -> None:
        if self._f.kind != "gauge":
            raise TypeError(f"{self._f.name} is a {self._f.kind}")
        with self._f._lock:
            self._c.value = float(value)

    def observe(self, value: float) -> None:
        if self._f.kind != "histogram":
            raise TypeError(f"{self._f.name} is a {self._f.kind}")
        v = float(value)
        with self._f._lock:
            self._c.sum += v
            self._c.count += 1
            for i, ub in enumerate(self._f.buckets):
                if v <= ub:
                    self._c.bucket_counts[i] += 1
                    break

    @property
    def value(self) -> float:
        return self._c.value


class MetricsRegistry:
    """Named families + the exposition/snapshot views over them."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._families: Dict[str, Family] = {}

    def _get(self, name: str, kind: str, help_: str,
             labelnames: Sequence[str],
             buckets: Sequence[float] = ()) -> Family:
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = Family(name, kind, help_, labelnames, buckets)
                self._families[name] = fam
                return fam
        if fam.kind != kind or (tuple(labelnames) != fam.labelnames
                                and labelnames):
            raise ValueError(
                f"metric {name!r} already registered as {fam.kind}"
                f"{fam.labelnames} (got {kind}{tuple(labelnames)})")
        return fam

    def counter(self, name: str, help_: str = "",
                labelnames: Sequence[str] = ()) -> Family:
        return self._get(name, "counter", help_, labelnames)

    def gauge(self, name: str, help_: str = "",
              labelnames: Sequence[str] = ()) -> Family:
        return self._get(name, "gauge", help_, labelnames)

    def histogram(self, name: str, help_: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Sequence[float] = STEP_BUCKETS) -> Family:
        return self._get(name, "histogram", help_, labelnames, buckets)

    def exposition(self) -> str:
        """Prometheus text format 0.0.4 (the strict-parser contract)."""
        lines: List[str] = []
        with self._lock:
            families = sorted(self._families.values(),
                              key=lambda f: f.name)
        for fam in families:
            lines.append(f"# HELP {fam.name} "
                         f"{_escape(fam.help or fam.name)}")
            lines.append(f"# TYPE {fam.name} {fam.kind}")
            lines.extend(fam._sample_lines())
        return "\n".join(lines) + "\n"

    def snapshot_flat(self) -> Dict[str, float]:
        """{name: value} over unlabeled children (heartbeat payloads,
        JSONL lines); histograms flatten to `_sum`/`_count`."""
        out: Dict[str, float] = {}
        with self._lock:
            families = list(self._families.values())
        for fam in families:
            fam._snapshot_into(out)
        return out


def histogram_quantile(family: Family, q: float,
                       **labelvalues: str) -> Optional[float]:
    """Prometheus-style quantile estimate from a histogram family's
    cumulative buckets (linear interpolation inside the bucket, the
    ``histogram_quantile()`` PromQL rule) — the READ-BACK path
    tools/loadtest.py reports p50/p99 through, so a latency number in a
    record is always derivable from the scraped registry, never a
    side-channel list. None when the (labeled) child has no
    observations. The estimate's resolution is the bucket grid; the
    last bucket clamps to its upper bound (+Inf falls back to the
    highest finite bound)."""
    if family.kind != "histogram":
        raise TypeError(f"{family.name} is a {family.kind}")
    if labelvalues:
        key = tuple(str(labelvalues[ln])[:128]
                    for ln in family.labelnames)
    else:
        key = ()
    with family._lock:
        ch = family._children.get(key)
        if ch is None or ch.count == 0:
            return None
        counts = list(ch.bucket_counts)
        total = ch.count
    rank = max(0.0, min(1.0, float(q))) * total
    cum = 0
    lo = 0.0
    for ub, n in zip(family.buckets, counts):
        if cum + n >= rank and n > 0:
            frac = (rank - cum) / n
            return lo + (ub - lo) * frac
        cum += n
        lo = ub
    # rank lands in the +Inf bucket: clamp to the highest finite bound
    return family.buckets[-1] if family.buckets else None


#: exposition content type (scrape endpoints set it verbatim)
CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


# -- the standard families ----------------------------------------------------

def register_standard(reg: MetricsRegistry) -> None:
    """Register the step/feed/mem/restart families every scrape
    endpoint must present (zero-valued until a producer runs) — the
    acceptance contract for web_status, the coordinator and serving."""
    reg.counter("veles_step_total", "training steps dispatched")
    reg.histogram("veles_step_seconds",
                  "driver wall time per step (dispatch to dispatch)",
                  buckets=STEP_BUCKETS)
    reg.counter("veles_examples_total",
                "valid training examples consumed")
    reg.gauge("veles_examples_per_second",
              "examples/s over the last completed epoch")
    reg.gauge("veles_loss", "last class-pass mean loss")
    reg.gauge("veles_epoch", "decision epoch counter")
    reg.counter("veles_feed_h2d_bytes_total",
                "host->device batch bytes through the DeviceFeed")
    reg.counter("veles_feed_loader_block_seconds_total",
                "driver time blocked on the host loader")
    reg.counter("veles_feed_device_sync_seconds_total",
                "driver time blocked on the device at class-pass "
                "boundaries")
    reg.counter("veles_feed_on_demand_total",
                "feed pops that had to produce synchronously (1 is the "
                "unavoidable first batch; growth = loader too slow)")
    reg.counter("veles_feed_batches_total",
                "batches the DeviceFeed produced (loader.run + put)")
    reg.counter("veles_feed_put_seconds_total",
                "driver time inside the feed's device_put call (the "
                "async put's host side: layout change and enqueue)")
    reg.counter("veles_feed_h2d_ready_total",
                "popped batches whose arrays were already on the device "
                "(is_ready(), asked without blocking)")
    reg.counter("veles_feed_h2d_late_total",
                "popped batches still in transfer when the loop took "
                "them: the step dispatched on them waits for the link")
    reg.counter("veles_loader_produce_seconds_total",
                "seconds inside PrefetchingLoader._produce, summed over "
                "the produce threads (gather, flip, normalize)")
    reg.counter("veles_loader_batches_produced_total",
                "batches PrefetchingLoader._produce completed")
    reg.counter("veles_loader_lookahead_ready_total",
                "fills whose lookahead future was done when asked")
    reg.counter("veles_loader_lookahead_waited_total",
                "fills that waited: the lookahead future was still "
                "running, or there was none (first batch of a run)")
    reg.counter("veles_loader_lookahead_cross_epoch_total",
                "lookahead futures submitted for a batch of the next "
                "epoch (the produce pool runs on across the boundary)")
    reg.gauge("veles_mem_live_bytes", "live jax.Array bytes per device",
              labelnames=("device",))
    reg.gauge("veles_mem_live_bytes_max",
              "live jax.Array bytes on the fullest device")
    reg.counter("veles_restart_total",
                "supervised restarts (supervisor or cluster)")
    reg.gauge("veles_generation",
              "supervision generation / attempt counter")
    reg.counter("veles_collective_bytes_total",
                "modeled per-device collective egress bytes by op and "
                "link leg (dcn/ici) — the ZeRO grad_reduce exchange + "
                "param all-gather, fed per dispatched train step from "
                "FusedTrainStep.collective_accounting (byte model in "
                "docs/SCALING.md)",
                labelnames=("op", "leg"))
    reg.gauge("veles_serving_queue_depth",
              "predict requests queued for the serving dispatch loop "
              "(ring admission / merge batcher), sampled at every "
              "enqueue and round")
    reg.histogram("veles_serving_ring_occupancy",
                  "occupied rows per dispatched serving ring round — "
                  "ring efficiency measured, not claimed (a low "
                  "occupancy under load means admission, not the "
                  "device, is the bottleneck)",
                  buckets=RING_OCCUPANCY_BUCKETS)
    reg.counter("veles_serving_swap_applied_total",
                "hot weight swaps applied to the serving ring "
                "(watcher pushes + explicit rollbacks; the blue/green "
                "pointer moved, no recompile, no drain)")
    reg.counter("veles_serving_swap_refused_total",
                "hot swaps refused by stage — the ring kept serving "
                "the current generation (reasons: fetch_failed, "
                "verify_failed, import_failed, geometry, "
                "wire_transform, device_put, equivalence, nonfinite, "
                "merge_core, no_previous)",
                labelnames=("reason",))
    reg.gauge("veles_serving_generation_age_seconds",
              "seconds the live weight generation has been serving "
              "(resets to 0 at every applied swap/rollback)")
    # fleet front door (serving_router.py) — present on every router
    # scrape even before the first beacon lands; the labelnames here
    # MUST match the router's bindings (the registry re-get contract)
    reg.counter("veles_router_requests_total",
                "client requests through the fleet router by terminal "
                "outcome (ok / shed / error / bad)",
                labelnames=("outcome",))
    reg.counter("veles_router_dispatch_total",
                "per-replica dispatch attempts by outcome (ok / fail / "
                "shed / client_error / hedge)",
                labelnames=("replica", "outcome"))
    reg.counter("veles_router_hedges_total",
                "hedged dispatches (first replica exceeded its "
                "measured p99)")
    reg.counter("veles_router_retries_total",
                "dispatch retries after a replica failure or shed")
    reg.gauge("veles_router_replicas_live",
              "replicas currently routable (status up, beacon fresh)")
    reg.gauge("veles_router_fleet_capacity",
              "summed capacity hint across routable replicas — the "
              "HPA-shaped autoscale signal (deploy/veles-serving.yaml)")
    reg.histogram("veles_router_latency_seconds",
                  "end-to-end /predict latency through the router "
                  "(includes retries and hedges)",
                  buckets=LATENCY_BUCKETS)


_DEFAULT: Optional[MetricsRegistry] = None
_DEFAULT_LOCK = threading.Lock()


def default_registry() -> MetricsRegistry:
    """The process registry (standard families pre-registered)."""
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                reg = MetricsRegistry()
                register_standard(reg)
                _DEFAULT = reg
    return _DEFAULT


def reset_default_registry() -> None:
    """Drop the process registry (tests)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = None


def step_handles(reg: Optional[MetricsRegistry] = None) -> SimpleNamespace:
    """Pre-bound instruments for the driver loop — bound ONCE before
    the loop so the hot path never does a name lookup (the velint
    ``hot-metric`` contract)."""
    reg = reg or default_registry()
    return SimpleNamespace(
        steps=reg.counter("veles_step_total"),
        step_seconds=reg.histogram("veles_step_seconds"),
        examples=reg.counter("veles_examples_total"),
        examples_per_s=reg.gauge("veles_examples_per_second"),
        loss=reg.gauge("veles_loss"),
        epoch=reg.gauge("veles_epoch"),
    )


def collective_handles(acct: Optional[Dict[str, Any]],
                       reg: Optional[MetricsRegistry] = None
                       ) -> Optional[SimpleNamespace]:
    """Pre-bound veles_collective_bytes_total children + per-step byte
    amounts for one step's collective accounting dict
    (FusedTrainStep.collective_accounting()) — bound ONCE outside the
    driver loop, so the hot path pays four float adds and never a name
    or label lookup (the hot-metric contract). None when the step
    traces no registry collective."""
    if not acct:
        return None
    reg = reg or default_registry()
    fam = reg.counter("veles_collective_bytes_total",
                      labelnames=("op", "leg"))
    return SimpleNamespace(
        dcn=fam.labels(op=acct["op"], leg="dcn"),
        ici=fam.labels(op=acct["op"], leg="ici"),
        ag_dcn=fam.labels(op="param_allgather", leg="dcn"),
        ag_ici=fam.labels(op="param_allgather", leg="ici"),
        dcn_bytes=float(acct.get("dcn_bytes", 0)),
        ici_bytes=float(acct.get("ici_bytes", 0)),
        ag_dcn_bytes=float(acct.get("allgather_dcn_bytes", 0)),
        ag_ici_bytes=float(acct.get("allgather_ici_bytes", 0)))


def feed_handles(reg: Optional[MetricsRegistry] = None) -> SimpleNamespace:
    """Pre-bound `veles_feed_*` counters: the DeviceFeed binds them once
    and is their one producer (per batch: float adds, no name lookup)."""
    reg = reg or default_registry()
    return SimpleNamespace(
        batches=reg.counter("veles_feed_batches_total"),
        bytes_h2d=reg.counter("veles_feed_h2d_bytes_total"),
        loader_block_s=reg.counter(
            "veles_feed_loader_block_seconds_total"),
        put_s=reg.counter("veles_feed_put_seconds_total"),
        device_sync_s=reg.counter("veles_feed_device_sync_seconds_total"),
        on_demand=reg.counter("veles_feed_on_demand_total"),
        h2d_ready=reg.counter("veles_feed_h2d_ready_total"),
        h2d_late=reg.counter("veles_feed_h2d_late_total"),
    )


def loader_handles(reg: Optional[MetricsRegistry] = None
                   ) -> SimpleNamespace:
    """Pre-bound `veles_loader_*` counters: the PrefetchingLoader binds
    them when it starts its produce pool and is their one producer."""
    reg = reg or default_registry()
    return SimpleNamespace(
        produce_s=reg.counter("veles_loader_produce_seconds_total"),
        produced=reg.counter("veles_loader_batches_produced_total"),
        ready=reg.counter("veles_loader_lookahead_ready_total"),
        waited=reg.counter("veles_loader_lookahead_waited_total"),
        cross_epoch=reg.counter("veles_loader_lookahead_cross_epoch_total"),
    )


def moe_handles(reg: Optional[MetricsRegistry] = None) -> SimpleNamespace:
    """The `veles_moe_*` families of a top-k expert layer that computes
    the experts it holds: slots are (token, selected expert) pairs,
    counted inside the step into int32 state (`znicz/lm.py`) and
    published from it by whoever reads that state
    (`znicz.lm.publish_moe_counters`), never per step. Registered on
    first use: a program without such a layer exposes none."""
    reg = reg or default_registry()
    by_layer = ("layer",)
    return SimpleNamespace(
        steps=reg.counter("veles_moe_steps_total",
                          "train steps the slot counters cover", by_layer),
        slots=reg.counter("veles_moe_slots_total",
                          "slots routed, over all experts", by_layer),
        held=reg.counter("veles_moe_held_slots_total",
                         "slots routed to the experts held here", by_layer),
        fullest=reg.counter(
            "veles_moe_fullest_held_slots_total",
            "sum over steps of the fullest held expert's slots", by_layer),
        dropped=reg.counter("veles_moe_slots_dropped_total",
                            "held slots the layer did not compute"),
        reached=reg.gauge(
            "veles_moe_balance_reached",
            "1 if every layer's held share of the slots was within the "
            "band around its even share when counting began, else 0"))


def dsa_handles(reg: Optional[MetricsRegistry] = None) -> SimpleNamespace:
    """The `veles_dsa_*` families of attention over the keys an indexer
    selects (`ops/attention.py::indexed_attention`): (query, key) pairs,
    counted inside the step into int32 state (`znicz/lm.py`) and published
    from it by whoever reads that state (`znicz.lm.publish_dsa_counters`),
    never per step. Registered on first use."""
    reg = reg or default_registry()
    by_layer = ("layer",)
    return SimpleNamespace(
        steps=reg.counter("veles_dsa_steps_total",
                          "train steps the pair counters cover", by_layer),
        causal=reg.counter("veles_dsa_pairs_causal_total",
                           "pairs with key <= query", by_layer),
        selected=reg.counter("veles_dsa_pairs_selected_total",
                             "pairs the indexer selected", by_layer),
        scored=reg.counter(
            "veles_dsa_pairs_scored_total",
            "pairs whose main-attention score the program formed: the "
            "causal pairs of the tiles it visited", by_layer))


def gdn_handles(reg: Optional[MetricsRegistry] = None) -> SimpleNamespace:
    """The `veles_gdn_*` families of a Gated DeltaNet layer
    (`ops/linear_attention.py`): tokens and chunks its chain along the
    sequence walked, counted inside the step into int32 state
    (`znicz/lm.py`), and of the last step the final state's root mean
    square and the lowest cumulative log-decay a chunk reached (what a
    chunked form that divides by decays would overflow on first);
    published by whoever reads that state (`znicz.lm.publish_gdn_counters`),
    never per step. Registered on first use."""
    reg = reg or default_registry()
    by_layer = ("layer",)
    return SimpleNamespace(
        steps=reg.counter("veles_gdn_steps_total",
                          "train steps the counters cover", by_layer),
        tokens=reg.counter("veles_gdn_tokens_total",
                           "tokens the layer's chain walked", by_layer),
        chunks=reg.counter("veles_gdn_chunks_total",
                           "chunks the layer's chain walked", by_layer),
        state_rms=reg.gauge(
            "veles_gdn_state_rms",
            "root mean square of the state after the last step's "
            "sequences", by_layer),
        decay_min=reg.gauge(
            "veles_gdn_decay_min",
            "lowest cumulative log-decay of a chunk in the last step",
            by_layer))


def setup_handles(reg: Optional[MetricsRegistry] = None) -> SimpleNamespace:
    """The `veles_setup_*` families `telemetry.tracer.phase` writes at a
    phase's close, and the gauge `veles_tpu/__init__.py` sets once."""
    reg = reg or default_registry()
    by_phase = ("phase",)
    return SimpleNamespace(
        seconds=reg.counter(
            "veles_setup_seconds_total",
            "a set-up phase's own seconds: its duration less the phases "
            "it caused on its thread", by_phase),
        phases=reg.counter("veles_setup_phases_total",
                           "set-up phases closed", by_phase),
        age_at_import=reg.gauge(
            "veles_process_age_at_import_seconds",
            "the process's age when the package was first imported: "
            "interpreter start and whatever the caller imported and "
            "started before it"))


def compile_handles(reg: Optional[MetricsRegistry] = None
                    ) -> SimpleNamespace:
    """The `veles_compile_*` families `telemetry.compile_stages` writes
    from jax.monitoring's events; `during` is the innermost set-up phase
    open on the thread the event arrived on, or ``none``."""
    reg = reg or default_registry()
    during = ("during",)
    return SimpleNamespace(
        seconds=reg.counter(
            "veles_compile_seconds_total",
            "seconds inside jax's trace / lower / backend stages, each "
            "stage and thread the union of its spans (a function jitted "
            "once and traced inside another's trace counts once)",
            ("stage", "during")),
        programs=reg.counter(
            "veles_compile_programs_total",
            "programs handed to the backend: compiled, or read from the "
            "persistent cache", during),
        cache=reg.counter(
            "veles_compile_cache_total",
            "persistent compile cache: programs read from it (hit) and "
            "programs compiled and written to it (miss)",
            ("result", "during")),
        cache_read_s=reg.counter(
            "veles_compile_cache_read_seconds_total",
            "seconds reading and deserialising cache hits", during))


def family_values(name: str, reg: Optional[MetricsRegistry] = None
                  ) -> Optional[Dict[Tuple[str, ...], float]]:
    """{label values: value} of one family's children, or None where no
    producer has registered it."""
    fam = (reg or default_registry())._families.get(name)
    if fam is None:
        return None
    with fam._lock:
        return {k: c.value for k, c in fam._children.items()}


def mirror_mem(mem: Optional[Dict[str, Any]],
               reg: Optional[MetricsRegistry] = None) -> None:
    """Mirror a memstats snapshot (parallel/memstats.py — the one
    accounting rule) into the mem gauges."""
    if not mem:
        return
    reg = reg or default_registry()
    per_dev = reg.gauge("veles_mem_live_bytes", labelnames=("device",))
    for dev, b in (mem.get("live_bytes") or {}).items():
        per_dev.labels(device=str(dev)).set(float(b))
    reg.gauge("veles_mem_live_bytes_max").set(
        float(mem.get("live_bytes_max", 0)))


def scrape_mem(reg: Optional[MetricsRegistry] = None) -> None:
    """Scrape-time mem refresh: sample memstats (never initializes a
    backend) into the gauges. Guarded — a scrape must never fail on
    accounting."""
    try:
        from veles_tpu.parallel.memstats import device_memory_stats
        mirror_mem(device_memory_stats(), reg)
    except Exception:  # noqa: BLE001
        pass


# -- JSONL sink ---------------------------------------------------------------

class JsonlSink:
    """Append-only JSONL mirror of registry flushes, with size-capped
    rotation: when the file exceeds `max_bytes` it is renamed to
    ``<path>.1`` (replacing any previous rotation) and a fresh file
    starts — two generations bound total disk use."""

    def __init__(self, path: str, max_bytes: int = 16 << 20) -> None:
        self.path = path
        self.max_bytes = max(4096, int(max_bytes))
        self._lock = threading.Lock()

    def write(self, obj: Dict[str, Any]) -> None:
        line = json.dumps(obj, sort_keys=True)
        with self._lock:
            try:
                if os.path.exists(self.path) \
                        and os.path.getsize(self.path) + len(line) + 1 \
                        > self.max_bytes:
                    os.replace(self.path, self.path + ".1")
                with open(self.path, "a") as f:
                    f.write(line + "\n")
            except OSError:
                pass    # a full disk must never fail the producer


_SINK: Optional[JsonlSink] = None


def install_jsonl(path: str, max_bytes: int = 0) -> JsonlSink:
    """Install the process JSONL sink (CLI --trace sidecar, env
    ``VELES_METRICS_JSONL``). Idempotent on the same path."""
    global _SINK
    if _SINK is None or _SINK.path != path:
        _SINK = JsonlSink(
            path, max_bytes or int(os.environ.get(
                "VELES_METRICS_JSONL_MAX_BYTES", str(16 << 20))))
    return _SINK


def installed_sink() -> Optional[JsonlSink]:
    return _SINK


def uninstall_jsonl() -> None:
    global _SINK
    _SINK = None


def flush_installed(extra: Optional[Dict[str, Any]] = None,
                    reg: Optional[MetricsRegistry] = None) -> None:
    """Mirror the registry's flat snapshot to the installed sink (one
    JSONL line per flush); no-op when no sink is installed."""
    sink = _SINK
    if sink is None:
        return
    row: Dict[str, Any] = {"ts": round(time.time(), 3)}
    if extra:
        row.update(extra)
    row["metrics"] = (reg or default_registry()).snapshot_flat()
    sink.write(row)


def snapshot_flat() -> Dict[str, float]:
    """The default registry's flat snapshot (heartbeat payloads)."""
    return default_registry().snapshot_flat()
