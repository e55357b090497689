"""Autotune the lowering-variant registry for the flagship AlexNet step.

Every tunable op the step contains (LRN fwd/bwd lowering, max-pooling
backward shape, s2d stem, dropout RNG) is timed candidate-by-candidate
in-graph — the donated train_repeat loop — and the winner is selected AND
persisted in the on-disk decision cache, so the next run (training, a
second autotune) is a pure cache hit. See docs/AUTOTUNE.md.

Usage (TPU, full geometry):
    python tools/autotune.py
CPU smoke (tiny geometry, Pallas candidates in interpret mode):
    JAX_PLATFORMS=cpu python tools/autotune.py

The last stdout line is one JSON record: chosen variant per op, timings
for freshly tuned ops, and the cache path.
The persistent XLA compilation cache follows the one rule in
veles_tpu/caches.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--batch", type=int, default=None,
                   help="microbench batch (default: 512 on TPU, 8 on CPU)")
    p.add_argument("--steps", type=int, default=None,
                   help="train_repeat steps per timing window "
                        "(default: 8 on TPU, 2 on CPU)")
    p.add_argument("--repeats", type=int, default=2,
                   help="timed windows per candidate (min wins)")
    p.add_argument("--width", type=float, default=None,
                   help="AlexNet width multiplier (default: 1.0 on TPU, "
                        "0.125 on CPU)")
    p.add_argument("--hw", type=int, default=None,
                   help="input resolution (default: 227 on TPU, 67 on CPU)")
    p.add_argument("--cache", default=None, metavar="PATH",
                   help="decision cache path (default: "
                        "$VELES_AUTOTUNE_CACHE or "
                        "<checkout>/.veles_cache/autotune.json)")
    p.add_argument("--ops", default="", metavar="OP[,OP...]",
                   help="restrict tuning to these ops (default: all)")
    p.add_argument("--force", action="store_true",
                   help="re-time even on a cache hit")
    p.add_argument("--budget", type=int, default=None, metavar="N",
                   help="budgeted coordinate-descent search over the "
                        "GENERATED kernel candidates (ops.templates): "
                        "spend up to N trials across the template-"
                        "backed ops — workflow ops (maxpool, …) timed in-graph,"
                        " below-graph ops (flash_attn, sgd_update) via "
                        "their template microbench — priority-ordered "
                        "by LAYER_PROFILE.json; every point equivalence-"
                        "gated against ops.reference before timing. "
                        "Non-template ops still get the flat enumeration")
    p.add_argument("--profile-json", default=None, metavar="PATH",
                   help="per-op cost shares for the search's priority "
                        "order (default: $VELES_LAYER_PROFILE_PATH or "
                        "LAYER_PROFILE.json — write it with "
                        "tools/layer_profile.py)")
    p.add_argument("--vmem-budget", type=int, default=None,
                   metavar="BYTES",
                   help="override the per-device VMEM budget the "
                        "search prunes against (analysis pass 6: a "
                        "generated point whose static footprint "
                        "exceeds it is skipped without timing or "
                        "budget cost) — what-if runs on CPU, where no "
                        "budget exists by default, or tighter-than-"
                        "device exploration; also honored as "
                        "$VELES_VMEM_BUDGET")
    args = p.parse_args(argv)

    if args.budget is not None and args.budget < 1:
        # the launcher's --autotune-budget precedent: a non-positive
        # budget would silently skip every template-backed op AND
        # exclude it from the flat fallback — reject it
        p.error("--budget must be >= 1")
    if args.profile_json and not args.budget:
        # the --autotune-budget precedent: a flag nothing consumes is a
        # silent no-op — the flat enumeration never reads the profile
        p.error("--profile-json orders the budgeted search: "
                "combine with --budget N")
    if args.vmem_budget is not None and not args.budget:
        # same precedent: only the budgeted search prunes
        p.error("--vmem-budget bounds the budgeted search's generated "
                "points: combine with --budget N")
    if args.vmem_budget is not None and args.vmem_budget < 1:
        p.error("--vmem-budget must be a positive byte count")

    import jax

    from veles_tpu.caches import enable_compilation_cache
    enable_compilation_cache()
    on_cpu = jax.default_backend() == "cpu"

    from veles_tpu import prng
    from veles_tpu.ops import templates, variants
    from veles_tpu.ops.autotune import (AutotuneCache, autotune_workflow,
                                        search_workflow)
    from veles_tpu.samples.alexnet import create_workflow

    batch = args.batch or (8 if on_cpu else 512)
    steps = args.steps or (2 if on_cpu else 8)
    width = args.width if args.width is not None \
        else (0.125 if on_cpu else 1.0)
    hw = args.hw or (67 if on_cpu else 227)
    kw = {}
    if width != 1.0:
        kw = dict(width_mult=width, fc_width=int(4096 * width) or 64,
                  input_hw=hw)
    elif hw != 227:
        kw = dict(input_hw=hw)
    prng.seed_all(1234)
    wf = create_workflow(minibatch_size=batch, n_train=2 * batch,
                         n_validation=batch, **kw)
    wf.initialize(device=None)
    cache = AutotuneCache(args.cache)
    compute_dtype = None if on_cpu else "bfloat16"
    only = [o for o in args.ops.split(",") if o] or None
    if args.budget:
        # budgeted search across EVERY template-backed op (maxpool in-graph
        # through the flagship step, flash_attn/sgd_update via their
        # microbenches), then the flat enumeration for the rest
        searched = [op for op in templates.template_ops()
                    if only is None or op in only]
        report = {}
        if searched:
            report = search_workflow(
                wf, ops=searched, budget=args.budget, cache=cache,
                compute_dtype=compute_dtype, steps=steps,
                repeats=args.repeats, batch=batch, force=args.force,
                profile_path=args.profile_json,
                vmem_budget=args.vmem_budget)
        flat_ops = [op for op in (only or variants.ops())
                    if op not in report]
        if flat_ops:
            report.update(autotune_workflow(
                wf, steps=steps, repeats=args.repeats, batch=batch,
                cache=cache, force=args.force,
                compute_dtype=compute_dtype, ops=flat_ops))
    else:
        report = autotune_workflow(
            wf, steps=steps, repeats=args.repeats, batch=batch,
            cache=cache, force=args.force, compute_dtype=compute_dtype,
            ops=only)
    for op, rec in sorted(report.items()):
        line = f"AUTOTUNE {op}: {rec['variant']} ({rec['source']})"
        if rec.get("trials"):
            line += (f"  trials={rec['trials']}/{rec.get('budget', '?')}"
                     f"  share={rec.get('priority_share', 0):.2f}")
        if rec.get("pruned"):
            # the no-silent-caps rule: points the VMEM budget dropped
            # are named in the per-point log; the count rides the line
            line += f"  pruned={len(rec['pruned'])}"
        if rec.get("timings_s"):
            line += "  " + "  ".join(
                f"{k}={v if isinstance(v, str) else f'{v * 1e3:.2f}ms'}"
                for k, v in sorted(rec["timings_s"].items()))
        print(line, flush=True)
    print(json.dumps({
        "autotune": report,
        "variants": variants.selection_table(include_defaults=True),
        "device_kind": jax.devices()[0].device_kind,
        "batch": batch,
        "cache": cache.path,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
