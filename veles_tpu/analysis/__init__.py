"""Static analysis for veles_tpu: make wiring, tracing and hot-path
mistakes checkable BEFORE anything runs — on CPU, in CI.

Eight passes (docs/ANALYSIS.md has the full rule catalogue):

- `graph`  — workflow-graph verifier over a constructed `Workflow`
  (dangling/shadowed aliases, AND-gate cycles, unreachable units,
  read-before-write alias flows). Runs at `Workflow.initialize(verify=)`
  and via `python -m veles_tpu --verify-workflow`.
- `trace`  — jaxpr auditor over the fused/pipelined train step
  (dtype promotion, host syncs, dropped donation, sharding drift,
  retrace hazards). `jax.make_jaxpr` only: no compile, no devices.
- `lint`   — `velint`, the project AST lint (`tools/velint.py --ci` is
  the ratchet-only CI gate).
- `concurrency` — whole-program thread-root/race analysis, lock-order
  cycle detection, wait-under-lock (rides the velint gate).
- `protocol` — HTTP endpoint contracts (shared token, bounded bodies)
  and the project-wide thread-owner stop() teardown contract (rides
  the velint gate).
- `resources` — static VMEM/HBM footprint pass: kernel VMEM verdicts
  that PRUNE the budgeted search (`--verify-workflow=resources`), and
  the per-device workflow HBM model behind the launcher pre-flight,
  the supervisor's "memory" report and the serving capacity hint.
- `planner` — the whole-system performance model + budgeted config
  search (docs/PLANNER.md): predicted step time (compute roofline +
  wire-aware comms + feed) over (mesh, batch, ZeRO, wire, fusion),
  gated by the `resources` ledgers, behind `tools/plan.py`.
- `modelcheck` — bounded protocol model checker: exhaustive
  interleaving + fault-injection exploration of the REAL election /
  membership / hot-swap logic (resilience/cluster.py, serving_watch)
  under a simulated world and virtual clock, against the 8-invariant
  ledger in docs/RESILIENCE.md. Every violation carries a replayable
  counterexample schedule. `tools/modelcheck.py --ci` is the gate;
  `--verify-workflow=modelcheck` runs a small fixed-budget sweep.

`findings.Finding` is the shared record the workflow-facing passes
emit; `concurrency`/`protocol` emit `lint.LintFinding` so they share
velint's baseline and suppression machinery. `graph`/`lint`/
`concurrency`/`protocol` import without jax; `trace` is loaded lazily
so import-light consumers (the supervisor's exit report) can guard it.
"""

from __future__ import annotations

from veles_tpu.analysis import concurrency, protocol  # noqa: F401
from veles_tpu.analysis.findings import (SEV_ERROR, SEV_WARN,  # noqa: F401
                                         Finding, errors, summarize)
from veles_tpu.analysis.graph import (WorkflowVerifyError,  # noqa: F401
                                      verify_workflow)
from veles_tpu.analysis.lint import lint_paths, lint_source  # noqa: F401


def __getattr__(name: str):
    # trace imports jax; load it only when actually used. importlib, not
    # `from ... import trace`: the from-import re-enters THIS hook while
    # the submodule is still unimported and recurses.
    if name in ("audit_fused_step", "audit_workflow",
                "environment_findings", "trace"):
        import importlib
        trace = importlib.import_module("veles_tpu.analysis.trace")
        if name == "trace":
            return trace
        return getattr(trace, name)
    if name == "planner":
        # planner imports the ops registry (a jax MODULE import, no
        # backend); lazy for the same import-light consumers as trace
        import importlib
        return importlib.import_module("veles_tpu.analysis.planner")
    if name == "modelcheck":
        # jax-free but heavy on protocol modules (cluster, serving_gen,
        # serving_watch); lazy so `import veles_tpu.analysis` stays a
        # findings/lint-sized import for the supervisor's exit report
        import importlib
        return importlib.import_module("veles_tpu.analysis.modelcheck")
    raise AttributeError(name)
