#!/usr/bin/env python3
"""What Python pays before XLA sees a language-model cell's train step
(`xing4_ep8.step`; `--config keye2_ep8`: `keye2_ep8.long16k`; `--config
qwen3next_ep16`: `qwen3next_ep16.seq8k`): the
seconds of `.trace()` and `.lower()`, the traced step's top-level
equations, the bytes of StableHLO, how many bodies and call sites the
`veles_*` kernels leave in the lowered module, and its `lax.cond`s.

No chip and no device buffer: the step is built from
`benchmark/configs/<config>.json` with zero weights and traced at
abstract arguments (ISSUE 34: PR 33 lost 15 s of `setup_s` that neither
the compile clock nor the device held; tracing and lowering are what the
persistent compile cache does not skip). The seconds are of THIS host;
the counts are the same everywhere, and
`tests/test_chip_compile.py::test_xing4_ep8_train_step_compiles_and_fits_one_chip`
asserts on them through `measure`.

    python tools/trace_cost.py                  # what this platform traces
    python tools/trace_cost.py --described --hc xla --hc pallas_one_pass
    python tools/trace_cost.py --described --flash xla_mha
    python tools/trace_cost.py --described --config keye2_ep8
    python tools/trace_cost.py --described --bare-locations --config keye2_ep8

`--described` places the arguments on a described v5e (no chip needed) and
answers the kernels' `available()` as that chip would, so that the Pallas
lowering is what traces and lowers here. `--bare-locations` keeps Python's
call stacks out of the module's locations, so that `stablehlo_sha256` says
whether two trees lower the SAME step: with them a kernel's payload carries
the line numbers of every file on its stack, and an edit anywhere above a
kernel moves the hash (PR 41: `keye2_ep8`, `xing4_ep8` and AlexNet lower to
the parent's module to the byte this way).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys
import time
from typing import Any, Dict, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: the configurations this tool builds: the sample whose `layer_table`
#: makes the program, and a sequence's targets beside its (batch, seq)
#: ids (xing4's head also reads the next-next token)
CONFIGS = {"xing4_ep8": ("xing4", (2,)), "keye2_ep8": ("keye2", ()),
           "qwen3next_ep16": ("qwen3next", ())}


def cell_step(sharding=None, config: str = "xing4_ep8"
              ) -> Tuple[Any, tuple, Dict[str, Any]]:
    """(step, abstract (state, ids, targets, weights), config) of the
    cell's program; `sharding` places every argument (a described chip's
    `SingleDeviceSharding`), None leaves them unplaced."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.parallel import checkpoint as ck
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    sample, target_tail = CONFIGS[config]
    sample = importlib.import_module("veles_tpu.samples." + sample)
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    batch, seq = cfg["batch_per_chip"], cfg["seq_len"]

    class ShapeOnlyLoader(FullBatchLoader):
        def load_data(self):
            self.bind_arrays(np.zeros((batch, seq), np.int32),
                             np.zeros((batch, seq) + target_tail, np.int32),
                             0, 0, batch)

    wf = StandardWorkflow(
        layers=sample.layer_table({**cfg, "init_std": 0.0}),
        loader=ShapeOnlyLoader(minibatch_size=batch, on_device=False),
        loss="softmax", n_classes=cfg["vocab_size"],
        decision_config={"max_epochs": 1, "fail_iterations": 1},
        gd_config=dict(cfg["optimizer"]), name=config + "_compile")
    wf.initialize(device=None)
    step = wf.build_fused_step(compute_dtype=cfg["compute_dtype"])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        ck._abstract_state(step, "threefry2x32"))
    key = jax.eval_shape(lambda: jax.random.key(0))
    state["key"] = sds(key.shape, key.dtype)
    args = (state, sds((batch, seq), jnp.int32),
            sds((batch, seq) + target_tail, jnp.int32),
            sds((batch,), jnp.float32))
    return step, args, cfg


def kernel_counts(stablehlo: str, prefix: str = "veles_"
                  ) -> Dict[str, Dict[str, int]]:
    """{kernel: {"bodies": custom calls of that name in the module's text,
    "sites": times a function holding one is reached from `main`}}. A
    kernel jitted once is ONE body in a private function called at every
    site; a kernel inlined a site is a body a site."""
    heads = [(m.group(1), m.start()) for m in re.finditer(
        r"func\.func (?:public |private )?@([\w.]+)\(", stablehlo)]
    funcs = {name: stablehlo[a:b] for (name, a), (_, b) in zip(
        heads, heads[1:] + [("", len(stablehlo))])}
    calls = {n: re.findall(r"call @([\w.]+)\(", body)
             for n, body in funcs.items()}
    reached: Dict[str, int] = {}

    def walk(name: str, times: int) -> None:
        reached[name] = reached.get(name, 0) + times
        for callee in calls.get(name, ()):
            walk(callee, times)

    walk("main", 1)
    out: Dict[str, Dict[str, int]] = {}
    for fname, body in funcs.items():
        for k in re.findall(r'kernel_name = "(%s\w+)"' % prefix, body):
            row = out.setdefault(k, {"bodies": 0, "sites": 0})
            row["bodies"] += 1
            row["sites"] += reached.get(fname, 0)
    return out


def measure(hc: Optional[str] = None, sharding=None,
            config: str = "xing4_ep8", flash: Optional[str] = None
            ) -> Dict[str, Any]:
    """Trace and lower `config`'s step once under the `hc` and the
    `flash_attn` lowering named (None: what the platform resolves) and
    count. Returns the numbers and the `lowered` object, so a caller can
    go on to compile it."""
    import jax

    from veles_tpu.ops import variants
    chosen = {op: name for op, name in (("hc", hc), ("flash_attn", flash))
              if name is not None}
    for op, name in chosen.items():
        variants.select(op, name)
    try:
        step, args, cfg = cell_step(sharding, config)
        table = step.variant_table()
        # at the platform's default precision, as the benchmark runs it
        with jax.default_matmul_precision("bfloat16"):
            fn = jax.jit(step.train_callable(), donate_argnums=(0,))
            t0 = time.perf_counter()
            traced = fn.trace(*args)
            t1 = time.perf_counter()
            lowered = traced.lower()
            t2 = time.perf_counter()
    finally:
        for op in chosen:
            variants.clear_selection(op)
    text = lowered.as_text()
    return {"hc": table.get("hc"), "dsa": table.get("dsa"),
            "flash_attn": table.get("flash_attn"),
            "trace_s": t1 - t0, "lower_s": t2 - t1,
            "equations": len(traced.jaxpr.eqns),
            "stablehlo_bytes": len(text),
            "stablehlo_sha256": hashlib.sha256(text.encode()).hexdigest(),
            "kernels": kernel_counts(text),
            # the held experts' (`ops/moe.py::_held_swiglu`), each inlined
            # where it runs: first forwards, backwards, and whatever a
            # block's `jax.checkpoint` computes again
            "conds": text.count("stablehlo.case"),
            "text": text, "lowered": lowered, "config": cfg, "step": step,
            "args": args}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--hc", action="append", default=None,
                    help="an `hc` lowering to trace under (repeatable)")
    ap.add_argument("--flash", default=None,
                    help="the `flash_attn` lowering to trace latent "
                         "attention's core under (xla_mha: its blocked "
                         "XLA form)")
    ap.add_argument("--config", choices=sorted(CONFIGS), default="xing4_ep8",
                    help="the benchmark configuration whose step to build")
    ap.add_argument("--described", action="store_true",
                    help="lower for a described v5e instead of the "
                         "platform's own device")
    ap.add_argument("--bare-locations", action="store_true",
                    help="no call stacks in the module's locations: the "
                         "hash then compares two trees' steps")
    ns = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    if ns.bare_locations:
        import jax
        jax.config.update("jax_traceback_in_locations_limit", 0)
    sharding = None
    if ns.described:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        from veles_tpu.ops import pallas_kernels as pk
        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        pk.available = lambda: True
    for hc in ns.hc or [None]:
        row = measure(hc, sharding, ns.config, ns.flash)
        print("TRACE_COST " + json.dumps(
            {k: v for k, v in row.items()
             if k not in ("text", "lowered", "config", "step", "args")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
