"""The `qwen3next_lm` session: a hybrid language model, three Gated
DeltaNet layers to one gated full-attention layer, many small experts
beside a gated shared expert, through `StandardWorkflow` and
`FusedTrainStep`, on one chip, as a share of a deployment
(`configs/qwen3next_ep16.json`, README.md "Adding things").

The third language-model session, and no third copy: the loop, the fresh
batch a step, the counters' copies, the arithmetic of where the window
opens, the first gradient read from the velocity and the order in which
the control and the reference run are `sessions/keye2_lm.py`'s
`TrainSession`, which this one subclasses. What the family changes is
here: the program's layer table comes from the program's own sample
(`veles_tpu/samples/qwen3next.py::layer_table`); the weights, the counts
and the plain reference are `qwen3next_seeded.py`, `qwen3next_ops_count.py`
and `qwen3next_reference.py`; the loss has two terms; the blocks count
slots (`veles_moe_*`) and, the linear layers, tokens and chunks
(`veles_gdn_*`), with the last step's final-state root mean square and
lowest cumulative log-decay.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import (qwen3next_ops_count, qwen3next_reference,
                       qwen3next_seeded, seeded)
from benchmark.sessions import keye2_lm
from benchmark.sessions.keye2_lm import CHECK_STEPS

#: the numbers `check_against_reference` compares, each with a limit of
#: its own in `limits/<cell>.json`
LIMITS = ("loss_rel_gap", "grad_norm_gap", "grad_rel_err",
          "head_grad_rel_err", "gdn_out_grad_rel_err", "gdn_state_rel_err",
          "dparam_norm_gap", "route_mismatch_share", "slots_dropped")
#: step state too large to copy a step: the last step's selected experts
#: and the linear layers' final states
BULKY = keye2_lm.BULKY + ("gdn_state",)


class TrainSession(keye2_lm.TrainSession):
    """`keye2_lm.TrainSession` over another family's program, weights,
    counts and reference."""

    def __init__(self, cell: Dict[str, Any], seed: int, t_start: float,
                 say: Callable[[str], None],
                 sabotage: Optional[Callable] = None) -> None:
        import jax

        from veles_tpu import prng
        from veles_tpu.loader.fullbatch import FullBatchLoader
        from veles_tpu.samples import qwen3next
        from veles_tpu.znicz.standard_workflow import StandardWorkflow

        self.cell, self.seed, self.say, self.t_start = cell, seed, say, t_start
        cfg, tr = cell["config_data"], cell["traffic_data"]
        self.cfg, self.tr = cfg, tr
        self.marks = {"import": time.perf_counter() - t_start}
        self.devices = jax.devices()[:cell["chips"]]
        self.batch = batch = cfg["batch_per_chip"]
        seq = cfg["seq_len"]

        class ShapeOnlyLoader(FullBatchLoader):
            """Gives the workflow its input shape; the token stream never
            passes through it."""

            def load_data(self) -> None:
                self.bind_arrays(np.zeros((batch, seq), np.int32),
                                 np.zeros((batch, seq), np.int32),
                                 0, 0, batch)

        prng.seed_all(seeded.host_seed(seed))
        self.wf = StandardWorkflow(
            # the weights come from the seed below: the units draw none
            layers=qwen3next.layer_table({**cfg, "init_std": 0.0}),
            loader=ShapeOnlyLoader(minibatch_size=batch, on_device=False),
            loss="softmax", n_classes=cfg["vocab_size"],
            decision_config={"max_epochs": 10 ** 9,
                             "fail_iterations": 10 ** 9},
            gd_config=dict(cfg["optimizer"]), name="bench_" + cfg["name"])
        self.wf.initialize(device=None)
        self._mark("initialize")
        step = self.wf.build_fused_step(mesh=None,
                                        compute_dtype=cfg["compute_dtype"])
        self.step = sabotage(step) if sabotage is not None else step
        self._params_of = jax.jit(
            lambda k: qwen3next_seeded.make_params(cfg, k))
        self._batch_of = jax.jit(
            lambda key, k: qwen3next_seeded.make_batch(cfg, batch, key, k))
        self._copy = jax.jit(lambda aux: jax.tree.map(
            lambda a: a + 0, [{k: v for k, v in layer.items()
                               if k not in BULKY} for layer in aux]))
        self.state = None
        self.start_from(seed)
        want = jax.eval_shape(
            lambda k: qwen3next_seeded.make_params(cfg, k), self.wkey)
        have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            self.state["params"])
        if jax.tree.structure(want) != jax.tree.structure(have) or \
                jax.tree.leaves(want) != jax.tree.leaves(have):
            raise RuntimeError("the program's parameters are not the "
                               f"configuration's: {have} != {want}")
        if qwen3next_ops_count.n_params(cfg) != cfg["n_params"]:
            raise RuntimeError("n_params of the configuration file is wrong")
        self._mark("state")
        self.flops_per_step = qwen3next_ops_count.train_flops_per_step(
            cfg, batch)
        self.feed, self.feed_block_ms = None, []
        # where the window opens (`keye2_lm.TrainSession` says why)
        lag = int(tr.get("steps_in_flight", 1))
        self.k_open = CHECK_STEPS + max(2, lag, int(tr["warmup_steps"]))

    # -- the first steps, which the reference follows -----------------------------

    def first_steps(self) -> Dict[str, Any]:
        """Drive the step from the seed through CHECK_STEPS steps by the
        window's own call. Returns each step's loss, its two terms,
        selected experts and the linear layers' final states, and the
        per-leaf norm of the parameters' change after the last; the
        velocity after the first step is kept on the host
        (`first_grad`)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg

        @jax.jit
        def dparam_norms(params, k):
            d = jax.tree.map(jnp.subtract, params,
                             qwen3next_seeded.make_params(cfg, k))
            return {f"{i}.{name}": jnp.sqrt(jnp.sum(jnp.square(a)))
                    for i, layer in enumerate(d)
                    for name, a in layer.items()}

        prog: Dict[str, Any] = {"loss": [], "picked": [], "gdn_state": [],
                                **{t: [] for t in qwen3next_reference.TERMS}}
        for i in range(CHECK_STEPS):
            loss, _fed = self.dispatch()
            if i == 0:
                self.vel1 = jax.device_get(self.state["vel"])
            aux = jax.device_get(self.state["aux"])
            prog["loss"].append(float(loss))
            for term, key in zip(qwen3next_reference.TERMS,
                                 ("ce_main", "term_balance")):
                prog[term].append(float(aux[-1][key][0]))
            prog["picked"].append([np.asarray(a["picked"])
                                   for a in aux[1:-1]])
            prog["gdn_state"].append([np.asarray(a["gdn_state"])
                                      for a in aux[1:-1]
                                      if "gdn_state" in a])
        prog["dparam_norm"] = {
            n: float(v) for n, v in dparam_norms(self.state["params"],
                                                 self.wkey).items()}
        self._mark("first_steps")
        return prog

    # -- after the window ----------------------------------------------------------

    def free_program(self) -> None:
        """The linear layers' counters are read and published; the expert
        layers' and the rest are `keye2_lm.TrainSession.free_program`'s."""
        import jax

        from veles_tpu.znicz import lm
        got = jax.device_get([self.counters_now(), self.aux_first[:1]])
        now = lm.gdn_counts(self.step, got[0])
        lm.publish_gdn_counters(
            now, lm.gdn_counts(self.step, got[1][0]) if got[1] else None)
        self.say("gdn: " + ", ".join(
            f"{layer} state rms {c['state_rms']:.4g} lowest cumulative "
            f"log-decay {c['decay_min']:.4g}" for layer, c in now.items()))
        super().free_program()

    def reference(self, p0, precision: str = "float32", **kw: Any
                  ) -> Dict[str, Any]:
        """The plain reference over the same first steps; `p0` is the
        host's copy of the first parameters."""
        batches = [self._batch_of(self.ikey, k) for k in range(CHECK_STEPS)]
        params0 = self._params_of(self.wkey)
        self._params_of.clear_cache()
        t0 = time.perf_counter()
        ref = qwen3next_reference.reference_steps(
            self.cfg, params0, batches, first_params=p0,
            precision=precision, **kw)
        qwen3next_reference.unload()
        self.say(f"reference ({precision}): {time.perf_counter() - t0:.1f} s"
                 f" in all, of them {ref.pop('seconds')}")
        return ref

    def check_against_reference(self, prog: Dict[str, Any],
                                limits: Dict[str, float]
                                ) -> List[Dict[str, Any]]:
        """The rows of the `correct` table: each number of `LIMITS` as the
        first steps read it, beside its limit. What they were read from,
        leaf by leaf, is left beside the trace (`readings.json`), for
        whoever sets limits."""
        prog, ref, _ = self.readings(prog)
        self.say("check: program " + ", ".join(
            f"{t} {prog[t]}" for t in qwen3next_reference.TERMS)
            + "; reference " + ", ".join(
            f"{t} {ref[t]}" for t in qwen3next_reference.TERMS))
        from veles_tpu.caches import cache_path
        where = cache_path("benchmark", self.cell["name"])
        os.makedirs(where, exist_ok=True)
        with open(os.path.join(where, "readings.json"), "w") as f:
            json.dump({"seed": self.seed,
                       **qwen3next_reference.tables(prog, ref)}, f)
        return qwen3next_reference.compare(self.cfg, prog, ref, limits)

    def limit_readings(self, prog: Dict[str, Any], control: bool
                       ) -> Dict[str, Any]:
        """What `read_limits.py` prints of one seed: what a sound run
        gives with the tables it was read from and, for a control seed,
        what the control gives."""
        no_limit = dict.fromkeys(LIMITS, float("inf"))

        def row(a, b):
            return {r["name"]: [r["value"], r["at"]] for r in
                    qwen3next_reference.compare(self.cfg, a, b, no_limit)}

        prog, ref, low = self.readings(prog, control)
        out: Dict[str, Any] = {"sound": row(prog, ref),
                               "tables": qwen3next_reference.tables(prog, ref)}
        if low is not None:
            ref = dict(ref, grad_diff_norm=ref["grad_diff_norm_of"]["control"])
            out["control"] = row(low, ref)
            out["control_tables"] = qwen3next_reference.tables(low, ref)
        return out
