"""The `keye2_lm` session: a sparse-expert language model with grouped-query
attention over the keys a learned indexer selects, through
`StandardWorkflow` and `FusedTrainStep`, on one chip, as a share of a
deployment (`configs/keye2_ep8.json`, README.md "Adding things").

The program's layer table comes from the program's own sample
(`veles_tpu/samples/keye2.py::layer_table`, the same one `python -m
veles_tpu veles_tpu/samples/keye2.py --fused` trains); the weights, the
token stream, the counts and the plain reference are the benchmark's
(`keye2_seeded.py`, `keye2_ops_count.py`, `keye2_reference.py`).

Every step trains on a FRESH batch: `batch_per_chip` sequences of
`seq_len` + 1 ids, i.i.d. uniform over the held vocabulary, from
`fold_in(stream_key(seed, "inputs"), step)`, made on the device by a
jitted call of its own right before the step's; the target is the next
token. Nothing crosses the host link. The `train` driver reads the
traffic file; this session reads nothing of it but `warmup_steps` and
`steps_in_flight` (to know where the window opens).

The blocks count inside the step, into int32 state: the slots of the
expert layers and the (query, key) pairs of the attention. The session
copies the counters (a few hundred bytes a layer) on the device after
every step of the window and keeps the copy at its opening and the last
five; when the program is freed it reads them: the differences are the
`veles_moe_*` and `veles_dsa_*` counters (`docs/OBSERVABILITY.md`), and
the held share of the slots over the window's first and last
`DRIFT_STEPS` steps is printed (a router that trains may drift: PERF.md
section 6). No step waits for the host.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import keye2_ops_count, keye2_reference, keye2_seeded, seeded

#: the numbers `check_against_reference` compares, each with a limit of
#: its own in `limits/<cell>.json`
LIMITS = ("loss_rel_gap", "grad_norm_gap", "grad_rel_err",
          "head_grad_rel_err", "dparam_norm_gap", "route_mismatch_share",
          "select_mismatch_share", "slots_dropped")
CHECK_STEPS = 3
DRIFT_STEPS = 4
#: step state too large to copy a step: the last step's selections
BULKY = ("picked", "selected")


class TrainSession:
    """The compiled step with its state and the loop that drives it:
    built once by set-up, driven through the first steps and handed, the
    same object, to the window."""

    def __init__(self, cell: Dict[str, Any], seed: int, t_start: float,
                 say: Callable[[str], None],
                 sabotage: Optional[Callable] = None) -> None:
        import jax

        from veles_tpu import prng
        from veles_tpu.loader.fullbatch import FullBatchLoader
        from veles_tpu.samples import keye2
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
            layers=keye2.layer_table({**cfg, "init_std": 0.0}),
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
            lambda k: keye2_seeded.make_params(cfg, k))
        self._batch_of = jax.jit(
            lambda key, k: keye2_seeded.make_batch(cfg, batch, key, k))
        self._copy = jax.jit(lambda aux: jax.tree.map(
            lambda a: a + 0, [{k: v for k, v in layer.items()
                               if k not in BULKY} for layer in aux]))
        self.state = None
        self.start_from(seed)
        want = jax.eval_shape(
            lambda k: keye2_seeded.make_params(cfg, k), self.wkey)
        have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            self.state["params"])
        if jax.tree.structure(want) != jax.tree.structure(have) or \
                jax.tree.leaves(want) != jax.tree.leaves(have):
            raise RuntimeError("the program's parameters are not the "
                               f"configuration's: {have} != {want}")
        if keye2_ops_count.n_params(cfg) != cfg["n_params"]:
            raise RuntimeError("n_params of the configuration file is wrong")
        self._mark("state")
        self.flops_per_step = keye2_ops_count.train_flops_per_step(
            cfg, batch)
        self.feed, self.feed_block_ms = None, []
        # where the window opens: `drivers/train.py` drives the first
        # steps, then max(2, steps_in_flight, warmup_steps) warm-up
        # dispatches, and tells its session nothing of it
        lag = int(tr.get("steps_in_flight", 1))
        self.k_open = CHECK_STEPS + max(2, lag, int(tr["warmup_steps"]))

    def counters_now(self):
        """A copy, on the device, of what the blocks have counted so far
        (`znicz.lm.moe_counts` and `dsa_counts` read its host copy): no
        step waits for it."""
        return self._copy(self.state["aux"])

    def start_from(self, seed: int) -> None:
        """The state a run of `seed` starts from: the seed's weights, zero
        velocity and counters, step 0 of its token stream. The compiled
        programs stay (a process may follow several seeds with one)."""
        import jax
        for a in jax.tree.leaves(self.state):
            a.delete()
        self.seed = seed
        self.wkey = seeded.stream_key(seed, "weights")
        self.ikey = seeded.stream_key(seed, "inputs")
        self.state = self.step.init_state()
        for a in jax.tree.leaves(self.state["params"]):
            a.delete()
        self.state["params"] = self._params_of(self.wkey)
        jax.block_until_ready(self.state["params"])
        self.k = 0
        self.pending: deque = deque()
        #: the counters before each step of the window: those of its
        #: first DRIFT_STEPS + 1 steps and of the last DRIFT_STEPS + 1
        self.aux_first: List[Any] = []
        self.aux_last: deque = deque(maxlen=DRIFT_STEPS + 1)
        jax.block_until_ready(self.counters_now())  # compiled by set-up

    def _mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter() - self.t_start

    # -- the loop ----------------------------------------------------------------

    def dispatch(self):
        """One pass of the loop: the step's batch is made, the step is
        dispatched. Returns the loss (not waited for) and the batch."""
        import jax
        ann = jax.profiler.TraceAnnotation
        with jax.profiler.StepTraceAnnotation("bench.step", step_num=self.k):
            if self.k >= self.k_open:
                now = self.counters_now()
                if len(self.aux_first) <= DRIFT_STEPS:
                    self.aux_first.append(now)
                self.aux_last.append(now)
            with ann("bench.batch"):
                x, y = self._batch_of(self.ikey, self.k)
            with ann("bench.dispatch"):
                self.state, (loss, _n_err) = self.step.train(
                    self.state, x, y, None)
        self.k += 1
        return loss, (x, y, None)

    def sync_oldest(self):
        """Wait for the oldest step in flight; its completion stamp."""
        import jax
        loss = self.pending.popleft()
        with jax.profiler.TraceAnnotation("bench.sync"):
            loss.block_until_ready()
        return time.perf_counter(), loss

    # -- the first steps, which the reference follows -----------------------------

    def first_steps(self) -> Dict[str, Any]:
        """Drive the step from the seed through CHECK_STEPS steps by the
        window's own call. Returns each step's loss and its three terms,
        selected experts and selected keys, and the per-leaf norm of the
        parameters' change after the last; the velocity after the first
        step is kept on the host (`first_grad`)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg

        @jax.jit
        def dparam_norms(params, k):
            d = jax.tree.map(jnp.subtract, params,
                             keye2_seeded.make_params(cfg, k))
            return {f"{i}.{name}": jnp.sqrt(jnp.sum(jnp.square(a)))
                    for i, layer in enumerate(d)
                    for name, a in layer.items()}

        prog: Dict[str, Any] = {"loss": [], "picked": [], "selected": [],
                                **{t: [] for t in keye2_reference.TERMS}}
        for i in range(CHECK_STEPS):
            loss, _fed = self.dispatch()
            if i == 0:
                self.vel1 = jax.device_get(self.state["vel"])
            aux = jax.device_get(self.state["aux"])
            prog["loss"].append(float(loss))
            for term, key in zip(keye2_reference.TERMS,
                                 ("ce_main", "term_balance", "term_index")):
                prog[term].append(float(aux[-1][key][0]))
            for key in BULKY:
                prog[key].append([np.asarray(a[key]) for a in aux[1:-1]])
        prog["dparam_norm"] = {
            n: float(v) for n, v in dparam_norms(self.state["params"],
                                                 self.wkey).items()}
        self._mark("first_steps")
        return prog

    # -- after the window ----------------------------------------------------------

    def free_program(self) -> None:
        """The blocks' counters are read and published, then the
        program's state goes, so that the reference has the chip to
        itself and the memory peak stays the program's."""
        import jax

        from veles_tpu.znicz import lm
        got = jax.device_get([self.counters_now(), self.aux_first,
                              list(self.aux_last)])
        now = lm.moe_counts(self.step, got[0])
        self.slots_dropped = sum(c["dropped"] for c in now.values())
        if got[1]:
            base = lm.moe_counts(self.step, got[1][0])
            lm.publish_moe_counters(now, base)
            lm.publish_dsa_counters(lm.dsa_counts(self.step, got[0]),
                                    lm.dsa_counts(self.step, got[1][0]))

            def held_share(a, b):
                a, b = (lm.moe_counts(self.step, c) for c in (a, b))
                return 100.0 * sum(
                    (b[n]["held"] - a[n]["held"])
                    / max(b[n]["slots"] - a[n]["slots"], 1)
                    for n in a) / len(a)

            if len(got[1]) > DRIFT_STEPS and len(got[2]) > DRIFT_STEPS:
                self.say(f"drift: held share of the slots, % (mean over "
                         f"the layers), over the window's first "
                         f"{DRIFT_STEPS} steps "
                         f"{held_share(got[1][0], got[1][-1])!r}, over the "
                         f"{DRIFT_STEPS} before its last "
                         f"{held_share(got[2][0], got[2][-1])!r}")
        for a in jax.tree.leaves(self.state):
            a.delete()
        self.state = None
        self.aux_first, self.aux_last = [], deque()
        # a loaded step keeps its temporaries reserved: its programs go
        # too, or the reference has no room
        self.step.release()
        self.step = self.wf = None
        import gc
        gc.unfreeze()               # (the driver froze what set-up built)
        gc.collect()
        self.say(f"memory after the program went: "
                 f"{self.devices[0].memory_stats()}")

    def first_grad(self, p0):
        """The program's first gradient as its optimizer got it, from the
        velocity after one step from rest, v1 = -rate (g + wd w0), w0 =
        `p0` on the host: on the host, one leaf at a time."""
        opt = self.cfg["optimizer"]
        return tuple(
            {name: -vl[name] / np.float32(
                opt["learning_rate"] * (opt["learning_rate_bias"]
                                        if p.ndim == 1 else 1.0))
             - np.float32(opt["weights_decay"]) * p
             for name, p in pl.items()} for vl, pl in zip(self.vel1, p0))

    def reference(self, p0, precision: str = "float32", **kw: Any
                  ) -> Dict[str, Any]:
        """The plain reference over the same first steps; `p0` is the
        host's copy of the first parameters."""
        batches = [self._batch_of(self.ikey, k) for k in range(CHECK_STEPS)]
        params0 = self._params_of(self.wkey)
        self._params_of.clear_cache()
        t0 = time.perf_counter()
        ref = keye2_reference.reference_steps(
            self.cfg, params0, batches, first_params=p0,
            precision=precision, **kw)
        keye2_reference.unload()
        self.say(f"reference ({precision}): {time.perf_counter() - t0:.1f} s"
                 f" in all, of them {ref.pop('seconds')}")
        return ref

    def readings(self, prog: Dict[str, Any], control: bool = False):
        """(program's readings completed, the reference's, the control's
        or None): run after `free_program`, shared by a run and by
        `read_limits.py`. The control is the reference in the precision
        below the configuration's, put in the program's place: it runs
        first and the one pass of the reference reads both gradients."""
        import jax
        p0 = jax.device_get(self._params_of(self.wkey))
        g_prog = self.first_grad(p0)
        self.vel1 = None
        prog["grad_norm"] = {
            f"{i}.{name}": float(np.linalg.norm(g.ravel()))
            for i, layer in enumerate(g_prog) for name, g in layer.items()}
        prog["slots_dropped"] = getattr(self, "slots_dropped", 0)
        low = None
        if control:
            low = self.reference(p0, precision="float8",
                                 keep_first_grad=True)
            low["slots_dropped"] = 0
        ref = self.reference(
            p0, first_grad_of_program=g_prog,
            first_grads_of={} if low is None
            else {"control": low.pop("first_grad")})
        return prog, ref, low

    def check_against_reference(self, prog: Dict[str, Any],
                                limits: Dict[str, float]
                                ) -> List[Dict[str, Any]]:
        """The rows of the `correct` table: each number of `LIMITS` as the
        first steps read it, beside its limit. What they were read from,
        leaf by leaf, is left beside the trace (`readings.json`), for
        whoever sets limits."""
        prog, ref, _ = self.readings(prog)
        self.say("check: program " + ", ".join(
            f"{t} {prog[t]}" for t in keye2_reference.TERMS)
            + "; reference " + ", ".join(
            f"{t} {ref[t]}" for t in keye2_reference.TERMS))
        from veles_tpu.caches import cache_path
        where = cache_path("benchmark", self.cell["name"])
        os.makedirs(where, exist_ok=True)
        with open(os.path.join(where, "readings.json"), "w") as f:
            json.dump({"seed": self.seed,
                       **keye2_reference.tables(prog, ref)}, f)
        return keye2_reference.compare(self.cfg, prog, ref, limits)

    def limit_readings(self, prog: Dict[str, Any], control: bool
                       ) -> Dict[str, Any]:
        """What `read_limits.py` prints of one seed: what a sound run
        gives with the tables it was read from and, for a control seed,
        what the control gives."""
        no_limit = dict.fromkeys(LIMITS, float("inf"))

        def row(a, b):
            return {r["name"]: [r["value"], r["at"]] for r in
                    keye2_reference.compare(self.cfg, a, b, no_limit)}

        prog, ref, low = self.readings(prog, control)
        out: Dict[str, Any] = {"sound": row(prog, ref),
                               "tables": keye2_reference.tables(prog, ref)}
        if low is not None:
            ref = dict(ref, grad_diff_norm=ref["grad_diff_norm_of"]["control"])
            out["control"] = row(low, ref)
            out["control_tables"] = keye2_reference.tables(low, ref)
        return out

    def fed_rows_wrong(self) -> None:
        return None
