"""The `xing4_lm` session: a sparse-expert language model with latent
attention, hyper-connected residual streams and one multi-token-prediction
module, through `StandardWorkflow` and `FusedTrainStep`, on one chip, as a
share of a deployment (`configs/xing4_ep8.json`, README.md "Adding
things").

The program's layer table comes from the program's own sample
(`veles_tpu/samples/xing4.py::layer_table`, the same one `python -m
veles_tpu veles_tpu/samples/xing4.py --fused` trains); the weights, the
token stream, the counts and the plain reference are the benchmark's
(`xing4_seeded.py`, `xing4_ops_count.py`, `xing4_reference.py`).

Traffic parameters read here (`traffic/token_stream.json`): `balance_last`
(the steps before the window over which the held share of the slots is
read) and `balance_band` (how far from the even share it may lie for
`veles_moe_balance_reached`); the `train` driver reads the rest. Every
step trains on a FRESH batch: `batch_per_chip` sequences of `seq_len` + 2
ids, i.i.d. uniform over the held vocabulary, from `fold_in(stream_key(
seed, "inputs"), step)`, made on the device by a jitted call of its own
right before the step's; the targets are the next and the next-next
token. Nothing crosses the host link.

The expert layers count their slots inside the step, into int32 state.
The session copies that state (a few hundred bytes a layer) when the
warm-up's last `balance_last` steps begin and when they end, which is
where the window opens, and reads it once more when the program is
freed: the differences are the `veles_moe_*` counters
(`docs/OBSERVABILITY.md`). No step waits for the host.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmark import seeded, xing4_ops_count, xing4_reference, xing4_seeded

#: the numbers `check_against_reference` compares, each with a limit of
#: its own in `limits/<cell>.json`
LIMITS = ("loss_rel_gap", "grad_norm_gap", "grad_rel_err",
          "head_grad_rel_err", "dparam_norm_gap", "route_mismatch_share",
          "balance_bias_gap", "slots_dropped")
CHECK_STEPS = 3


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
        from veles_tpu.samples import xing4
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
                                 np.zeros((batch, seq, 2), np.int32),
                                 0, 0, batch)

        prng.seed_all(seeded.host_seed(seed))
        self.wf = StandardWorkflow(
            # the weights come from the seed below: the units draw none
            layers=xing4.layer_table({**cfg, "init_std": 0.0}),
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
            lambda k: xing4_seeded.make_params(cfg, k))
        self._batch_of = jax.jit(
            lambda key, k: xing4_seeded.make_batch(cfg, batch, key, k))
        self._copy = jax.jit(lambda aux: jax.tree.map(
            lambda a: a + 0, [{k: v for k, v in layer.items()
                               if not k.endswith("picked")}
                              for layer in aux]))
        self.state = None
        self.start_from(seed)
        want = jax.eval_shape(
            lambda k: xing4_seeded.make_params(cfg, k), self.wkey)
        have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            self.state["params"])
        if jax.tree.structure(want) != jax.tree.structure(have) or \
                jax.tree.leaves(want) != jax.tree.leaves(have):
            raise RuntimeError("the program's parameters are not the "
                               f"configuration's: {have} != {want}")
        if xing4_ops_count.n_params(cfg) != cfg["n_params"]:
            raise RuntimeError("n_params of the configuration file is wrong")
        self._mark("state")
        self.flops_per_step = xing4_ops_count.train_flops_per_step(
            cfg, batch)
        self.feed, self.feed_block_ms = None, []
        # where the window opens: `drivers/train.py` drives the first
        # steps, then max(2, steps_in_flight, warmup_steps) warm-up
        # dispatches, and tells its session nothing of it
        lag = int(tr.get("steps_in_flight", 1))
        self.k_open = CHECK_STEPS + max(2, lag, int(tr["warmup_steps"]))
        self.k_band = max(0, self.k_open - int(tr["balance_last"]))

    def counters_now(self):
        """A copy, on the device, of what the expert layers have counted
        so far (`znicz.lm.moe_counts` reads its host copy): no step waits
        for it."""
        return self._copy(self.state["aux"])

    def start_from(self, seed: int) -> None:
        """The state a run of `seed` starts from: the seed's weights, zero
        velocity, zero selection bias and counters, step 0 of its token
        stream. The compiled programs stay (`read_balance.py` follows
        several seeds with one)."""
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
        self.aux_band = self.aux_open = None

    def _mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter() - self.t_start

    # -- the loop ----------------------------------------------------------------

    def dispatch(self):
        """One pass of the loop: the step's batch is made, the step is
        dispatched. Returns the loss (not waited for) and the batch."""
        import jax
        ann = jax.profiler.TraceAnnotation
        with jax.profiler.StepTraceAnnotation("bench.step", step_num=self.k):
            if self.k == self.k_band:
                self.aux_band = self.counters_now()
            if self.k == self.k_open:
                self.aux_open = self.counters_now()
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

    @staticmethod
    def _of_expert_layers(aux, key: str) -> List[np.ndarray]:
        """One leaf of every expert layer's step state, in
        `xing4_ops_count.expert_layers`' order: the trunk's blocks, then
        the head's module."""
        out = [np.asarray(a[key]) for a in aux[1:-1] if a]
        if "mtp_" + key in aux[-1]:
            out.append(np.asarray(aux[-1]["mtp_" + key]))
        return out

    def first_steps(self) -> Dict[str, Any]:
        """Drive the step from the seed through CHECK_STEPS steps by the
        window's own call. Returns each step's two losses and selected
        experts, the per-leaf norm of the parameters' change and the
        selection biases after the last; the velocity after the first
        step is kept on the host (`first_grad`)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg

        @jax.jit
        def dparam_norms(params, k):
            d = jax.tree.map(jnp.subtract, params,
                             xing4_seeded.make_params(cfg, k))
            return {f"{i}.{name}": jnp.sqrt(jnp.sum(jnp.square(a)))
                    for i, layer in enumerate(d)
                    for name, a in layer.items()}

        prog: Dict[str, Any] = {"loss": [], "loss_main": [], "loss_mtp": [],
                                "picked": []}
        for i in range(CHECK_STEPS):
            loss, _fed = self.dispatch()
            if i == 0:
                self.vel1 = jax.device_get(self.state["vel"])
            aux = jax.device_get(self.state["aux"])
            prog["loss"].append(float(loss))
            prog["loss_main"].append(float(aux[-1]["ce_main"][0]))
            prog["loss_mtp"].append(float(aux[-1]["ce_mtp"][0]))
            prog["picked"].append(self._of_expert_layers(aux, "picked"))
        prog["bias"] = self._of_expert_layers(aux, "bias")
        prog["dparam_norm"] = {
            n: float(v) for n, v in dparam_norms(self.state["params"],
                                                 self.wkey).items()}
        self._mark("first_steps")
        return prog

    # -- after the window ----------------------------------------------------------

    def free_program(self) -> None:
        """The expert layers' counters are read and published, then the
        program's state goes, so that the reference has the chip to
        itself and the memory peak stays the program's."""
        import jax

        from veles_tpu.znicz import lm
        now = lm.moe_counts(self.step, jax.device_get(self.counters_now()))
        self.slots_dropped = sum(c["dropped"] for c in now.values())
        if self.aux_open is not None:
            base = lm.moe_counts(self.step, jax.device_get(self.aux_open))
            band = lm.moe_counts(self.step, jax.device_get(self.aux_band))
            even = self.cfg["n_routed_experts"] \
                / xing4_ops_count.dims(self.cfg)["experts"]
            shares = {n: (base[n]["held"] - band[n]["held"])
                      / max(base[n]["slots"] - band[n]["slots"], 1)
                      for n in base}
            reached = all(abs(s / even - 1.0) <= self.tr["balance_band"]
                          for s in shares.values())
            self.say(f"balance: held share of the slots over the last "
                     f"{self.tr['balance_last']} warm-up steps {shares}, "
                     f"even {even}: band reached {reached}")
            lm.publish_moe_counters(now, base, reached)
        for a in jax.tree.leaves(self.state):
            a.delete()
        self.state = None
        # a loaded step keeps its 6.7 GB of temporaries reserved (chip
        # runs of PR 32): its programs go too, or the reference has no room
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
        cfg = self.cfg
        batches = [self._batch_of(self.ikey, k) for k in range(CHECK_STEPS)]
        params0 = self._params_of(self.wkey)
        # a loaded program keeps its temporaries reserved (the weights'
        # generator makes 3 GB of random bits): the reference needs the
        # room, and keeps its own programs loaded from seed to seed
        self._params_of.clear_cache()
        t0 = time.perf_counter()
        ref = xing4_reference.reference_steps(
            cfg, params0, batches, first_params=p0,
            held_first=cfg.get("held_experts_first", 0),
            precision=precision, **kw)
        xing4_reference.unload()
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
        self.say(f"check: program losses {prog['loss_main']} + "
                 f"{self.cfg['mtp_loss_weight']} x {prog['loss_mtp']}, "
                 f"reference {ref['loss_main']}, {ref['loss_mtp']}")
        from veles_tpu.caches import cache_path
        where = cache_path("benchmark", self.cell["name"])
        os.makedirs(where, exist_ok=True)
        with open(os.path.join(where, "readings.json"), "w") as f:
            json.dump({"seed": self.seed,
                       **xing4_reference.tables(prog, ref)}, f)
        return xing4_reference.compare(self.cfg, prog, ref, limits)

    def limit_readings(self, prog: Dict[str, Any], control: bool
                       ) -> Dict[str, Any]:
        """What `read_limits.py` prints of one seed: what a sound run
        gives with the tables it was read from and, for a control seed,
        what the control gives."""
        no_limit = dict.fromkeys(LIMITS, float("inf"))

        def row(a, b):
            return {r["name"]: [r["value"], r["at"]] for r in
                    xing4_reference.compare(self.cfg, a, b, no_limit)}

        prog, ref, low = self.readings(prog, control)
        out: Dict[str, Any] = {"sound": row(prog, ref),
                               "tables": xing4_reference.tables(prog, ref)}
        if low is not None:
            ref = dict(ref, grad_diff_norm=ref["grad_diff_norm_of"]["control"])
            out["control"] = row(low, ref)
            out["control_tables"] = xing4_reference.tables(low, ref)
        return out

    def fed_rows_wrong(self) -> None:
        return None
