"""Ensemble training & evaluation.

Parity: reference `veles/ensemble/` (SURVEY.md §2.5) — train N instances
of a workflow (different seeds / config jitter), then serve the averaged
prediction. Population-parallel like genetics: each member is an
independent full run (trivially maps onto independent TPU slices —
SURVEY.md §2.4 checklist).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from veles_tpu.logger import Logger


class Ensemble(Logger):
    """`factory(seed) -> trained workflow` is called per member; members
    expose their forward chain for averaged inference.

    Population-parallel like genetics (SURVEY.md §2.4 checklist row —
    the reference distributed ensemble individuals across slaves):
    `train(parallel=True)` runs one `factory(seed)` per process in a
    ProcessPool, so members train concurrently on independent hosts/
    slices; the trained workflows return by pickle (the same
    whole-workflow pickle the Snapshotter uses). The factory must be
    picklable (module-level function or partial)."""

    def __init__(self, factory: Callable[[int], Any],
                 seeds: Sequence[int] = (1, 2, 3),
                 max_workers: Optional[int] = None,
                 queue_timeout_s: float = 8 * 3600.0) -> None:
        super().__init__()
        self.factory = factory
        self.seeds = list(seeds)
        self.max_workers = max_workers
        #: finite cluster-training deadline: a wedged worker renewing a
        #: member's lease while hung must surface as a TimeoutError, not
        #: block train() forever (ADVICE r5; the queue server also caps
        #: renewals per lease). Members are full training runs — the
        #: default is generous but FINITE.
        self.queue_timeout_s = queue_timeout_s
        self.members: List[Any] = []

    def train(self, parallel: bool = False,
              queue_server: Any = None) -> "Ensemble":
        if queue_server is not None:
            # cluster mode: members train on whichever -m workers lease
            # them (task_queue lease/re-queue semantics — the reference
            # distributed ensemble individuals across slaves; the worker
            # side is `member_worker` below) and come back as
            # whole-workflow pickles, the Snapshotter's format
            import pickle
            # results carry whole-workflow pickles; a result cap below
            # the artifact size would 413 every post (the server fails
            # the task, train() raises — but raising the cap up front
            # avoids burning a training run to find out)
            queue_server.max_body = max(queue_server.max_body, 256 << 20)
            self.info("training %d members over the cluster queue",
                      len(self.seeds))
            results = queue_server.submit(
                [{"seed": s} for s in self.seeds], with_artifacts=True,
                timeout_s=self.queue_timeout_s)
            members = []
            for s, (_fitness, artifact) in zip(self.seeds, results):
                if not artifact:
                    raise RuntimeError(
                        f"member seed={s} returned no trained artifact")
                wf = pickle.loads(artifact)
                # snapshot-restore contract: unpickled workflows carry
                # their trained params but need initialize() to rebuild
                # device arrays / jit dispatch before serving
                wf.initialize(device=None)
                members.append(wf)
            self.members = members
            return self
        if parallel:
            import concurrent.futures as cf
            import multiprocessing as mp
            workers = min(self.max_workers or len(self.seeds),
                          len(self.seeds))
            self.info("training %d members on %d processes",
                      len(self.seeds), workers)
            # a chip belongs to one process: a parent that already
            # holds it cannot hand it to member processes
            from veles_tpu.parallel.memstats import \
                refuse_spawn_if_chip_held
            refuse_spawn_if_chip_held("Ensemble.train(parallel=True)")
            # spawn, not fork: the parent's jax runtime is multithreaded
            # and fork()ed children can deadlock in its locks
            with cf.ProcessPoolExecutor(
                    workers, mp_context=mp.get_context("spawn")) as pool:
                futs = [pool.submit(self.factory, s) for s in self.seeds]
                # seed order preserved regardless of completion order
                self.members = [f.result() for f in futs]
            return self
        for seed in self.seeds:
            self.info("training member seed=%d", seed)
            self.members.append(self.factory(seed))
        return self

    def _member_outputs(self, x: np.ndarray) -> List[np.ndarray]:
        assert self.members, "train() first"
        outs = []
        for wf in self.members:
            wf.loader.minibatch_data.reset(np.asarray(x, np.float32))
            for fwd in wf.forwards:
                fwd.run()
            outs.append(np.asarray(wf.forwards[-1].output.mem).copy())
        return outs

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Averaged forward output (probabilities for softmax heads)."""
        outs = self._member_outputs(x)
        return sum(outs) / len(outs)

    def evaluate(self, x: np.ndarray, labels: np.ndarray) -> Dict[str, Any]:
        """One forward pass per member; ensemble and per-member errors
        both derive from the same outputs."""
        outs = self._member_outputs(x)
        probs = sum(outs) / len(outs)
        n_err = int((probs.argmax(axis=1) != labels).sum())
        member_errs = [int((p.argmax(1) != labels).sum()) for p in outs]
        return {"n_err": n_err, "member_errs": member_errs,
                "n_samples": len(labels)}


def member_worker(host: str, port: int,
                  factory: Callable[[int], Any],
                  token: Optional[str] = None,
                  give_up_s: float = 60.0) -> int:
    """Worker-process entry for cluster ensemble training: lease member
    seeds from the coordinator's FitnessQueueServer, train
    `factory(seed)` locally, post the best validation error plus the
    trained-workflow pickle back as the result artifact. Returns the
    number of members this worker trained.

    The production counterpart of `Ensemble.train(queue_server=...)` —
    run one of these per `-m` host (reference: slaves training ensemble
    individuals, SURVEY.md §2.5)."""
    import pickle

    from veles_tpu.task_queue import FitnessQueueWorker

    def train_member(payload: Dict[str, Any]):
        wf = factory(int(payload["seed"]))
        dec = getattr(wf, "decision", None)
        err = getattr(dec, "best_validation_err", None)
        return (float("inf") if err is None else float(err),
                pickle.dumps(wf))

    return FitnessQueueWorker(host, port, train_member, token=token,
                              give_up_s=give_up_s).run()
