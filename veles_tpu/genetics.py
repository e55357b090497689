"""Genetic hyperparameter search.

Parity: reference `veles/genetics/` (SURVEY.md §2.5) — a chromosome is a
vector of config values (the reference patched `root` paths); fitness is
the best validation metric of a full workflow run; the GA loop does
selection, uniform crossover, and gaussian/reset mutation, distributing
individuals across slaves. Here individuals fan out over processes (the
SPMD cluster trains ONE model; population parallelism is process-level,
exactly the reference's model — SURVEY.md §2.4 checklist).
"""

from __future__ import annotations

import concurrent.futures as cf
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from veles_tpu import prng
from veles_tpu.logger import Logger


@dataclass
class Tune:
    """A tunable config entry: dotted `path` sampled in [lo, hi]
    (log-uniform when `log`; rounded to int when `integer`)."""
    path: str
    lo: float
    hi: float
    log: bool = False
    integer: bool = False

    def sample(self, gen) -> float:
        if self.log:
            v = float(np.exp(gen.fill_uniform(
                (), np.log(self.lo), np.log(self.hi), np.float64)))
        else:
            v = float(gen.fill_uniform((), self.lo, self.hi, np.float64))
        return int(round(v)) if self.integer else v

    def clip(self, v: float) -> float:
        v = min(max(v, self.lo), self.hi)
        return int(round(v)) if self.integer else v


@dataclass
class Chromosome:
    values: List[float]
    fitness: Optional[float] = None  # lower is better

    def overrides(self, tunables: Sequence[Tune]) -> Dict[str, float]:
        return {t.path: v for t, v in zip(tunables, self.values)}


class Population(Logger):
    """GA over config space. `fitness_fn(overrides) -> float` runs one
    full workflow (typically returning best_validation_err); it must be a
    top-level function when `max_workers > 1` (process pool pickling)."""

    def __init__(self, tunables: Sequence[Tune],
                 fitness_fn: Callable[[Dict[str, float]], float],
                 size: int = 12, elite: int = 2,
                 mutation_rate: float = 0.25,
                 mutation_scale: float = 0.2,
                 max_workers: int = 1,
                 queue_server: Any = None,
                 queue_timeout_s: float = 4 * 3600.0,
                 rng_name: str = "genetics") -> None:
        super().__init__()
        self.tunables = list(tunables)
        self.fitness_fn = fitness_fn
        #: finite cluster-evaluation deadline per generation: a wedged
        #: worker (renewing its lease while hung) must surface as a
        #: TimeoutError, not block the GA forever (ADVICE r5; the server
        #: additionally caps renewals per lease)
        self.queue_timeout_s = queue_timeout_s
        self.size = size
        self.elite = elite
        self.mutation_rate = mutation_rate
        self.mutation_scale = mutation_scale
        self.max_workers = max_workers
        #: a started task_queue.FitnessQueueServer: individuals are
        #: leased to cluster workers instead of evaluated locally (the
        #: reference's master-distributes-individuals-to-slaves mode,
        #: with lease-expiry re-queue on worker loss)
        self.queue_server = queue_server
        self.gen = prng.get(rng_name)
        self.members: List[Chromosome] = [
            Chromosome([t.sample(self.gen) for t in self.tunables])
            for _ in range(size)]
        self.generation = 0
        self.history: List[Tuple[int, float]] = []

    # -- GA operators --------------------------------------------------------

    def _crossover(self, a: Chromosome, b: Chromosome) -> Chromosome:
        mask = self.gen.fill_uniform((len(self.tunables),), 0, 1,
                                     np.float64) < 0.5
        vals = [av if m else bv
                for av, bv, m in zip(a.values, b.values, mask)]
        return Chromosome(vals)

    def _mutate(self, c: Chromosome) -> Chromosome:
        vals = list(c.values)
        for i, t in enumerate(self.tunables):
            if float(self.gen.fill_uniform((), 0, 1, np.float64)) \
                    < self.mutation_rate:
                span = (np.log(t.hi) - np.log(t.lo)) if t.log \
                    else (t.hi - t.lo)
                delta = float(self.gen.fill_normal(
                    (), 0.0, self.mutation_scale * span, np.float64))
                if t.log:
                    vals[i] = t.clip(float(np.exp(np.log(vals[i]) + delta)))
                else:
                    vals[i] = t.clip(vals[i] + delta)
        return Chromosome(vals)

    def _tournament(self, scored: List[Chromosome]) -> Chromosome:
        k = max(2, self.size // 4)
        picks = [scored[int(self.gen.randint(0, len(scored)))]
                 for _ in range(k)]
        return min(picks, key=lambda c: c.fitness)

    # -- evaluation ----------------------------------------------------------

    def _evaluate(self, members: List[Chromosome]) -> None:
        todo = [m for m in members if m.fitness is None]
        if not todo:
            return
        if self.queue_server is not None:
            fitnesses = self.queue_server.submit(
                [m.overrides(self.tunables) for m in todo],
                timeout_s=self.queue_timeout_s)
            for m, f in zip(todo, fitnesses):
                m.fitness = float(f)
        elif self.max_workers > 1:
            # a chip belongs to one process: a parent that already
            # holds it cannot hand it to fitness workers
            from veles_tpu.parallel.memstats import \
                refuse_spawn_if_chip_held
            refuse_spawn_if_chip_held("Population(max_workers>1)")
            with cf.ProcessPoolExecutor(self.max_workers) as pool:
                futs = {pool.submit(self.fitness_fn,
                                    m.overrides(self.tunables)): m
                        for m in todo}
                for fut in cf.as_completed(futs):
                    futs[fut].fitness = float(fut.result())
        else:
            for m in todo:
                m.fitness = float(self.fitness_fn(
                    m.overrides(self.tunables)))

    # -- main loop -----------------------------------------------------------

    def evolve(self, generations: int = 5) -> Chromosome:
        for _ in range(generations):
            self._evaluate(self.members)
            self.members.sort(key=lambda c: c.fitness)
            best = self.members[0]
            self.history.append((self.generation, best.fitness))
            self.info("generation %d: best=%g values=%s",
                      self.generation, best.fitness,
                      best.overrides(self.tunables))
            nxt = [Chromosome(list(m.values), m.fitness)
                   for m in self.members[:self.elite]]
            while len(nxt) < self.size:
                child = self._crossover(self._tournament(self.members),
                                        self._tournament(self.members))
                nxt.append(self._mutate(child))
            self.members = nxt
            self.generation += 1
        self._evaluate(self.members)
        self.members.sort(key=lambda c: c.fitness)
        return self.members[0]

    @property
    def best(self) -> Chromosome:
        done = [m for m in self.members if m.fitness is not None]
        return min(done, key=lambda c: c.fitness)
