"""Granular (unit-by-unit dispatch) vs fused (one XLA dispatch per
minibatch) AlexNet training cost — VERDICT r4 item 9: the
reference-parity execution model's measured price.

Both modes run the SAME minibatch count on the same resident batch with
per-step host dispatch (no train_repeat scan, so the two loops differ
only in dispatch granularity). The granular number includes real
per-unit host dispatch latency — that is part of the mode's honest
cost, and the caveat field says so.

Usage: python tools/granular_vs_fused.py [batch] [steps]
Prints one JSON line with both rates and the ratio.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(batch: int = 512, steps: int = 8) -> None:
    import jax

    from veles_tpu import prng
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.samples.alexnet import create_workflow

    def fresh():
        prng.seed_all(1)
        wf = create_workflow(minibatch_size=batch, n_train=2 * batch,
                             n_validation=batch)
        wf.initialize(device=None)
        return wf

    # -- granular: the unit graph, one dispatch per unit. The batch is
    # STAGED ONCE before timing (same as the fused loop's resident
    # batch) so the two loops differ only in dispatch granularity, not
    # loader/H2D cost -------------------------------------------------------
    wf = fresh()
    ld = wf.loader
    # stage one TRAIN batch: minibatch_class is CONSTRUCTED as TRAIN, so
    # run() at least once and then until the schedule lands on TRAIN
    ld.run()
    while int(ld.minibatch_class) != TRAIN:
        ld.run()

    def granular_minibatch():
        for u in wf.forwards:
            u.run()
        wf.evaluator.run()
        for g in wf.gds:
            g.run()
        return True

    def sync_granular():
        # barrier on the LAST unit the loop dispatched (gds run in
        # backprop order, so gds[-1] is final). Units run the xla
        # backend even with device=None (backend_name defaults to
        # "xla"), so host .mem would be a STALE buffer, not a barrier.
        g = wf.gds[-1] if wf.gds else wf.forwards[-1]
        arr = getattr(g, "weights", None) \
            or getattr(g, "err_input", None) or wf.forwards[-1].output
        jax.block_until_ready(arr.devmem(g.device))

    done = 0
    while done < 2:                                # warmup/compile
        done += granular_minibatch()
    sync_granular()
    t0 = time.perf_counter()
    done = 0
    while done < steps:
        done += granular_minibatch()
    sync_granular()
    granular_rate = batch * steps / (time.perf_counter() - t0)

    # -- fused: one donated XLA computation per minibatch. SAME f32
    # compute as the granular units — a bf16 fused step would conflate
    # dtype speedup with dispatch granularity, the one thing this tool
    # isolates --------------------------------------------------------------
    wf2 = fresh()
    step = wf2.build_fused_step()
    state = step.init_state()
    import jax.numpy as jnp
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    shape = (batch,) + tuple(wf2.loader.minibatch_data.shape[1:])
    x = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(k1)
    y = jax.jit(lambda k: jax.random.randint(k, (batch,), 0, 64))(k2)
    state, _ = step.train(state, x, y)             # compile + warm
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, _ = step.train(state, x, y)
    jax.block_until_ready(state)
    fused_rate = batch * steps / (time.perf_counter() - t0)

    print(json.dumps({
        "metric": "alexnet_granular_vs_fused",
        "batch": batch, "steps": steps,
        "granular_samples_per_sec": round(granular_rate, 2),
        "fused_samples_per_sec": round(fused_rate, 2),
        "fused_over_granular": round(fused_rate / granular_rate, 3),
        "compute_dtype": "float32 (both modes)",
        "device_kind": jax.devices()[0].device_kind,
        "caveat": "granular includes per-unit host dispatch: that "
                  "latency is part of the mode's cost",
    }))


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:3]))
