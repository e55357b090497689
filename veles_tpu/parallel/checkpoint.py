"""Sharded checkpointing of fused training state via Orbax.

The Snapshotter's whole-workflow pickle (reference parity, SURVEY.md
§5.4) gathers every array to host process 0 — right for the reference's
scale, wrong past it. This is the at-scale companion (the SURVEY §7
"orbax for arrays" slot): the fused step's state pytree (params,
velocities, PRNG key, lr scale) saves and restores WITH its shardings —
each host writes/reads only its addressable shards, so TP/EP-partitioned
states never materialize on one host. The workflow pickle still carries
topology/config; `save_state`/`restore_state` carry the tensors.

Restore targets come from the step itself (`init_state` under
eval_shape), so a state saved from a dp/gspmd/ep step restores into a
freshly built step of the same geometry without running a real init on
device.
"""

from __future__ import annotations

import os
from typing import Any, Dict

import jax
import numpy as np


_CKPTR = None


class CheckpointGeometryError(RuntimeError):
    """A checkpoint restore hit a geometry/pytree mismatch: the on-disk
    state and the restore target disagree on leaves, shapes or dtypes —
    e.g. restoring a TP=4 save into a TP=2 step, or a checkpoint from a
    differently-shaped model. Carries the per-leaf diff (`mismatches`)
    instead of a raw Orbax traceback, so the fix (rebuild the step with
    the save-time geometry) is visible from the message alone."""

    def __init__(self, message: str, mismatches=None) -> None:
        super().__init__(message)
        self.mismatches = list(mismatches or [])


def _checkpointer():
    """One cached AsyncCheckpointer per process: constructing one per
    call leaks its background thread/barrier resources over long runs."""
    global _CKPTR
    if _CKPTR is None:
        import orbax.checkpoint as ocp
        _CKPTR = ocp.StandardCheckpointer()
    return _CKPTR


_HOST_CKPTR = None


def _host_checkpointer():
    """Cached PyTreeCheckpointer for host-side (numpy) restores — the
    ZeRO reshard path reads the saved geometry into host RAM instead of
    materializing it replicated on every device (see
    _vel_reshard_restore); format-compatible with what
    StandardCheckpointer saved."""
    global _HOST_CKPTR
    if _HOST_CKPTR is None:
        import orbax.checkpoint as ocp
        _HOST_CKPTR = ocp.PyTreeCheckpointer()
    return _HOST_CKPTR


from veles_tpu.prng import key_impl_name as _key_impl_name  # noqa: E402


def _unwrap_key(state: Dict[str, Any]) -> Dict[str, Any]:
    """Typed PRNG key arrays are an extended dtype Orbax cannot
    serialize; carry the raw uint32 key data instead."""
    out = dict(state)
    if "key" in out:
        out["key"] = jax.random.key_data(out["key"])
    return out


def save_state(state: Dict[str, Any], directory: str) -> str:
    """Write the state pytree (sharded jax arrays) to `directory`/state.
    Every process participates (multi-host safe); returns the path. The
    key's PRNG impl name rides in a sidecar so a restore under a
    different jax_default_prng_impl re-wraps with the SAVED impl (key
    geometry differs between impls: threefry (2,) vs rbg (4,))."""
    path = os.path.join(os.path.abspath(directory), "state")
    ckptr = _checkpointer()
    ckptr.save(path, _unwrap_key(state), force=True)
    ckptr.wait_until_finished()
    if "key" in state and jax.process_index() == 0:
        with open(os.path.join(os.path.abspath(directory),
                               "key_impl.txt"), "w") as f:
            f.write(_key_impl_name(state["key"]))
    return path


def _abstract_state(step, key_impl: str) -> Dict[str, Any]:
    """ShapeDtypeStructs of the step's state (key carried as raw uint32
    data), built from the units' HOST-side shapes: no device allocation,
    no PRNG draw — a restore target for states too big to double-buffer.
    A ZeRO-sharded step (step.zero_active) carries flat (padded,)
    optimizer-state vectors per its update-sharding plan instead of
    param-shaped leaves."""
    import jax.numpy as jnp

    from veles_tpu.ops import optim
    params = tuple(
        {k: jax.ShapeDtypeStruct(a.shape, a.mem.dtype)
         for k, a in u.param_arrays().items()}
        for u in step.forwards)
    cfgs = getattr(step, "cfgs", None) or [None] * len(params)
    plans = (step.zero_plans() if getattr(step, "zero_active", False)
             else (None,) * len(params))

    def vel_leaves(p, plan):
        if plan is None:
            return p
        return {k: jax.ShapeDtypeStruct((plan[k].padded,), p[k].dtype)
                for k in p}

    vel = tuple(
        {"m": vel_leaves(p, pl), "v": vel_leaves(p, pl),
         "t": jax.ShapeDtypeStruct((), jnp.int32)}
        if isinstance(c, optim.AdamConfig) else vel_leaves(p, pl)
        for p, c, pl in zip(params, cfgs, plans))
    key_shape = jax.eval_shape(
        lambda: jax.random.key_data(jax.random.key(0, impl=key_impl)))
    out = {"params": params, "vel": vel,
           "key": jax.ShapeDtypeStruct(key_shape.shape, key_shape.dtype),
           "lr_scale": jax.ShapeDtypeStruct((), jnp.float32)}
    if getattr(step, "has_aux", False):
        # step state no gradient touches (an expert layer's selection
        # bias and counters), one dict per forward unit
        out["aux"] = tuple(
            {k: jax.ShapeDtypeStruct(a.shape, a.mem.dtype)
             for k, a in u.aux_arrays().items()}
            if hasattr(u, "aux_arrays") else {} for u in step.forwards)
    if getattr(step, "ef_active", lambda: False)():
        # stateful (int8+EF) grad_reduce: the error-feedback residual
        # slot rides the checkpoint so a same-geometry resume carries
        # the compensation state; a geometry change DROPS it (see
        # _vel_reshard_restore — never mis-sharded)
        from veles_tpu.parallel.mesh import DATA_AXIS
        n = step.mesh.shape[DATA_AXIS]
        out["ef"] = tuple(
            {k: jax.ShapeDtypeStruct((n * rl,), jnp.float32)
             for k, rl in lens.items()}
            for lens in step.ef_lens())
    return out


def restore_state(step, directory: str) -> Dict[str, Any]:
    """Restore a state pytree saved by `save_state` into the shardings
    of `step` (a FusedTrainStep-compatible object). The abstract target
    is built from host-side shapes + the step's own sharding plan, so
    nothing is allocated on device before Orbax streams the shards in,
    and the global PRNG stream is untouched (reproducible resume). The
    key re-wraps with the impl recorded at save time, independent of the
    process's jax_default_prng_impl."""
    directory = os.path.abspath(directory)
    path = os.path.join(directory, "state")
    impl_path = os.path.join(directory, "key_impl.txt")
    if os.path.exists(impl_path):
        with open(impl_path) as f:
            key_impl = f.read().strip()
    else:   # pre-sidecar save: assume the jax default at save time
        key_impl = "threefry2x32"
    template = _abstract_state(step, key_impl)
    shardings = _target_shardings(step, template)
    target = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        template, shardings)
    ckptr = _checkpointer()
    # geometry check BEFORE touching device memory: orbax's own restore
    # does not reliably reject a mismatched target (observed: a narrower
    # model restores garbage silently), and when it does object the
    # traceback buries which leaf disagreed
    err = _geometry_error(ckptr, path, target, None)
    if err is not None:
        # one mismatch class is LEGAL and resharded in place: the
        # optimizer-state (vel) geometry moving between ZeRO plans —
        # a save under data-axis N restored into a step with a
        # different N, or a zero-sharded save into a replicated step
        # (and vice versa). Everything else still raises.
        state = _vel_reshard_restore(ckptr, path, step, template,
                                     key_impl)
        if state is not None:
            return state
        raise err
    try:
        state = ckptr.restore(path, target)
    except Exception as e:  # noqa: BLE001 — diagnose, then re-raise typed
        raise (_geometry_error(ckptr, path, target, e) or e) from e
    state["key"] = jax.random.wrap_key_data(state["key"], impl=key_impl)
    return state


def _keystr(path) -> str:
    """Orbax-style key string for one pytree keypath — the ONE
    stringification `_leaf_index` builds its index with and
    `_vel_reshard_restore` looks leaves up by (they must stay
    byte-identical or legal reshards crash on KeyError)."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _leaf_index(tree) -> Dict[str, Any]:
    """Flatten a pytree to {keypath: leaf} with orbax-style key strings
    (shared diff basis for the saved metadata and the restore target)."""
    import jax.tree_util as jtu
    return {_keystr(path): leaf
            for path, leaf in jtu.tree_flatten_with_path(tree)[0]}


def _saved_index(ckptr, path: str) -> Dict[str, Any]:
    """{keypath: ArrayMetadata} of the tree saved at `path`. The
    installed Orbax (0.11) answers `metadata()` with a StepMetadata
    whose `item_metadata.tree` is the saved pytree (tuples come back as
    lists, which `_keystr` spells the same way)."""
    return _leaf_index(ckptr.metadata(path).item_metadata.tree)


def _geometry_error(ckptr, path: str, target, cause):
    """Diff the SAVED tree metadata against the restore target; returns
    a CheckpointGeometryError naming every leaf that exists on only one
    side or disagrees on shape/dtype — or None/`cause` when the trees
    agree (the failure, if any, is something else) or the metadata is
    unreadable (not a checkpoint at all: not a geometry problem)."""
    try:
        saved = _saved_index(ckptr, path)
    except Exception:  # noqa: BLE001 — no metadata: not a geometry issue
        return cause
    want = _leaf_index(target)
    mismatches = []
    for k in sorted(set(saved) | set(want)):
        if k not in want:
            mismatches.append(f"{k}: in checkpoint only "
                              f"(saved {_describe(saved[k])})")
        elif k not in saved:
            mismatches.append(f"{k}: in restore target only "
                              f"(want {_describe(want[k])})")
        elif _describe(saved[k]) != _describe(want[k]):
            mismatches.append(f"{k}: saved {_describe(saved[k])} != "
                              f"target {_describe(want[k])}")
    if not mismatches:
        return cause    # trees agree: the failure is something else
    head = mismatches[:12]
    more = len(mismatches) - len(head)
    detail = "\n  ".join(head) + (f"\n  … and {more} more" if more else "")
    return CheckpointGeometryError(
        f"checkpoint at {path} does not match the step's state geometry "
        f"({len(mismatches)} mismatched leaves) — rebuild the step with "
        f"the save-time layer/mesh configuration or point at the right "
        f"checkpoint:\n  {detail}", mismatches)


def _describe(leaf) -> str:
    shape = tuple(getattr(leaf, "shape", ()) or ())
    dtype = getattr(leaf, "dtype", None)
    return f"{shape}/{dtype}"


# -- ZeRO optimizer-state resharding (restore across a data-axis change) ------

def _orig_vel_shapes(step) -> Dict[str, tuple]:
    """{vel keypath: the leaf's ORIGINAL (unflattened) shape} for every
    velocity/moment leaf — the invariant both the replicated and any
    ZeRO-flattened geometry encode (the Adam step counter `t` is
    excluded: its geometry never changes)."""
    from veles_tpu.ops import optim
    cfgs = getattr(step, "cfgs", None) or [None] * len(step.forwards)
    out: Dict[str, tuple] = {}
    for i, (u, c) in enumerate(zip(step.forwards, cfgs)):
        for k, a in u.param_arrays().items():
            shape = tuple(a.shape)
            if isinstance(c, optim.AdamConfig):
                out[f"vel/{i}/m/{k}"] = shape
                out[f"vel/{i}/v/{k}"] = shape
            else:
                out[f"vel/{i}/{k}"] = shape
    return out


def _vel_reshard_restore(ckptr, path: str, step, template, key_impl: str):
    """Geometry-mismatch fallback for `restore_state`: when the ONLY
    disagreement between the checkpoint and the step's target is the
    velocity/moment leaf geometry, and each disagreeing pair is two
    legal encodings of the same leaf (its original shape, or a flat
    ZeRO (padded,) vector with padded >= size), restore into the SAVED
    geometry and reshape every such leaf into the step's plan: undo the
    old padding, re-pad for the new data-axis size, land each leaf
    under the step's own shardings. Returns the resharded state, or
    None when the mismatch is a different class (caller raises the
    original CheckpointGeometryError)."""
    import numpy as np
    try:
        saved = _saved_index(ckptr, path)
    except Exception:  # noqa: BLE001 — unreadable: not this class
        return None
    want = _leaf_index(template)
    # the error-feedback slot ("ef/...", stateful grad_reduce variants)
    # is a compensation accumulator, not trajectory state: across ANY
    # geometry/variant mismatch it is DROPPED (target leaves reset to
    # zeros, saved leaves ignored) rather than resharded — a residual
    # sliced under the wrong (hosts x local) factorization would
    # compensate the wrong elements forever. It never gates the reshard.
    saved_ef = {k for k in saved if k.startswith("ef/")}
    want_ef = {k for k in want if k.startswith("ef/")}
    if set(saved) - saved_ef != set(want) - want_ef:
        return None
    orig = _orig_vel_shapes(step)

    def legal(shape, base) -> bool:
        size = int(np.prod(base)) if base else 1
        return tuple(shape) == base or (
            len(shape) == 1 and int(shape[0]) >= size)

    differing = []
    for k in set(saved) - saved_ef:
        if _describe(saved[k]) == _describe(want[k]):
            continue
        base = orig.get(k)
        s_dt = getattr(saved[k], "dtype", None)
        w_dt = getattr(want[k], "dtype", None)
        if base is None or str(s_dt) != str(w_dt) \
                or not legal(tuple(saved[k].shape or ()), base) \
                or not legal(tuple(want[k].shape or ()), base):
            return None
        differing.append(k)
    ef_differs = saved_ef != want_ef or any(
        _describe(saved[k]) != _describe(want[k]) for k in saved_ef)
    if not differing and not ef_differs:
        return None     # trees agree: not a geometry problem at all

    # restore into the SAVED geometry as HOST numpy (PyTree restore,
    # restore_type=np.ndarray): the reshaping below runs on host arrays
    # and each leaf reaches the devices exactly once, already under the
    # step's own shardings. A replicated device restore here would
    # materialize every FULL moment vector on EVERY device first —
    # an HBM spike of N x the sharded footprint on exactly the models
    # ZeRO-sharding exists to fit (zero excludes multi-host, so the
    # whole tree is host-addressable by construction).
    import jax.tree_util as jtu
    import orbax.checkpoint as ocp
    base_template = {k: v for k, v in template.items() if k != "ef"}
    saved_target = jtu.tree_map_with_path(
        lambda p_, leaf: jax.ShapeDtypeStruct(
            tuple(saved[_keystr(p_)].shape or ()),
            saved[_keystr(p_)].dtype),
        base_template)
    if saved_ef:
        # the restore item must mirror the ON-DISK structure: rebuild
        # the saved ef subtree (tuple-of-dicts, like vel) from its leaf
        # keypaths; the restored residuals are dropped below
        layers: Dict[int, Dict[str, Any]] = {}
        for k in saved_ef:
            _, idx, leafname = k.split("/", 2)
            layers.setdefault(int(idx), {})[leafname] = \
                jax.ShapeDtypeStruct(tuple(saved[k].shape or ()),
                                     saved[k].dtype)
        saved_target["ef"] = tuple(
            layers.get(i, {}) for i in range(len(step.forwards)))
    restore_args = jtu.tree_map(
        lambda _: ocp.RestoreArgs(restore_type=np.ndarray), saved_target)
    state = _host_checkpointer().restore(path, item=saved_target,
                                         restore_args=restore_args)
    state.pop("ef", None)   # residuals from another geometry: dropped

    shardings = _target_shardings(step, template)
    base_shardings = {k: v for k, v in shardings.items() if k != "ef"}

    def convert(path_, leaf, tmpl, sh):
        k = _keystr(path_)
        tshape = tuple(tmpl.shape or ())
        if tuple(np.shape(leaf)) != tshape:
            base = orig[k]
            size = int(np.prod(base)) if base else 1
            flat = np.asarray(leaf).reshape(-1)[:size]
            if len(tshape) == 1:        # target is a ZeRO flat vector
                out = np.zeros(tshape[0], flat.dtype)
                out[:size] = flat
            else:                       # target is the original shape
                out = flat.reshape(tshape)
            leaf = out
        return jax.device_put(leaf, sh)

    state = jtu.tree_map_with_path(convert, state, base_template,
                                   base_shardings)
    if "ef" in template:
        # the step wants an EF slot: fresh zeros under its OWN plan —
        # dropping the residual costs one uncompensated step, never a
        # mis-sharded compensation
        state["ef"] = jtu.tree_map(
            lambda t, sh: jax.device_put(
                np.zeros(t.shape, t.dtype), sh),
            template["ef"], shardings["ef"])
    state["key"] = jax.random.wrap_key_data(state["key"], impl=key_impl)
    return state


def _target_shardings(step, template):
    """Per-leaf restore shardings from the step's OWN plan: gspmd states
    use the named-sharding tree (megatron col/row specs), shard_map
    modes (dp/seq) use the spec tree — replicated leaves span the WHOLE
    mesh (a single-device leaf would collide with the mesh computation)
    and EP expert tensors land pre-partitioned over the data axis."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = getattr(step, "mesh", None)
    mode = getattr(step, "mode", None)
    if mesh is None:
        from jax.sharding import SingleDeviceSharding
        sh = SingleDeviceSharding(jax.devices()[0])   # local-mode step
        return jax.tree_util.tree_map(lambda a: sh, template)
    if mode == "gspmd":
        return step._state_shardings()
    if mode == "dp":
        specs = step._smap_state_spec()
    elif mode == "seq":
        # seq mode may carry shard_map TP (model-axis param sharding):
        # restore into those specs so TP-sharded params stream in
        # partitioned instead of materializing whole per device
        specs = step._seq_state_spec()
    else:
        specs = jax.tree_util.tree_map(lambda _: P(), template)
    return jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P))
