"""Snapshots: solver-state checkpoints + .caffemodel weight exchange.

The reference writes two artifacts (solver.cpp:632-696): the model as a
binary NetParameter (``.caffemodel``, written by rank 0) and per-worker
``.solverstate`` files with the iteration and momentum history. Here:

- ``snapshot()`` writes ``<prefix>_iter_<N>.caffemodel`` (wire-compatible with
  Caffe) and ``<prefix>_iter_<N>.solverstate.npz`` (params + history + iter +
  comm residuals), sharding-agnostic since params are replicated.
- ``restore()`` rebuilds (params, TrainState) from the .npz;
  ``load_caffemodel()`` imports weights alone (CopyTrainedLayersFrom).

**Snapshots are canonical per-leaf** — the arena (core/arena.py) buckets
gradients inside the compiled train step and nothing else, so every
(params, state) this module sees is the per-leaf tree regardless of
``--param_arena``. A snapshot written with bucketed collectives reloads
under ``--param_arena=false`` bit-identically and the other way round, and
nothing here depends on the arena's offset table or bucket size (tested:
test_runtime.test_arena_snapshot_portability).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.net import Net
from ..parallel.trainer import (SSPState, TrainState, init_comm_error,
                                init_ssp_state, reconcile_comm_error)
from ..proto.wire import decode_caffemodel, encode_caffemodel
from ..solvers.updates import SolverState
from .ckpt_files import latest_snapshot, sweep_stale_tmp  # noqa: F401


# Layer names may contain '/' (GoogLeNet's "inception_3a/1x1"), so tree keys
# are joined with the ASCII unit separator, which cannot appear in prototxt
# identifiers.
_SEP = "\x1f"


def _flatten(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + _SEP))
        else:
            out[key] = np.asarray(v)
    return out


def _unflatten(flat: Dict[str, np.ndarray]):
    tree: Dict = {}
    for key, v in flat.items():
        parts = key.split(_SEP)
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(v)
    return tree


def _gather(tree):
    """Per-device SSP leaves (and TOPK residuals) are sharded over the data
    axis; under multi-process they span non-addressable devices, so gather
    them to every host first — each rank then writes identical bytes."""
    if jax.process_count() == 1 or not jax.tree_util.tree_leaves(tree):
        return tree
    from jax.experimental import multihost_utils
    return jax.tree_util.tree_map(
        lambda x: multihost_utils.process_allgather(x, tiled=True)
        if isinstance(x, jax.Array) and not x.is_fully_addressable else x,
        tree)


def snapshot_paths(prefix: str, state) -> Tuple[str, str]:
    """(model_path, state_path) the snapshot protocol will produce for this
    state — shared by the sync writer and the async writer's caller-visible
    return value."""
    is_ssp = isinstance(state, SSPState)
    it = int(state.it if is_ssp else state.solver.it)
    return (f"{prefix}_iter_{it}.caffemodel",
            f"{prefix}_iter_{it}.solverstate.npz")


def host_state_copy(params, state):
    """Blocking host copy of (params, state) — THE sync point the async
    snapshot writer serializes from: sharded leaves are gathered first
    (np.asarray on a non-addressable array would fail), every leaf lands
    as numpy, and the state's TrainState/SSPState type is preserved (the
    pytree re-registers the NamedTuple)."""
    to_np = lambda tree: jax.tree_util.tree_map(np.asarray, tree)  # noqa: E731
    return to_np(params), to_np(_gather(state))


def snapshot(prefix: str, net: Net, params, state) -> Tuple[str, str]:
    """Write both artifacts atomically (tmp + rename): with replicated state
    every rank writes identical bytes, so even concurrent snapshots to a
    shared filesystem are safe — the last rename wins with valid content.

    ``state`` is either a TrainState (sync/dense training) or an SSPState
    (staleness > 0); the .solverstate records which, so restore() rebuilds the
    right carry — the analog of the reference's per-thread .solverstate files
    carrying divergent worker histories (solver.cpp:654-667)."""
    is_ssp = isinstance(state, SSPState)
    it = int(state.it if is_ssp else state.solver.it)
    os.makedirs(os.path.dirname(prefix) or ".", exist_ok=True)
    model_path, state_path = snapshot_paths(prefix, state)
    pid = os.getpid()

    # .caffemodel always holds the globally-agreed view: anchor under SSP.
    model_params = state.anchor_params if is_ssp else params
    tmp = f"{model_path}.tmp.{pid}"
    with open(tmp, "wb") as f:
        f.write(encode_caffemodel(net.name or "net",
                                  net.export_weights(model_params)))
    os.replace(tmp, model_path)

    gather = _gather
    arrays = {"iter": np.asarray(it)}
    if is_ssp:
        arrays["kind"] = np.asarray("ssp")
        arrays.update({f"params/{k}": v
                       for k, v in _flatten(state.anchor_params).items()})
        arrays.update({f"local_params/{k}": v
                       for k, v in _flatten(gather(state.local_params)).items()})
        arrays.update({f"local_history/{k}": v
                       for k, v in
                       _flatten(gather(state.local_history)).items()})
        arrays.update({f"adarev_server/{k}": v
                       for k, v in _flatten(state.adarev_server).items()})
        arrays.update({f"adarev_gsum/{k}": v
                       for k, v in
                       _flatten(gather(state.adarev_gsum)).items()})
    else:
        arrays["kind"] = np.asarray("dense")
        arrays.update({f"params/{k}": v for k, v in _flatten(params).items()})
        arrays.update({f"history/{k}": v
                       for k, v in _flatten(state.solver.history).items()})
    arrays.update({f"comm_error/{k}": v
                   for k, v in _flatten(gather(state.comm_error)).items()})
    tmp = f"{state_path}.tmp.{pid}"
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, state_path)
    return model_path, state_path


def restore(state_path: str) -> Tuple[Dict, object]:
    """Rebuild (params, state) from a .solverstate.npz. The state is a
    TrainState or SSPState depending on how the snapshot was taken; callers
    running in the other mode can convert via ``coerce_state``."""
    z = np.load(state_path)
    groups: Dict[str, Dict[str, np.ndarray]] = {}
    it = 0
    kind = "dense"
    for key in z.files:
        if key == "iter":
            it = int(z[key])
        elif key == "kind":
            kind = str(z[key])
        else:
            group, rest = key.split("/", 1)
            groups.setdefault(group, {})[rest] = z[key]
    params = _unflatten(groups.get("params", {}))
    it_arr = jnp.asarray(it, jnp.int32)
    err = _unflatten(groups.get("comm_error", {}))
    if kind == "ssp":
        state = SSPState(
            local_params=_unflatten(groups.get("local_params", {})),
            local_history=_unflatten(groups.get("local_history", {})),
            anchor_params=params, it=it_arr, comm_error=err,
            adarev_server=_unflatten(groups.get("adarev_server", {})),
            adarev_gsum=_unflatten(groups.get("adarev_gsum", {})))
    else:
        state = TrainState(
            solver=SolverState(it=it_arr,
                               history=_unflatten(groups.get("history", {}))),
            comm_error=err)
    return params, state


def coerce_state(params, state, *, staleness: int, n_dev: int, comm=None):
    """Adapt a restored state to the engine's current mode.

    dense -> SSP: broadcast params to fresh per-device copies (histories
    restart, like the reference's thread-0 fallback in Restore).
    SSP -> dense: collapse to the anchor view with fresh history.
    Matching modes pass through (with an n_dev check for SSP), reconciling
    comm_error against the engine's *current* comm config — layers that
    changed strategy get fresh/dropped residuals. On a mode CHANGE the
    residuals restart at zero instead: dense residuals hold per-step gradient
    mass while SSP residuals hold per-period parameter-delta mass — different
    units, so carrying them over would inject a wrongly-scaled correction at
    the first sync (histories restart on mode change for the same reason)."""
    from ..solvers.updates import init_state

    def fix_err(p, st):
        st = st._replace(comm_error=reconcile_comm_error(
            p, st.comm_error, comm, n_dev))
        if not isinstance(st, SSPState):
            return st
        # adarevision accumulators resume only into an identically-shaped
        # adarevision run; any config change restarts them (z/zmax at 1,
        # empty oplog) — mixing units across server logics would inject a
        # wrongly-scaled first sync, same reasoning as comm_error above
        from ..parallel.trainer import init_adarev_state
        server, gsum = init_adarev_state(p, comm, n_dev)
        same = jax.tree_util.tree_structure(server) == \
            jax.tree_util.tree_structure(st.adarev_server) and all(
                a.shape == b.shape for a, b in zip(
                    jax.tree_util.tree_leaves(server),
                    jax.tree_util.tree_leaves(st.adarev_server)))
        if same and server:
            gs_same = jax.tree_util.tree_structure(gsum) == \
                jax.tree_util.tree_structure(st.adarev_gsum) and all(
                    a.shape == b.shape for a, b in zip(
                        jax.tree_util.tree_leaves(gsum),
                        jax.tree_util.tree_leaves(st.adarev_gsum)))
            return st._replace(
                adarev_gsum=st.adarev_gsum if gs_same else gsum)
        return st._replace(adarev_server=server, adarev_gsum=gsum)

    want_ssp = staleness > 0
    is_ssp = isinstance(state, SSPState)
    if want_ssp and not is_ssp:
        fresh = init_ssp_state(params, n_dev, comm)  # zero residuals
        return params, fresh._replace(it=state.solver.it)
    if not want_ssp and is_ssp:
        anchor = state.anchor_params
        return anchor, TrainState(
            solver=init_state(anchor)._replace(it=state.it),
            comm_error=init_comm_error(anchor, comm, n_dev))
    if is_ssp:
        stored_dev = jax.tree_util.tree_leaves(state.local_params)[0].shape[0]
        if stored_dev != n_dev:
            fresh = init_ssp_state(state.anchor_params, n_dev, comm)
            return state.anchor_params, fresh._replace(it=state.it)
    return params, fix_err(params, state)


class AsyncSnapshotWriter:
    """Snapshot serialization off the training critical path.

    ``submit()`` takes the host copy synchronously (the ONLY sync point —
    ``host_state_copy`` blocks on the device and gathers sharded leaves,
    which must happen on the caller thread under multi-process), then
    hands serialization + the atomic tmp-rename protocol to a background
    thread running the unmodified ``snapshot()``. At most one write is in
    flight: a new ``submit`` first joins the previous one, so snapshot
    cadence can never outrun the disk into unbounded queued host copies.

    Failures are loud, never lost: a write error is re-raised by the next
    ``submit()``/``wait()``. A torn shutdown (process death mid-write)
    leaves at worst ``*.tmp.<pid>`` litter — the rename is what creates
    the real suffix, so a partial file can never shadow a completed
    artifact; ``sweep_stale_tmp`` collects the litter on the next
    auto-resume (tests/test_pipeline_overlap.py)."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._last: Optional[Tuple[str, str]] = None

    def submit(self, prefix: str, net: Net, params, state) -> Tuple[str, str]:
        """Queue one snapshot; returns the (model, state) paths the write
        will land at. Blocks only for the host copy (and any still-running
        previous write)."""
        self.wait()  # one in flight; re-raises a previous failure
        host_params, host_state = host_state_copy(params, state)
        paths = snapshot_paths(prefix, host_state)

        def _write():
            try:
                self._last = snapshot(prefix, net, host_params, host_state)
            except BaseException as e:  # noqa: BLE001 — surfaced on join
                self._error = e

        self._thread = threading.Thread(target=_write, name="ckpt_writer",
                                        daemon=True)
        self._thread.start()
        return paths

    def wait(self) -> Optional[Tuple[str, str]]:
        """Join the in-flight write (if any); re-raise its failure; return
        the last completed (model, state) paths.

        Failure surfacing contract (pinned by
        test_pipeline_overlap.test_async_snapshot_failure_aborts_at_next_
        sync_boundary): the training loop calls this at every snapshot
        boundary (submit's join) and at end-of-train, so a background
        write that died aborts the run AT THE NEXT SYNC BOUNDARY with the
        original exception — never a silent pass that leaves auto-resume
        pointing at a snapshot that does not exist."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            # name the failed artifact BEFORE re-raising: the exception
            # type is the writer's own (a disk error stays a disk error),
            # the context says which snapshot is missing because of it
            from .metrics import log
            log(f"async snapshot write FAILED "
                f"({type(err).__name__}: {err}); the snapshot it was "
                f"writing does not exist — aborting at this sync boundary")
            raise err
        return self._last

    def close(self) -> None:
        self.wait()


def load_caffemodel(path: str, net: Net, params):
    with open(path, "rb") as f:
        weights = decode_caffemodel(f.read())
    return net.load_weights(params, weights)


# latest_snapshot / sweep_stale_tmp live in ckpt_files (re-exported above):
# pure-filesystem discovery and tmp hygiene, kept jax-free for the socket
# tier.
