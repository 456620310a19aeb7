"""Pure-JAX inference executor with a shape-bucketed AOT compile cache.

The low-latency TPU inference discipline (the AOT/static-shape lesson from
the Julia-to-TPU full-compilation work): never trace on a request. Every
admissible batch shape is known up front — the bucket ladder — so all
executables are built at startup with ``jit(...).lower(avals).compile()``
and a request only ever pays (pad -> dispatch -> slice).

Bucket policy: a request of n rows runs on the smallest bucket >= n, padded
with zeros; outputs are sliced back to n rows. Row-independence of the
forward pass (conv/fc/softmax act per row in eval mode) makes the padding
rows inert, so bucketed results are bit-identical to a direct ``jit``
forward at the request's own shape — pinned by
tests/test_serving.py::test_bucketed_executor_matches_direct_jit.

Hot-reload contract: ``swap_params`` validates the incoming pytree against
the serving tree (same structure, shapes, dtypes — same net architecture)
and then swaps the reference atomically. In-flight requests that already
grabbed the old reference finish on the old weights; the next dispatch sees
the new ones. The compiled executables are keyed only on SHAPES, so a swap
never recompiles anything.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# The batch bucket ladder an executor warms when its caller names none.
DEFAULT_BUCKETS = (1, 4, 16, 64)


def parse_buckets(spec: str) -> Tuple[int, ...]:
    """'1,4,16,64' -> (1, 4, 16, 64), validated ascending positives; an
    empty spec (an unset --buckets) is DEFAULT_BUCKETS."""
    if not spec:
        return DEFAULT_BUCKETS
    try:
        buckets = tuple(sorted({int(tok) for tok in spec.split(",") if tok}))
    except ValueError as e:
        raise ValueError(f"bad bucket spec {spec!r}: {e}") from None
    if not buckets or buckets[0] < 1:
        raise ValueError(f"bad bucket spec {spec!r}: need positive sizes")
    return buckets


def merge_snapshot_params(base_params: Dict, snap_params: Dict) -> Dict:
    """Overlay a snapshot's {layer: {param: array}} onto the serving tree.

    The serving net may be a deploy-style subset of the train net (no loss
    layers), so extra snapshot layers are ignored; every serving layer must
    be present with matching shapes, or the swap is refused — a half-matched
    snapshot must never serve."""
    merged: Dict = {}
    for lname, lparams in base_params.items():
        if lname not in snap_params:
            raise ValueError(f"snapshot is missing param layer {lname!r}")
        merged[lname] = {}
        for pname, cur in lparams.items():
            if pname not in snap_params[lname]:
                raise ValueError(
                    f"snapshot is missing param {lname!r}/{pname!r}")
            arr = np.asarray(snap_params[lname][pname])
            if tuple(arr.shape) != tuple(np.shape(cur)):
                raise ValueError(
                    f"snapshot param {lname!r}/{pname!r} shape "
                    f"{arr.shape} != serving shape {tuple(np.shape(cur))}")
            merged[lname][pname] = arr
    return merged


def load_serving_params(net, base_params: Dict, path: str) -> Dict:
    """Read weights for serving from either snapshot artifact:
    ``.caffemodel`` (weights only) or ``.solverstate.npz`` (params tree)."""
    if path.endswith(".caffemodel"):
        from ..runtime.checkpoint import load_caffemodel
        return load_caffemodel(path, net, base_params)
    from ..runtime.checkpoint import restore
    snap_params, _ = restore(path)
    return merge_snapshot_params(base_params, snap_params)


class BucketedExecutor:
    """Shape-bucketed AOT inference over a TEST-phase :class:`core.net.Net`.

    ``net`` must expose its inputs as explicit blobs (deploy-style
    ``input:``/``input_dim:`` nets, or ``source_shapes`` for programmatic
    nets); the leading dim of every input is the batch axis and is replaced
    by the bucket size. Outputs whose leading dim equals the bucket are
    sliced back to the request's rows; any other output (scalar metrics in
    nets that kept a loss head) passes through untouched."""

    def __init__(self, net, params, buckets: Sequence[int] = DEFAULT_BUCKETS,
                 warm: bool = True, device=None):
        import jax
        import jax.numpy as jnp

        self.net = net
        self.buckets: Tuple[int, ...] = tuple(sorted(set(int(b)
                                                         for b in buckets)))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"need at least one positive bucket, "
                             f"got {buckets!r}")
        self.input_names: List[str] = list(net.input_names)
        if not self.input_names:
            raise ValueError("net declares no inputs to serve")
        # device pinning (the fleet's placement half): params live committed
        # on the pinned device and every bucket compiles FOR it, so N
        # replicas on N local devices never contend for one accelerator
        self.device = device
        if device is not None:
            self._params = jax.device_put(
                jax.tree_util.tree_map(jnp.asarray, params), device)
        else:
            self._params = jax.tree_util.tree_map(jnp.asarray, params)
        self._swap_lock = threading.Lock()
        self.params_version = 0            # bumped by every swap_params
        self.calls: Dict[int, int] = {b: 0 for b in self.buckets}
        self.rows_served = 0
        self.rows_padded = 0
        # per-bucket fill accounting: which rungs of the ladder run full
        # and which mostly dispatch padding (the capacity-planning signal
        # the `stats` op exports as executor_bucket_fill)
        self.rows_by_bucket: Dict[int, int] = {b: 0 for b in self.buckets}
        self.padded_by_bucket: Dict[int, int] = {b: 0 for b in self.buckets}

        def fwd(p, inputs):
            return net.apply(p, inputs, train=False).outputs

        self._fwd = fwd
        self._compiled: Dict[int, object] = {}
        if warm:
            self.warm()

    # ---- compile cache -------------------------------------------------- #
    def _input_aval(self, name: str, bucket: int):
        import jax
        import jax.numpy as jnp
        shape = self.net.blob_shapes[name]
        dtype = jnp.float32 if len(shape) > 1 else jnp.int32
        return jax.ShapeDtypeStruct((bucket,) + tuple(shape[1:]), dtype)

    def warm(self) -> None:
        """AOT-compile every bucket so no request ever pays trace cost.
        With a pinned device the lowering runs under ``default_device``,
        baking the executable's placement (uncommitted request arrays then
        land there at dispatch)."""
        import contextlib

        import jax

        params_avals = jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), self._params)
        ctx = (jax.default_device(self.device) if self.device is not None
               else contextlib.nullcontext())
        with ctx:
            for b in self.buckets:
                if b in self._compiled:
                    continue
                inputs = {n: self._input_aval(n, b)
                          for n in self.input_names}
                self._compiled[b] = (
                    jax.jit(self._fwd).lower(params_avals,
                                             inputs).compile())

    def bucket_for(self, rows: int) -> int:
        if rows < 1:
            raise ValueError("empty request")
        for b in self.buckets:
            if rows <= b:
                return b
        raise ValueError(f"request of {rows} rows exceeds the largest "
                         f"bucket {self.buckets[-1]}")

    @property
    def max_batch(self) -> int:
        return self.buckets[-1]

    def bucket_fill(self) -> Dict[int, Optional[float]]:
        """{bucket: real-rows / dispatched-rows} per ladder rung (None
        until a rung has served). 1.0 = every dispatched row was a real
        request row; low fill on a big rung means its compile slot mostly
        pads — a ladder worth re-cutting."""
        out: Dict[int, Optional[float]] = {}
        for b in self.buckets:
            total = self.rows_by_bucket[b] + self.padded_by_bucket[b]
            out[b] = round(self.rows_by_bucket[b] / total, 4) if total \
                else None
        return out

    # ---- serving -------------------------------------------------------- #
    def validate_request(self, inputs: Dict[str, np.ndarray]) -> int:
        """Admission-time validation (the batcher calls this BEFORE
        queueing): every input present, consistent row counts, row shapes
        matching the model. Rejecting here keeps one malformed request
        from poisoning the micro-batch it would have been joined into.
        Returns the request's row count."""
        missing = [n for n in self.input_names if n not in inputs]
        if missing:
            raise ValueError(f"request missing inputs {missing}")
        rows = int(np.shape(inputs[self.input_names[0]])[0])
        if rows < 1:
            raise ValueError("empty request")
        for name in self.input_names:
            arr = np.asarray(inputs[name])
            if int(arr.shape[0]) != rows:
                raise ValueError(f"input {name!r} has {arr.shape[0]} rows, "
                                 f"expected {rows}")
            want = self.net.blob_shapes[name]
            if tuple(arr.shape[1:]) != tuple(want[1:]):
                raise ValueError(
                    f"input {name!r} row shape {tuple(arr.shape[1:])} != "
                    f"model shape {tuple(want[1:])}")
        return rows

    def infer(self, inputs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Pad up to the nearest bucket, dispatch the precompiled
        executable, slice the padding back off. Thread-safe: the params
        reference is read once, so a concurrent hot-reload never tears a
        dispatch."""
        rows = self.validate_request(inputs)
        bucket = self.bucket_for(rows)
        padded = {}
        for name in self.input_names:
            arr = np.asarray(inputs[name])
            want = self.net.blob_shapes[name]
            dtype = np.float32 if len(want) > 1 else np.int32
            arr = arr.astype(dtype, copy=False)
            if rows < bucket:
                pad = np.zeros((bucket - rows,) + arr.shape[1:], dtype)
                arr = np.concatenate([arr, pad], axis=0)
            padded[name] = arr
        params = self._params      # one atomic read: swap-safe
        out = self._compiled[bucket](params, padded)
        self.calls[bucket] += 1
        self.rows_served += rows
        self.rows_padded += bucket - rows
        self.rows_by_bucket[bucket] += rows
        self.padded_by_bucket[bucket] += bucket - rows
        return {k: (np.asarray(v)[:rows]
                    if np.ndim(v) >= 1 and np.shape(v)[0] == bucket
                    else np.asarray(v))
                for k, v in out.items()}

    # ---- hot reload ----------------------------------------------------- #
    def swap_params(self, new_params: Dict) -> int:
        """Atomically replace the serving params. Validates structure,
        shapes, and dtypes against the current tree (the executables are
        shape-keyed; a mismatched tree would poison every bucket). Returns
        the new params version."""
        import jax
        import jax.numpy as jnp

        new_params = jax.tree_util.tree_map(jnp.asarray, new_params)
        if self.device is not None:
            # the executables are pinned: a swap must land the new tree on
            # THIS replica's device, not wherever the snapshot loaded
            new_params = jax.device_put(new_params, self.device)
        cur_leaves, cur_tree = jax.tree_util.tree_flatten(self._params)
        new_leaves, new_tree = jax.tree_util.tree_flatten(new_params)
        if cur_tree != new_tree:
            raise ValueError("params tree structure mismatch: the snapshot "
                             "was taken from a different net")
        for c, n in zip(cur_leaves, new_leaves):
            if c.shape != n.shape or c.dtype != n.dtype:
                raise ValueError(
                    f"params leaf mismatch: {n.shape}/{n.dtype} vs serving "
                    f"{c.shape}/{c.dtype}")
        with self._swap_lock:
            self._params = new_params
            self.params_version += 1
            return self.params_version

    # ---- construction from artifacts ------------------------------------ #
    @classmethod
    def from_files(cls, model_path: str, weights_path: Optional[str] = None,
                   buckets: Sequence[int] = DEFAULT_BUCKETS,
                   warm: bool = True, device=None) -> "BucketedExecutor":
        """Build from a deploy prototxt + optional weights (.caffemodel or
        .solverstate.npz). Without weights the net serves its filler
        initialization (smoke mode)."""
        import jax
        from ..core.net import Net
        from ..proto.messages import load_net

        net = Net(load_net(model_path), "TEST")
        params = net.init(jax.random.PRNGKey(0))
        if weights_path:
            params = load_serving_params(net, params, weights_path)
        return cls(net, params, buckets=buckets, warm=warm, device=device)
