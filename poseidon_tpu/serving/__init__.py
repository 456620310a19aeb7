"""Serving tier: stand a trained snapshot up behind a socket.

The training side of the repo ends at ``Engine.snapshot_now()``; this package
is the other half of the TensorFlow-style split — a first-class serving
subsystem next to training:

- :mod:`executor`  — pure-JAX inference with a shape-bucketed AOT compile
  cache (every batch bucket precompiled at startup, no trace-on-first-request)
- :mod:`batcher`   — dynamic micro-batching with bounded admission and
  explicit shed responses (backpressure, never a hang)
- :mod:`server`    — threaded socket front-end on the proto/wire.py framing,
  with per-request deadlines and a stats introspection op
- :mod:`reloader`  — checkpoint hot-reload: watch the snapshot directory and
  atomically swap serving params without dropping in-flight requests
  (``FleetReloader`` generalizes it to roll a whole fleet, one drain at a
  time)
- :mod:`fleet`     — the replica manager: N executors behind one front door
  with least-loaded routing, WARMING/SERVING/DRAINING/DEAD health states,
  failover on replica death, and rolling hot-reload
- :mod:`client`    — small blocking client (retry_with_backoff) + load
  generator (closed-loop and open-loop offered-load modes) shared by
  tests and `bench_serve`

PEP-562 lazy exports keep ``import poseidon_tpu.serving`` jax-free until an
executor is actually built (client/server/batcher never import jax).
"""

_EXPORTS = {
    "BucketedExecutor": ".executor",
    "DEFAULT_BUCKETS": ".executor",
    "DynamicBatcher": ".batcher",
    "ShedError": ".batcher",
    "DeadlineError": ".batcher",
    "InferenceServer": ".server",
    "CheckpointReloader": ".reloader",
    "FleetReloader": ".reloader",
    "ReplicaManager": ".fleet",
    "Replica": ".fleet",
    "ServingClient": ".client",
    "ServingError": ".client",
    "run_load": ".client",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        import importlib
        mod = importlib.import_module(_EXPORTS[name], __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
