"""DWBP wall-clock A/B on the 8-device mesh: does distinctness buy time?

The reference's signature result is per-layer sync threads overlapping the
remaining backward (/root/reference/src/caffe/solver.cpp:419-449). Round 3
showed the rebuild's A/B was degenerate: XLA's all-reduce combiner merged
all per-layer taps into ONE collective identical to DENSE_FUSED — there
was no overlap to measure. Round 4
added chained taps (CommConfig.dwbp_bucket_mb) that force one DISTINCT
collective per bucket. THIS script is the wall-clock half of the proof:
time real train steps in four modes on the same mesh —

  fused     one stacked psum after the whole backward (no-overlap baseline)
  dense     plain taps (combiner merges them -> behaves like fused)
  bucketed  chained taps, ~4 MB buckets (distinct, ordered collectives)
  per_blob  chained taps, one collective per parameter blob

and report per-mode step time + speedup vs fused. An honest negative is a
valid result: on a backend with synchronous collectives (CPU) distinctness
cannot overlap and mostly adds launch overhead — the conclusion then is
that XLA's combiner is optimal for THAT runtime, with the bucketed mode
ready for runtimes whose scheduler CAN overlap (TPU latency-hiding
scheduler + libtpu combiner thresholds, see docs/performance-guide.md).

Prints ONE JSON line: {"metric": "dwbp_wallclock_ab", ...}
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--batch", type=int, default=8, help="per-device batch")
    ap.add_argument("--image", type=int, default=67)
    ap.add_argument("--bucket_mb", type=float, default=4.0)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from poseidon_tpu.core.net import Net
    from poseidon_tpu.models import zoo
    from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                       init_train_state, make_mesh)
    from poseidon_tpu.parallel.strategies import DENSE_FUSED
    from poseidon_tpu.proto.messages import SolverParameter

    out = {"metric": "dwbp_wallclock_ab", "n_devices": jax.device_count(),
           "backend": jax.default_backend(), "iters": args.iters,
           "bucket_mb": args.bucket_mb}
    try:
        mesh = make_mesh()
        n_dev = jax.device_count()
        # alexnet topology at reduced spatial size: real layer mix (conv
        # stack + the two big FCs whose gradients dominate comm volume)
        net_param = zoo.alexnet(num_classes=256, with_accuracy=False)
        shapes = {"data": (args.batch, 3, args.image, args.image),
                  "label": (args.batch,)}
        net = Net(net_param, phase="TRAIN", source_shapes=shapes)
        sp = SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
        params = net.init(jax.random.PRNGKey(0))
        rs = np.random.RandomState(0)
        batch = {"data": jnp.asarray(rs.randn(
                     args.batch * n_dev, 3, args.image, args.image)
                     .astype(np.float32)),
                 "label": jnp.asarray(rs.randint(
                     0, 256, size=(args.batch * n_dev,), dtype=np.int32))}
        modes = {
            "fused": CommConfig(layer_strategies={
                name: DENSE_FUSED for name in params}),
            "dense": CommConfig(),
            "bucketed": CommConfig(dwbp_bucket_mb=args.bucket_mb),
            "per_blob": CommConfig(dwbp_bucket_mb=0),
        }
        times = {}
        for name, comm in modes.items():
            ts = build_train_step(net, sp, mesh, comm, donate=False)
            state = init_train_state(params, comm, n_dev)
            p, s, m = ts.step(params, state, batch, jax.random.PRNGKey(7))
            jax.block_until_ready(m["loss"])
            # median-of-iters: CPU-mesh walls are noisy (8 threads on a
            # shared host); median resists scheduler spikes
            walls = []
            for _ in range(args.iters):
                t0 = time.perf_counter()
                p, s, m = ts.step(p, s, batch, jax.random.PRNGKey(7))
                jax.block_until_ready(m["loss"])
                walls.append(time.perf_counter() - t0)
            times[name] = float(np.median(walls) * 1e3)
            out[f"{name}_step_ms"] = round(times[name], 2)
            del ts, state, p, s
        for name in ("dense", "bucketed", "per_blob"):
            out[f"{name}_speedup_vs_fused"] = round(
                times["fused"] / times[name], 4)
        out["value"] = out["bucketed_speedup_vs_fused"]
        out["conclusion"] = (
            "bucketed DWBP beats the fused baseline on this runtime"
            if out["value"] > 1.02 else
            "no overlap win on this runtime (synchronous collectives); "
            "XLA's combiner is near-optimal here — distinctness is for "
            "schedulers that can overlap (TPU latency-hiding scheduler)")
    except Exception as e:  # noqa: BLE001
        import traceback
        out["value"] = None
        out["error"] = f"{type(e).__name__}: {e} | " + \
            traceback.format_exc().strip().splitlines()[-1]
    print(json.dumps(out), flush=True)
    return 0 if out.get("value") is not None else 1


if __name__ == "__main__":
    main()
