"""End-to-end Engine.train() throughput — the product path, not the device step.

The headline bench (bench.py) measures the compiled train step with an
on-device synthetic batch re-fed every scan iteration. The reference's
number is end-to-end (/root/reference/docs/performance.md:19): LMDB decode,
transform, host->device transfer, and the solver loop all included
(/root/reference/src/caffe/layers/base_data_layer.cpp:73-103 is the ingest
side). This script times the SAME full path here: BatchPipeline (native
dataplane + background prefetch) -> stacked transfer -> scan-chunk dispatch
through Engine.train(), and reports images/s for direct comparison against
the headline device-step number. A gap >15% between the two IS the next
work item (round-3 verdict item 4).

Prints ONE JSON line:
  {"metric": "engine_e2e_images_per_sec_per_chip", "value": N, ...}

Usage: python scripts/bench_engine_e2e.py [--iters 192] [--warmup 64]
       [--steps_per_dispatch 16] [--batch 256] [--no-device-transform]
       [--cpu]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DB = os.path.join(REPO, "examples/imagenet/ilsvrc12_train_lmdb")


def ensure_db() -> None:
    if os.path.isdir(DB):
        return
    subprocess.run(
        [sys.executable, os.path.join(REPO, "examples/make_synthetic_db.py"),
         "imagenet", "--train", "512", "--test", "16"],
        check=True, cwd=REPO, timeout=900)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=192,
                    help="timed optimizer steps (after warmup)")
    ap.add_argument("--warmup", type=int, default=64,
                    help="untimed steps covering compile + pipeline fill")
    ap.add_argument("--steps_per_dispatch", type=int, default=16)
    ap.add_argument("--batch", type=int, default=256,
                    help="per-device batch (overrides the prototxt)")
    ap.add_argument("--no-device-transform", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    payload: dict = {"metric": "engine_e2e_images_per_sec_per_chip",
                     "unit": "images/s/chip", "value": 0.0,
                     "steps_per_dispatch": args.steps_per_dispatch,
                     "device_transform": not args.no_device_transform}
    try:
        ensure_db()
        import jax
        import jax.numpy as jnp
        from poseidon_tpu import config
        from poseidon_tpu.proto.messages import load_net, load_solver
        from poseidon_tpu.runtime.engine import Engine

        payload["backend"] = jax.default_backend()
        if payload["backend"] == "cpu" and not args.cpu:
            raise RuntimeError("refusing a silent CPU fallback "
                               "(pass --cpu for an explicit smoke run)")
        config.set_policy(compute_dtype=jnp.bfloat16)

        sp = load_solver(
            os.path.join(REPO, "examples/imagenet/alexnet_solver.prototxt"))
        net_param = load_net(os.path.join(REPO, sp.net))
        for lp in net_param.layers:
            if lp.type == "DATA":
                if args.batch:
                    lp.data_param.batch_size = args.batch
                if not args.no_device_transform and \
                        lp.transform_param.mean_file:
                    # the u8 fast path needs a per-channel mean (a mean_file
                    # image must stay host-side); ILSVRC12 BGR channel means
                    lp.transform_param.mean_file = ""
                    lp.transform_param.mean_value = [104.0, 117.0, 123.0]
        # pure-throughput cadence: no display/test/snapshot boundaries, so
        # every dispatch is a full steps_per_dispatch chunk
        sp = dataclasses.replace(
            sp, net="", net_param=None, train_net_param=net_param,
            display=0, test_interval=0, snapshot=0, test_iter=[],
            test_net=[], test_net_param=[], snapshot_after_train=False,
            max_iter=args.warmup + args.iters)
        eng = Engine(sp, output_dir=os.path.join(REPO, "evidence"),
                     steps_per_dispatch=args.steps_per_dispatch,
                     device_transform=not args.no_device_transform)
        n_dev = eng.n_dev

        t0 = time.perf_counter()
        eng.train(max_iter=args.warmup)          # compile + pipeline fill
        payload["warmup_s"] = round(time.perf_counter() - t0, 1)
        t0 = time.perf_counter()
        eng.train(max_iter=args.warmup + args.iters)
        dt = time.perf_counter() - t0
        eng.close()

        global_batch = args.batch * n_dev
        ips = global_batch * args.iters / dt
        payload["value"] = round(ips / n_dev, 2)
        payload["global_images_per_sec"] = round(ips, 2)
        payload["n_devices"] = n_dev
        payload["per_device_batch"] = args.batch
        payload["timed_iters"] = args.iters
        payload["timed_s"] = round(dt, 2)
        # comparison hook for the verdict's 15% criterion
        lg = os.path.join(REPO, "BENCH_last_good.json")
        if os.path.exists(lg):
            try:
                with open(lg) as f:
                    head = json.load(f).get("value", 0.0)
                if head:
                    payload["headline_images_per_sec_per_chip"] = head
                    payload["fraction_of_headline"] = round(
                        payload["value"] / head, 4)
            except Exception:  # noqa: BLE001
                pass
    except Exception as e:  # noqa: BLE001
        import traceback
        payload["error"] = f"{type(e).__name__}: {e} | " + \
            traceback.format_exc().strip().splitlines()[-1]
    print(json.dumps(payload), flush=True)
    return 0 if "error" not in payload else 1


if __name__ == "__main__":
    main()
