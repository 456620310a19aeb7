#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once and print its result as the last line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This file knows no cell, configuration, traffic mix or metric by name. It
finds them by the names in BENCHMARK.json:

    workloads[].name     -> benchmark/cells/<name>.json         (the cell's own numbers; optional)
    workloads[].config   -> configs[].file                      (the configuration as it is run)
    workloads[].traffic  -> benchmark/traffic/<traffic>.json    (the mix; names its runner)
    traffic.runner       -> benchmark/runners/<runner>.py       (how such a job is driven)
    per_layer[].name     -> benchmark/layer_metrics/<name>.py   (one reader each)

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and the trace's breakdown. It runs only
on the TPU chips the cell asks for (exit code 2 otherwise, and no result);
``--cpu-tiny`` is the tests' rehearsal on the CPU at cut widths and says
``"platform": "cpu"`` in its line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import importlib                   # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import sys                         # noqa: E402

import device as device_mod        # noqa: E402
import device_trace                # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def refuse(why: str) -> None:
    print(f"[benchmark] REFUSING: {why}. Nothing was measured.",
          file=sys.stderr)
    raise SystemExit(2)


def load_json(path: str, what: str) -> dict:
    if not os.path.exists(path):
        refuse(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--cpu-tiny", action="store_true",
                    help="TEST ONLY: rehearse on the CPU at cut widths")
    ap.add_argument("--keep-trace", default="",
                    help="with --trace 1: copy the raw profiler trace here "
                         "(to look at one by hand)")
    ap.add_argument("--keep-layers", default="",
                    help="with --trace 1: write what the per-layer readers "
                         "reduce (the runner's ``layers``) here as JSON")
    args = ap.parse_args()

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"), "the benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        refuse(f"workload {args.workload!r} is not in BENCHMARK.json "
               f"(it has {sorted(cells)})")
    cell = dict(cells[args.workload])
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        refuse(f"workload {cell['name']!r}: configuration "
               f"{cell['config']!r} is not in BENCHMARK.json")
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]),
                       f"configuration {cell['config']!r}")
    traffic = load_json(
        os.path.join(BENCH_DIR, "traffic", f"{cell['traffic']}.json"),
        f"traffic mix {cell['traffic']!r}")
    own = os.path.join(BENCH_DIR, "cells", f"{cell['name']}.json")
    if os.path.exists(own):
        cell = {**load_json(own, "cell"), **cell}
    if not os.path.isdir(os.path.join(ROOT, "poseidon_tpu")):
        refuse("the program (poseidon_tpu/) is not in this checkout")

    if args.cpu_tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell['chips']}").strip()
    sys.path.insert(0, ROOT)
    os.chdir(ROOT)

    runner = importlib.import_module(f"runners.{traffic['runner']}")
    result = runner.run({
        "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "trace": bool(args.trace), "tiny": args.cpu_tiny,
        "seconds": bench["run_seconds"] if args.seconds is None
        else args.seconds,
        "t_start": T_START, "bench_dir": BENCH_DIR,
        "work_dir": os.path.join(BENCH_DIR, ".work"),
        "keep_trace": args.keep_trace})

    # what is the machine's and not the repository's work leaves the
    # end-to-end numbers and is reported beside them (device.py says what:
    # the runtime's start of the chips); the readers keep the runner's whole
    # readings, and that part beside them
    end_to_end = dict(result["end_to_end"])
    for name, seconds in device_mod.not_set_up().items():
        end_to_end[name] -= seconds

    device = dict(result["device"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {}, "device": device}
    if args.trace:
        layers = dict(result["layers"], **device_mod.layer_facts())
        if args.keep_layers:
            with open(args.keep_layers, "w") as f:
                json.dump(layers, f)
        for metric in bench["per_layer"]:
            if not applies(metric, cell["name"]):
                continue
            reader = importlib.import_module(
                f"layer_metrics.{metric['name']}")
            value = reader.reduce(layers)
            if value is not None:      # nothing to read: left out
                line["metrics"][metric["name"]] = {
                    "value": float(value), "unit": metric["unit"]}
        summary = device_trace.summarize(layers["trace"])
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        line["breakdown"] = summary["breakdown"]
    else:
        for metric in bench["end_to_end"]:
            if applies(metric, cell["name"]) \
                    and metric["name"] in end_to_end:
                line["metrics"][metric["name"]] = {
                    "value": float(end_to_end[metric["name"]]),
                    "unit": metric["unit"]}
    # the facts line: what the runner saw, and the run's own end-to-end
    # readings whichever kind of line follows (a traced run's untraced pace
    # and set-up time are what its per-layer metrics are read against)
    print(json.dumps({"facts": result["facts"], "end_to_end": end_to_end,
                      **device_mod.layer_facts()}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
