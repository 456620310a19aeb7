"""What the readers of ANOTHER checkout of the benchmark (the parent of the
PR that merged the per-cell twins, ISSUE 50) return on one recorded
``layers`` dict, printed as ``{metric name: value or null}``:

    python3 parent_values.py <checkout>/benchmark <layers.json[.gz]> <cell>

Run as a process of its own, so that the other checkout's modules
(``lm_trace``, ``layer_metrics.*``: the same names as this one's) are the
only ones on the path. The dict is handed over as that checkout's runner
would have made it: its configuration's ``scopes`` under the names it knew,
the marker its readers looked for, the required work under its key.
"""

import gzip
import importlib
import json
import os
import sys

# the parent's runners marked ``run["lm"]`` with their cell's name, and two
# handed the delta-rule scan's required work under the kernel's name
MARKER = {"zaya1_8b": "zaya", "trinity_mini": "trinity",
          "kimi_linear_48b": "kimi", "smallthinker_21b": "smallthinker",
          "olmo_hybrid_7b": "olmo_hybrid"}
SCAN_KEY = {"kimi_linear_48b": "kda_scan_per_step",
            "olmo_hybrid_7b": "gdn_scan_per_step"}


def load_layers(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def as_handed_by(bench_dir: str, bench: dict, cell: str, layers: dict) -> dict:
    config = next(w["config"] for w in bench["workloads"]
                  if w["name"] == cell)
    lm = layers.get("lm")
    if lm is None:
        return layers
    with open(os.path.join(os.path.dirname(bench_dir), next(
            c["file"] for c in bench["configs"] if c["name"] == config))) as f:
        scopes = json.load(f)["scopes"]
    lm = dict(lm, scopes=scopes)
    if isinstance(scopes.get("head"), list):      # OLMoE's, a list of names
        lm["head_scopes"] = scopes["head"]
        del lm["scopes"]
    if config in MARKER:
        lm[MARKER[config]] = True
    if config in SCAN_KEY:
        lm[SCAN_KEY[config]] = lm["delta_scan_per_step"]
    return dict(layers, lm=lm)


def main(bench_dir: str, layers_path: str, cell: str) -> None:
    bench_dir = os.path.abspath(bench_dir)
    sys.path.insert(0, bench_dir)
    with open(os.path.join(os.path.dirname(bench_dir),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers = as_handed_by(bench_dir, bench, cell, load_layers(layers_path))
    values = {}
    for metric in bench["per_layer"]:
        if "workloads" in metric and cell not in metric["workloads"]:
            continue
        reader = importlib.import_module(f"layer_metrics.{metric['name']}")
        values[metric["name"]] = reader.reduce(layers)
    print(json.dumps(values))


if __name__ == "__main__":
    main(*sys.argv[1:4])
