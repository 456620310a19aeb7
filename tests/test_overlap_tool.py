"""The DWBP overlap analyzer end-to-end on an in-process CPU trace.

scripts/analyze_overlap.py is the hardware-evidence tool (xplane ->
collective/compute co-run fraction); this test validates the whole chain —
trace capture, xplane proto parsing, event classification, interval math —
so the only thing left to vary on real TPU is the numbers.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


def test_overlap_fraction_interval_math():
    from analyze_overlap import overlap_fraction
    # one collective [10, 20) with compute covering [0, 15) => 50% overlap
    events = [
        ("psum.1", 10, 10),          # collective, dur 10
        ("fusion.2", 0, 15),         # compute
        ("$python_frame", 0, 100),   # filtered
        ("end: psum.1", 10, 10),     # filtered end-marker
    ]
    out = overlap_fraction(events)
    assert out["n_collectives"] == 1
    assert out["value"] == pytest.approx(0.5)


def test_overlap_tool_on_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def f(x):
        g = jnp.tanh(x) @ jnp.ones((256, 256), x.dtype)
        return lax.psum(g, "data").sum()

    step = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                 out_specs=P(), check_vma=False))
    x = jnp.ones((16, 256))
    step(x).block_until_ready()
    trace = str(tmp_path / "trace")
    jax.profiler.start_trace(trace)
    for _ in range(2):
        r = step(x)
    r.block_until_ready()
    jax.profiler.stop_trace()

    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "analyze_overlap.py"),
         trace],
        capture_output=True, text=True, timeout=300)
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["n_collectives"] > 0, out
    assert out["value"] is not None and 0.0 <= out["value"] <= 1.0
    assert res.returncode == 0
