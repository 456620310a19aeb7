"""Mosaic-lowering regression gate: compile Pallas kernels with the REAL
TPU compiler, no hardware needed.

Everything else green runs on the CPU interpret path, so a Mosaic lowering
regression would be invisible to the suite. The local libtpu can
AOT-compile for an abstract v5e topology (jax.experimental.topologies);
these tests push the flash attention forward+backward through that
pipeline — the same Mosaic passes the chip runs — on every suite run.
Numerics need the chip (POSEIDON_TEST_TPU=1 pytest tests/test_pallas.py
tests/test_kernels.py there; chip_smoke.py for the CNN kernels); the
lowering half is a plain test.

Skips (not fails) when another process holds the libtpu lockfile or the
plugin cannot initialize — those are environment states, not regressions.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5e-8")
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
try:
    topo = topologies.get_topology_desc("v5e:2x4", platform="tpu")
except Exception as e:
    print("SKIP:", e)
    sys.exit(3)
from poseidon_tpu.ops.pallas_kernels import flash_attention, lrn_fused
m1 = Mesh(np.array(topo.devices[:1]), ("x",))
sh = NamedSharding(m1, P())
q = jax.ShapeDtypeStruct((2, 4, 1024, 64), jnp.bfloat16, sharding=sh)

def fwd(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)

def bwd(q, k, v):
    f = lambda a, b, c: flash_attention(a, b, c, causal=True,
                                        interpret=False).sum()
    return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

for name, fn, avals in [("fwd", fwd, (q, q, q)), ("bwd", bwd, (q, q, q))]:
    txt = jax.jit(fn).lower(*avals).compile().as_text()
    assert txt.count("tpu_custom_call") >= 1, name
    print("OK", name)
x = jax.ShapeDtypeStruct((4, 96, 27, 27), jnp.float32, sharding=sh)
txt = jax.jit(lambda x: lrn_fused(x, 5, 1e-4, 0.75, 1.0,
                                  interpret=False)).lower(x) \
    .compile().as_text()
assert txt.count("tpu_custom_call") >= 1, "lrn"
print("OK lrn")
# grad routes through the one-pass Pallas BACKWARD kernel on TPU — it must
# pass Mosaic too (fwd-only coverage shipped an unlowered bwd in round 5).
# jax.grad discards the primal output, so XLA DCEs the FORWARD custom call
# (its residual is just x): the one surviving call IS the backward kernel.
txt = jax.jit(jax.grad(lambda x: lrn_fused(
    x, 5, 1e-4, 0.75, 1.0, interpret=False).sum())).lower(x) \
    .compile().as_text()
assert txt.count("tpu_custom_call") >= 1, "lrn bwd"
print("OK lrn_bwd")
# the TPU route of a bf16 pool backward is select-and-scatter with an F32
# result: the compiler folds a bare f32 -> bf16 cast into the scatter, which
# then sums overlapping windows in bf16 (seen on the v5e, PR 24)
os.environ["POSEIDON_FORCE_PALLAS"] = "1"      # lower as for the TPU
from poseidon_tpu.ops import nn as NN
xb = jax.ShapeDtypeStruct((8, 128, 27, 27), jnp.bfloat16, sharding=sh)
txt = jax.jit(jax.grad(lambda x: jnp.sum(NN.max_pool(
    x, (3, 3), (2, 2), (0, 0)).astype(jnp.float32) ** 2))).lower(xb) \
    .compile().as_text()
sas = [l for l in txt.splitlines() if " select-and-scatter(" in l]
assert sas and all(" = f32[" in l for l in sas), sas
print("OK pool_bwd")
"""


@pytest.mark.slow
def test_flash_kernels_mosaic_compile_for_v5e():
    """flash fwd/bwd + fused LRN must pass the real Mosaic pipeline; the
    bf16 pool backward must keep its select-and-scatter in f32."""
    r = subprocess.run(
        [sys.executable, "-c", _CODE.format(repo=REPO)],
        capture_output=True, text=True, timeout=900)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "OK fwd" in r.stdout and "OK bwd" in r.stdout \
        and "OK lrn" in r.stdout and "OK lrn_bwd" in r.stdout \
        and "OK pool_bwd" in r.stdout
