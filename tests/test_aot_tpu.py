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
# the `sas` arm of a bf16 pool backward (the TPU's route for MAX pooling
# until PR 35, still its A/B arm) is select-and-scatter with an F32 result:
# the compiler folds a bare f32 -> bf16 cast into the scatter, which then
# sums overlapping windows in bf16 (seen on the v5e, PR 24)
os.environ["POSEIDON_FORCE_PALLAS"] = "1"      # lower as for the TPU
os.environ["POSEIDON_POOL_BWD"] = "sas"
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


# The flash kernels at the token cells' ATTENTION geometries, bf16, causal,
# with the tiles the rule picks, under the bf16 policy (`train --bf16`), in
# the operand form ``flash_operand_form`` sends each: a tile choice Mosaic
# rejects, or a head's dQ rows that do not fit VMEM beside the single
# sweep's tiles, is found here and not on the chip.
_FLASH_CELL = r"""
import json, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("SKIP:", e)
    sys.exit(3)
from poseidon_tpu.config import set_perf_policy
from poseidon_tpu.ops import pallas_kernels as PK
set_perf_policy()
B, H, S, D, DV, W = {geometry}
lanes, _ = PK.flash_operand_form(S, D, DV)
sh = SingleDeviceSharding(topo.devices[0])
like = lambda w: jax.ShapeDtypeStruct(
    (B, S, H * w) if lanes else (B, H, S, w), jnp.bfloat16, sharding=sh)
q, v = like(D), like(DV)
row = jax.ShapeDtypeStruct((B, H, S), jnp.float32, sharding=sh)
scale, heads = D ** -0.5, H if lanes else None
fwd = lambda q, k, v: PK._flash_fwd(q, k, v, scale, True, None, None, False,
                                    window=W, heads=heads)
bwd = lambda q, k, v, o, lse, g: PK._flash_bwd(
    q, k, v, o, lse, g, scale, True, None, None, False, window=W,
    heads=heads)
text = {{"fwd": jax.jit(fwd).lower(q, q, v).compile().as_text(),
        "bwd": jax.jit(bwd).lower(q, q, v, v, row, v).compile().as_text()}}
kernels = {{"token_major": lanes}}
for kernel, name in (("fwd", "flash_fwd"), ("bwd", "flash_bwd")):
    bq, bk = PK.flash_blocks(kernel, S, D, 2, DV)
    live, visited = PK.flash_grid_programs(S, bq, bk, True, W,
                                           over_q=kernel == "bwd")
    kernels[kernel] = {{
        "blocks": [bq, bk], "live": B * H * live, "grid": B * H * visited,
        "vmem_mib": PK._flash_vmem_bytes(kernel, bq, bk, D, 2, DV, S) / 2**20,
        "compiled": sum(1 for l in text[kernel].splitlines()
                        if 'custom_call_target="tpu_custom_call"' in l
                        and re.search("%" + name + r"\b", l.split("=")[0]))}}
kernels["calls"] = sum(t.count('custom_call_target="tpu_custom_call"')
                       for t in text.values())
print("RESULT " + json.dumps(kernels))
"""


# cell: ((sequences, heads, S, Dh, Dv, window), token-major?, live, visited
# programs a head, the VMEM the rule counts for the single sweep in MiB)
_FLASH_GEOMETRIES = {
    # 2 x 16 heads of 128 at S 4096: 1024 x 1024 makes 512 programs a grid
    # where 128 x 128 made 32,768, 10 of 16 block pairs a head live
    "olmoe": ((2, 16, 4096, 128, 128, None), True, 10, 16, 28),
    # one sequence x 28 heads of 128 at S 16,384, twice the longest any
    # other cell runs: the causal grid (the global layer) and the band's at
    # W 4096 (the window layers: at most 5 live blocks a block), where the
    # causal grid would visit 256
    "smallthinker_global": ((1, 28, 16384, 128, 128, None), True, 136, 256,
                            40),
    "smallthinker_window": ((1, 28, 16384, 128, 128, 4096), True, 70, 80,
                            40),
    # latent attention: 20 heads of 256 / 256 along the lanes, the widest
    # dQ rows of any cell (8.4 + 8.4 MB); 32 heads of 192 / 128, head-major
    "glm_flash": ((2, 20, 8192, 256, 256, None), True, 36, 64, 44),
    "kimi_xing4": ((1, 32, 8192, 192, 128, None), False, 36, 64, 37),
}


@pytest.mark.parametrize("cell", sorted(_FLASH_GEOMETRIES))
def test_flash_kernels_compile_for_v5e_at_the_cell_geometry(cell):
    """flash_fwd and the single-sweep flash_bwd pass Mosaic for an abstract
    v5e at the cell's geometry with the rule's 1024 x 1024 tiles, the head's
    dQ rows resident: two calls, no dQ sweep. The tiles, the programs a
    grid and the VMEM the rule reckons are printed for whoever reads the
    run: the proof of fit without a chip."""
    import json
    geometry, lanes, live, visited, mib = _FLASH_GEOMETRIES[cell]
    r = subprocess.run(
        [sys.executable, "-c",
         _FLASH_CELL.format(repo=REPO, geometry=geometry)],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(cell, got)
    assert got["token_major"] is lanes and got["calls"] == 2
    assert got["fwd"].pop("vmem_mib") < got["bwd"].pop("vmem_mib") == mib
    heads = geometry[0] * geometry[1]
    for kernel in ("fwd", "bwd"):
        assert got[kernel] == {"blocks": [1024, 1024], "live": heads * live,
                               "grid": heads * visited, "compiled": 1}, got


# The full-width, one-layer OLMoE train step (examples/lm/olmoe_1b_7b_*) as
# `train --bf16` builds it, for one abstract v5e chip: the kernels that must
# be in it, and the compiler's memory accounting that sized the cell's batch
# (benchmark/cells/olmoe.l1.pack4k.json).
_OLMOE_STEP = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["POSEIDON_FORCE_PALLAS"] = "1"      # lower as for the TPU
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("SKIP:", e)
    sys.exit(3)
from poseidon_tpu.config import set_perf_policy
from poseidon_tpu.core.net import Net
from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                   init_train_state)
from poseidon_tpu.proto.messages import load_net, load_solver
from poseidon_tpu.runtime.attribution import param_relayouts
set_perf_policy()
batch, seq = {batch}, 4096
sp = load_solver(os.path.join({repo!r},
                              "examples/lm/olmoe_1b_7b_solver.prototxt"))
net = Net(load_net(os.path.join({repo!r}, sp.net)), "TRAIN",
          source_shapes={{"tokens": (batch, seq), "targets": (batch, seq)}})
mesh = Mesh(np.array(topo.devices[:1]), ("data",))
comm = CommConfig()
ts = build_train_step(net, sp, mesh, comm, donate=True, donate_batch=True)
rep = NamedSharding(mesh, P())
shaped = lambda t, sh: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), t)
params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
state = jax.eval_shape(
    lambda p: init_train_state(p, comm, 1, sp.solver_type), params)
tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                              sharding=ts.batch_sharding)
compiled = ts.lowerable.lower(
    shaped(params, rep), shaped(state, rep),
    {{"tokens": tokens, "targets": tokens}},
    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)).compile()
ma, text = compiled.memory_analysis(), compiled.as_text()
print("RESULT " + json.dumps({{
    "parameters": net.param_count(),
    "arena_parameters": ts.arena.total if ts.arena else 0,
    "routes": net.kernel_routes,
    "pallas_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
    "ragged_dot_fusions": sum(
        1 for l in text.splitlines()
        if "ragged_dot_tiling" in l and " custom-call(" in l),
    "param_relayout_copies": param_relayouts(text)["copies"],
    "expert_stack_f32_copies": sum(
        1 for l in text.splitlines() if " copy(" in l
        if " f32[64,1024,2048]" in l or " f32[64,2048,1024]" in l),
    "dense_expert_dots": sum(
        1 for l in text.splitlines()
        if " dot(" in l or " convolution(" in l
        if "[8192,64,1024]" in l or "[64,8192,1024]" in l),
    "argument_gb": ma.argument_size_in_bytes / 1e9,
    "temp_gb": ma.temp_size_in_bytes / 1e9,
    "total_gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 1e9}}))
"""


@pytest.mark.slow
def test_olmoe_full_width_step_compiles_for_one_v5e():
    """The flash kernels and the grouped matmul are in the compiled step
    (no dense 64-expert fallback, no CPU or interpret arm), a one-device
    step packs nothing into an arena, and the step fits one chip at the
    cell's batch: under 85% of the 16.9 GB the compiler allows (PR 22's
    sizing rule)."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _OLMOE_STEP.format(repo=REPO, batch=2)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next batch
    assert got["parameters"] == 625_616_896
    assert got["routes"] == {
        "l0_attn": "attention=pallas_flash (fwd 1024x1024 10/16, "
                   "bwd 1024x1024 10/16; "
                   "block_q x block_k, live/visited programs a head; "
                   "operands token-major (B,S,HxD))",
        "l0_moe": "grouped_matmul=ragged_dot"}
    assert got["pallas_custom_calls"] >= 2       # flash fwd, bwd
    # gate, up, down: forward, dx, dw; the weight gradients leave their
    # calls as the stacks are stored, so nothing between a parameter and
    # its update is copied into another layout (12 x 537 MB until PR 30)
    assert got["ragged_dot_fusions"] == 9
    assert got["param_relayout_copies"] == 0
    assert got["expert_stack_f32_copies"] == 0
    assert got["dense_expert_dots"] == 0
    # one device on the sync axes: no arena (until PR 26 the attention
    # projections and norm gains, 16.8M, were packed with both moments)
    assert got["arena_parameters"] == 0
    assert 7.0 < got["argument_gb"] < 8.0        # weights + two moments
    assert got["total_gb"] < 0.85 * 16.9


# The full-width Ouro train step (examples/lm/ouro_2_6b_*) as `train --bf16
# --remat <the solver header's flags>` builds it at one sequence of 8,192, for
# one abstract v5e chip: the compiler's memory accounting that fixed the
# configuration's depth and the cell's batch (benchmark/configs/ouro_2_6b.json,
# benchmark/cells/ouro.loop4.pack8k.json), at the depth in the files and one
# layer deeper.
_OURO_STEP = r"""
import json, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["POSEIDON_FORCE_PALLAS"] = "1"      # lower as for the TPU
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("SKIP:", e)
    sys.exit(3)
from poseidon_tpu.config import set_perf_policy
from poseidon_tpu.core.net import Net
from poseidon_tpu.core.remat import RematPlan, resolve_entries
from poseidon_tpu.models import zoo
from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                   init_train_state)
from poseidon_tpu.proto.messages import load_net, load_solver
set_perf_policy()
batch, seq, deeper = 1, 8192, {deeper}
solver = os.path.join({repo!r}, "examples/lm/ouro_2_6b_solver.prototxt")
sp = load_solver(solver)
flags = re.search(r"--remat '([^']+)'", open(solver).read()).group(1)
net_param = load_net(os.path.join({repo!r}, sp.net))
depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4
if deeper:
    net_param = zoo.ouro(batch=batch, n_layers=depth + deeper)
net = Net(net_param, "TRAIN",
          source_shapes={{"tokens": (batch, seq), "targets": (batch, seq)}})
layers, segments = resolve_entries([l.name for l in net.layers],
                                   flags.split(","))
plan = RematPlan(layers=layers, segments=segments, source="flag")
mesh = Mesh(np.array(topo.devices[:1]), ("data",))
comm = CommConfig()
ts = build_train_step(net, sp, mesh, comm, donate=True, donate_batch=False,
                      remat_plan=plan)
rep = NamedSharding(mesh, P())
shaped = lambda t, sh: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), t)
params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
state = jax.eval_shape(
    lambda p: init_train_state(p, comm, 1, sp.solver_type), params)
tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                              sharding=ts.batch_sharding)
compiled = ts.lowerable.lower(
    shaped(params, rep), shaped(state, rep),
    {{"tokens": tokens, "targets": tokens}},
    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)).compile()
ma, text = compiled.memory_analysis(), compiled.as_text()
print("RESULT " + json.dumps({{
    "depth": depth + deeper, "parameters": net.param_count(),
    "leaves": len(jax.tree.leaves(params)), "segments": len(segments),
    "routes": sorted(set(net.kernel_routes.values())),
    "pallas_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
    "argument_gb": ma.argument_size_in_bytes / 1e9,
    "temp_gb": ma.temp_size_in_bytes / 1e9,
    "total_gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 - ma.alias_size_in_bytes + ma.temp_size_in_bytes) / 1e9}}))
"""


@pytest.mark.slow
@pytest.mark.parametrize("deeper", [0, 1])
def test_ouro_full_width_step_fits_one_v5e_at_its_depth_and_no_deeper(deeper):
    """At the depth of the example (and of the cell) the remat'd step is
    under 85% of the 16.9 GB the compiler allows (PR 22's sizing rule) with
    one sequence of 8,192; one layer deeper it is at the rule's edge (over
    it until PR 47). A shared weight is
    one leaf (11 a layer + embedding, final norm, head, gate w and b), every
    block application and every head is one checkpoint segment, the flash
    kernels run forward, replayed forward, dQ and dK/dV in every
    application. (With the loss's backward left to autodiff the same step
    compiled at 15.0 GB at six layers; written out, 12.6.)"""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _OURO_STEP.format(repo=REPO, deeper=deeper)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next depth
    depth = got["depth"]
    assert got["parameters"] == 201_330_689 + depth * 51_388_416
    assert got["leaves"] == 11 * depth + 5
    assert got["segments"] == 4 * depth + 4
    assert got["routes"] == [
        "attention=pallas_flash (fwd 1024x1024 36/64, bwd 1024x1024 36/64; "
        "block_q x block_k, live/visited programs a "
        "head; operands token-major (B,S,HxD))"]
    assert got["pallas_custom_calls"] == 3 * 4 * depth
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    if deeper:
        # 14.28 = 84.5% since PR 47 (the ATTENTION layers' f32 rotary
        # temporaries of (1, 16, 8192, 128) went with the transposes; 15.65
        # before): a hair under the rule's 14.365, nothing a cell is sized by
        assert got["total_gb"] > 0.84 * 16.9
    else:
        # 13.40 (PR 47; 13.70 before)
        assert 0.70 * 16.9 < got["total_gb"] < 0.85 * 16.9


# The full-width ZAYA1 train step (examples/lm/zaya1_8b_*: 8 of 16 experts
# held, an eighth of the tied vocabulary) as `train --bf16 --remat <the solver
# header's flags>` builds it at the cell's two sequences of 8,192, for one
# abstract v5e chip: the compiler's memory accounting that fixed the
# configuration's depth (benchmark/configs/zaya1_8b.json,
# benchmark/cells/zaya1.e8of16.pack8k.json), at the depth in the files and one
# layer deeper.
_ZAYA_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 2, 8192, {deeper}").replace(
    "ouro_2_6b_solver", "zaya1_8b_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers)').replace(
    "zoo.ouro(batch=batch, n_layers=depth + deeper)",
    "zoo.zaya1(batch=batch, n_layers=depth + deeper, held=8, vocab=32784)")
assert _ZAYA_STEP.count("zaya1") == 2 and "ouro" not in _ZAYA_STEP


@pytest.mark.slow
@pytest.mark.parametrize("deeper", [0, 1])
def test_zaya_full_width_step_fits_one_v5e_at_its_depth_and_no_deeper(deeper):
    """At the depth of the example (and of the cell) the step with one
    checkpoint a layer is under 85% of the 16.9 GB the compiler allows
    (PR 22's sizing rule) with two sequences of 8,192; one layer deeper it
    is over. The tied table is one leaf, the routers' selection biases are
    leaves like any other (weight and two moments in the arguments), the
    flash kernels run forward, replayed forward, dQ and dK/dV in every
    layer at 8 query heads (k and v repeated to them), and the three
    grouped matmuls of every MOE layer run over the held experts' rows."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _ZAYA_STEP.format(repo=REPO, deeper=deeper)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next depth
    depth = got["depth"]
    assert depth == 7 + deeper
    assert got["parameters"] == 67_143_680 - 256 + depth * 106_902_802
    # embed, final norm; a layer: 2 gains, q k v1 v2 o, 4 conv blobs, tau,
    # 3 stacks, the router's 6 (5 in the first layer)
    assert got["leaves"] == 2 + 21 * depth - 1
    assert got["segments"] == depth + 1
    assert got["routes"] == [
        "attention=pallas_flash (fwd 1024x1024 36/64, bwd 1024x1024 36/64; "
        "block_q x block_k, live/visited programs a "
        "head; operands token-major (B,S,HxD)); 2 kv heads repeated x4",
        "grouped_matmul=ragged_dot"]
    # 3 flash calls and 15 grouped matmuls (3 forward, 3 replayed, 9
    # backward) a layer
    assert got["pallas_custom_calls"] == 18 * depth
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    if deeper:
        assert got["total_gb"] > 0.85 * 16.9
    else:
        assert 0.70 * 16.9 < got["total_gb"] < 0.85 * 16.9


# The full-width Trinity-Mini train step (examples/lm/trinity_mini_*: 1 dense +
# 4 MoE layers, 16 of 128 experts held, an eighth of the untied vocabulary) as
# `train --bf16 --remat <the solver header's flags>` builds it, for one
# abstract v5e chip: the compiler's memory accounting that fixed the cell's
# batch (benchmark/configs/trinity_mini.json,
# benchmark/cells/trinity.e16of128.pack8k.json) at the depth in the files, at
# the batch chosen and one sequence more.
_TRINITY_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 2 + {deeper}, 8192, 0").replace(
    "ouro_2_6b_solver", "trinity_mini_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers)')
assert _TRINITY_STEP.count("trinity") == 1 and "ouro_2" not in _TRINITY_STEP


@pytest.mark.slow
@pytest.mark.parametrize("more", [0, 1])
def test_trinity_full_width_step_fits_one_v5e_at_its_batch_and_no_larger(more):
    """At two sequences of 8,192 the step with one checkpoint a layer is
    under 85% of the 16.9 GB the compiler allows (PR 22's sizing rule); at
    three it is over. The window layers' three flash kernels run on the
    band's grid (21 live of 24 visited programs a head where the causal
    grid visits 64), the global layer's on the causal one; k and v reach
    the 32 query heads by a repeat of 8; the selection biases are leaves
    like any other (weight and two moments in the arguments); each MOE
    layer's held rows run in chunks of 16,384 under one loop a pass."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _TRINITY_STEP.format(repo=REPO, deeper=more)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next cut
    assert got["depth"] == 5 and got["parameters"] == 705_474_304
    # embed, head, final norm; a layer: 4 norms, q k v g o, 2 qk gains; the
    # dense layer's 3; a MoE layer's router 2, 3 stacks, shared 3
    assert got["leaves"] == 3 + 5 * 11 + 3 + 4 * 8
    assert got["segments"] == 5 + 1
    tiles = "fwd 1024x1024 {0}, bwd 1024x1024 {0}; " \
        "block_q x block_k, live/visited programs a head"
    assert got["routes"] == [
        "attention=pallas_flash (" + tiles.format("21/24")
        + "; window 2048: the band's grid; operands token-major (B,S,HxD)); "
        "4 kv heads repeated x8",
        "attention=pallas_flash (" + tiles.format("36/64")
        + "; operands token-major (B,S,HxD)); 4 kv heads repeated x8; "
        "no positions",
        f"grouped_matmul=ragged_dot; held rows: chunks of {8192 * (2 + more)}"
        f" of {65536 * (2 + more)}"]
    # 3 flash calls a layer. A MoE layer's held arm is one loop a pass (PR
    # 43): the forward's and its replay's hold 3 grouped matmuls and 1
    # group-metadata call each, the backward's 8 and 2 (the chunk's a and
    # b again, dy down, three weight gradients, two dx products): 18 where
    # PR 37's two-rung ladder held 38
    assert got["pallas_custom_calls"] == 3 * 5 + 18 * 4
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    if more:
        # 14.95 = 88.5% (PR 43); 16.27 (PR 36), 15.10 (PR 37)
        assert got["total_gb"] > 0.85 * 16.9
    else:
        # 13.223 = 78.2% with the chunked loop (PR 43; temporaries 4.76 GB);
        # 14.19 (PR 36), 14.227 with the ladder (PR 37: what the cell's
        # `why` quotes). The loop's f32 gradient sums handed to the update
        # as they are compile at 15.64 GB: the compiler fuses the narrowing
        # cast into the update and keeps the wide sums until then
        # (`_held_chunks_bwd`'s barrier)
        assert 0.70 * 16.9 < got["total_gb"] < 0.85 * 16.9


# The full-width Kimi-Linear train step (examples/lm/kimi_linear_*: layers 1-5,
# KDA + dense; KDA, KDA, MLA, KDA with a MoE each, 8 of 256 experts held, an
# eighth of the untied vocabulary) as `train --bf16 --remat <the solver
# header's flags>` builds it, for one abstract v5e chip: the compiler's
# memory accounting that fixed the cell's batch
# (benchmark/configs/kimi_linear_48b.json,
# benchmark/cells/kimi_linear.e8of256.pack8k.json) at the depth in the files,
# at the batch chosen and one sequence more.
_KIMI_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 1 + {deeper}, 8192, 0").replace(
    "ouro_2_6b_solver", "kimi_linear_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type in ("ATTENTION", "KDA_SCAN") '
    'for l in net_param.layers)')
assert _KIMI_STEP.count("kimi") == 1 and "ouro_2" not in _KIMI_STEP


@pytest.mark.slow
@pytest.mark.parametrize("more", [0, 1])
def test_kimi_full_width_step_fits_one_v5e_at_its_batch_and_no_larger(more):
    """At one sequence of 8,192 the step with one checkpoint a layer is
    under 85% of the 16.9 GB the compiler allows (PR 22's sizing rule); at
    two it was over until PR 43. The four KDA layers' recurrences are the scan's Pallas
    kernels (128 chunks of 64, four a program, the f32 state in VMEM: the
    forward, its replay and the backward, one call each a layer), the one
    MLA layer's three flash kernels
    run at 192-wide scores over 128-wide values on the causal grid, the
    shared key part repeated to the 32 heads; each MOE layer's held rows
    run in chunks of 8,192 under one loop a pass. (Since PR 43 two
    sequences compile under the rule too: "no larger" no longer holds.)"""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _KIMI_STEP.format(repo=REPO, deeper=more)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    if more and "Ran out of memory in memory space hbm" in r.stderr:
        return            # over the whole chip: 15.83 of 15.75 GiB (PR 41,
        #                   the jax.numpy scan; the kernels' step compiles)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next cut
    assert got["depth"] == 5 and got["parameters"] == 602_434_432
    # embed, head, final norm; a layer: 2 norms; a KDA mixer 15, the MLA
    # mixer 6; the dense layer's 3; a MoE layer's router 2, 3 stacks,
    # shared 3
    assert got["leaves"] == 3 + 5 * 2 + 4 * 15 + 6 + 3 + 4 * 8
    assert got["segments"] == 5 + 1
    rows = 8192 * 8 * (1 + more)
    assert got["routes"] == [
        "attention=pallas_flash (fwd 1024x1024 36/64, bwd 1024x1024 36/64; "
        "block_q x block_k, live/visited programs a "
        "head; flash d 192/128; operands head-major (Dh 192, not "
        "lane-aligned)); no positions; k_pe repeated x32",
        f"grouped_matmul=ragged_dot; held rows: chunks of 8192 of {rows}",
        "kda=pallas (C 64 x 4, 128 chunks, f32 state in VMEM)"]
    # 3 flash calls in the MLA layer; a MoE layer's held arm is one loop a
    # pass (see the Trinity test): 4 calls in the forward's, 10 in the
    # backward's, and no replay (nothing in the layer needs the MoE's
    # output again); a KDA layer's scan three times: forward, the replay,
    # backward
    assert got["pallas_custom_calls"] == 3 + 14 * 4 + 3 * 4
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    if more:
        # 12.87 = 76.2% with the chunked loop (PR 43; temporaries 5.64 GB):
        # the second sequence now PASSES the sizing rule (PERF.md section 7,
        # 43d: the cell's batch is the benchmark's to raise); 15.02 = 88.8%
        # with the ladder (PR 42), 17.0 with the jax.numpy scan (PR 41)
        assert 0.70 * 16.9 < got["total_gb"] < 0.85 * 16.9
    else:
        # 10.818 = 64.0% (PR 43; temporaries 3.59 GB); 12.08 (PR 42, which
        # the ladder's full rung cost 1.26 GB of); 13.00 (PR 41: what the
        # cell's `why` quotes)
        assert 0.60 * 16.9 < got["total_gb"] < 0.70 * 16.9



# The full-width Olmo-Hybrid train step (examples/lm/olmo_hybrid_7b_*:
# published layers 0-3, linear x3 + full; 15 of the 30 heads of both mixers
# held, an eighth of the untied vocabulary) as `train --bf16 --remat <the
# solver header's flags>` builds it at one sequence of 8,192, for one abstract
# v5e chip: the compiler's memory accounting that fixed the cell's batch
# (benchmark/cells/olmo_hybrid.p1.pack8k.json).
_OLMO_HYBRID_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 1 + {deeper}, 8192, 0").replace(
    "ouro_2_6b_solver", "olmo_hybrid_7b_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type in ("ATTENTION", "KDA_SCAN") '
    'for l in net_param.layers)')
assert _OLMO_HYBRID_STEP.count("olmo_hybrid") == 1 \
    and "ouro_2" not in _OLMO_HYBRID_STEP


@pytest.mark.slow
@pytest.mark.parametrize("more", [0, 1])
def test_olmo_hybrid_full_width_step_fits_one_v5e_at_one_and_two_sequences(
        more):
    """At one sequence of 8,192 and 15 of 30 heads the step with one
    checkpoint a layer is under 85% of the 16.9 GB the compiler allows (PR
    22's sizing rule): 12.17 GB = 72.0% (9.20 GB of arguments + 2.97 GB of
    temporaries; 12.37 with the ``jax.numpy`` scan; PR 48), and at two it
    still is (13.68 GB = 80.9%: ISSUE 48 fixes the cell at one). The three
    Gated DeltaNet layers' recurrences are the scan's Pallas kernels'
    per-head arm (128 chunks of 64, four a program, the f32 state in VMEM,
    heads of 96 / 192 in lanes padded to 128 / 256: the forward, its replay
    and the backward, one call each a layer), the full layer's three flash
    kernels run at 15 heads of 128, token-major, no positions: Mosaic
    compiles all of them for the v5e here."""
    import json
    r = subprocess.run(
        [sys.executable, "-c",
         _OLMO_HYBRID_STEP.format(repo=REPO, deeper=more)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next cut
    assert got["depth"] == 4 and got["parameters"] == 766_241_946
    # embed, head, final norm; a layer: 2 norms + 3 FFN; a linear mixer 13
    # (q k v z o a b, 3 convs, A_log + dt_bias, out-norm), the full one 6
    assert got["leaves"] == 3 + 4 * 5 + 3 * 13 + 6
    assert got["segments"] == 4 + 1
    assert got["routes"] == [
        "attention=pallas_flash (fwd 1024x1024 36/64, bwd 1024x1024 36/64; "
        "block_q x block_k, live/visited programs a "
        "head; operands token-major (B,S,HxD)); no positions",
        "kda=pallas (C 64 x 4, 128 chunks, f32 state in VMEM, one decay a "
        "head, lanes 96 / 192 padded to 128 / 256)"]
    # 3 flash calls in the full layer; a linear layer's scan three times:
    # forward, the replay, backward
    assert got["pallas_custom_calls"] == 3 + 3 * 3
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    if more:
        assert 0.76 * 16.9 < got["total_gb"] < 0.85 * 16.9
    else:
        assert 0.68 * 16.9 < got["total_gb"] < 0.76 * 16.9


# The full-width Granite-4.0-H-Micro train step (examples/lm/granite_h_micro_*:
# published layers 0-9, one period of nine Mamba-2 layers and the attention
# layer; an eighth of the tied vocabulary) as `train --bf16 --remat <the
# solver header's flags>` builds it at one sequence of 8,192, for one abstract
# v5e chip: the compiler's memory accounting that fixed the cell's batch
# (benchmark/cells/granite_h.p1.pack8k.json), and at two.
_GRANITE_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 1 + {deeper}, 8192, 0").replace(
    "ouro_2_6b_solver", "granite_h_micro_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type in ("ATTENTION", "SSD_SCAN") '
    'for l in net_param.layers)')
assert _GRANITE_STEP.count("granite_h_micro") == 1 \
    and "ouro_2" not in _GRANITE_STEP


@pytest.mark.slow
@pytest.mark.parametrize("more", [0, 1])
def test_granite_full_width_step_fits_one_v5e_at_one_and_two_sequences(more):
    """At one sequence of 8,192 the step with one checkpoint a layer is
    under ``remat.KEEP_SHARE`` of the 16.9 GB the compiler allows with the
    scans' and the flash kernels' forward results kept. The nine Mamba-2
    layers' recurrences are the scan's Pallas kernels (32 chunks of 256,
    eight heads a program, two a lane block, the f32 states in VMEM: forward
    and backward, one call each a layer where the checkpoint keeps what the
    forward wrote), the attention layer's flash kernels run at 32 heads of
    64 head-major with no positions: Mosaic compiles all of them for the
    v5e here."""
    import json
    from poseidon_tpu.core.remat import KEEP_SHARE
    r = subprocess.run(
        [sys.executable, "-c", _GRANITE_STEP.format(repo=REPO, deeper=more)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next cut
    assert got["depth"] == 10 and got["parameters"] == 772_160_448
    # the table, final norm; a layer: 2 norms + 2 MLP; a mamba mixer 8 (in,
    # conv w + b, A_log + dt_bias, D, out-norm, out), the attention one 4
    assert got["leaves"] == 2 + 10 * 4 + 9 * 8 + 4
    assert got["segments"] == 10 + 1
    assert got["routes"] == [
        "attention=pallas_flash (fwd 1024x1024 36/64, bwd 1024x1024 36/64; "
        "block_q x block_k, live/visited programs a "
        "head; operands head-major (Dh 64, not lane-aligned)); 8 kv heads "
        "repeated x4; no positions",
        "ssd_scan=pallas (Q 256, 32 chunks, 8 heads a program, 2 a lane "
        "block, one C B^T grid a program, f32 states in VMEM, passes 0.47 / "
        "0.47 of six a product)"]
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    assert got["total_gb"] < KEEP_SHARE * 16.9


# PR 57: the same three steps with ``remat.keep_rungs``' FIRST rung kept (the
# gated FFNs' products beside the scans' and the flash kernels' results), as
# ``Engine._compile_step`` first tries them: what the units keep by name, the
# floor it compares with the budget before it compiles, and the compiled size
# it compares after.
def _kept(step: str) -> str:
    step = step.replace(
        "from poseidon_tpu.core.remat import RematPlan, resolve_entries",
        "from poseidon_tpu.core.remat import (RematPlan, keep_rungs,\n"
        "                                     resolve_entries)\n"
        "from poseidon_tpu.runtime.attribution import unit_residuals").replace(
        'plan = RematPlan(layers=layers, segments=segments, source="flag")',
        'plan = RematPlan(layers=layers, segments=segments, source="flag",\n'
        '                 keep=keep_rungs()[0])').replace(
        "compiled = ts.lowerable.lower(", "traced = ts.lowerable.trace(").replace(
        "    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)).compile()",
        "    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep))\n"
        "n_lead = len(jax.tree.leaves((params, state)))\n"
        "named, stored = unit_residuals(traced.jaxpr, plan,\n"
        "                               range(n_lead, n_lead + 2))\n"
        "by_name = {{}}\n"
        "for name, _, b in named:\n"
        "    by_name[name] = by_name.get(name, 0) + b / 1e9\n"
        "floor = sum(a.size * a.dtype.itemsize\n"
        "            for a in traced.jaxpr.in_avals) + stored\n"
        "print('FLOOR ' + json.dumps({{'kept_gb': by_name,\n"
        "    'stored_gb': stored / 1e9,\n"
        "    'floor_gb': floor / 1e9 + sum(by_name.values())}}), flush=True)\n"
        "compiled = traced.lower().compile()")
    assert "keep_rungs()[0]" in step and "FLOOR" in step \
        and "traced.lower().compile()" in step
    return step


_KEPT_STEPS = {"olmo_hybrid": _OLMO_HYBRID_STEP, "granite": _GRANITE_STEP,
               "ouro": _OURO_STEP}


@pytest.mark.slow
@pytest.mark.parametrize("cell", ["olmo_hybrid", "granite", "ouro"])
def test_step_with_the_ffn_rung_kept_for_one_v5e(cell):
    """What ``Engine._compile_step`` meets on a v5e's cold start with the
    first rung (compiler accounting, sandbox, PR 57; beside PR 48's and PR
    54's records above: 12.17 / 12.09 GB keeping nothing, 12.47 / 12.25 GB
    with the kernels' results, the very numbers the chip's Engine reported).

    Olmo-Hybrid: 13.55 GB with 1.58 GB of ``ffn_in`` (four layers' gate and
    up, three ``gdn_z``) and 0.44 of ``ffn_out`` (four down products, three
    ``gdn_o``: a norm reads each again) beside 0.98 of kernel results;
    floor 12.51 GB. Granite: 14.80 GB with 2.68 GB of ``ffn_in`` (ten
    ``l<i>_ffn_in``; nothing reads ``l<i>_ffn_out`` again, so it is not
    kept; ``l<i>_ssd_in`` is not named, see ``Net._plan_gated_products``:
    with it the step compiled at 15.80), floor 13.56: under ``KEEP_SHARE``
    of the 16.9 GB by 0.07. Ouro: 6.11 GB of FFN products over 28
    applications put the floor at 14.77 GB, 0.11 under the budget, so the
    rung is not passed over, and the compiler refuses the step (18.66 of
    15.75 GiB): the Engine falls to PR 49's rung after one failed compile."""
    import json
    from poseidon_tpu.core.remat import KEEP_SHARE
    r = subprocess.run(
        [sys.executable, "-c",
         _kept(_KEPT_STEPS[cell]).format(repo=REPO, deeper=0)],
        capture_output=True, text=True, timeout=2400, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    lines = {l.split(" ", 1)[0]: json.loads(l.split(" ", 1)[1])
             for l in r.stdout.splitlines()
             if l.startswith(("FLOOR ", "RESULT "))}
    print(lines)          # the accounting, for whoever adds the next name
    floor = lines["FLOOR"]
    kept = {k: round(v, 2) for k, v in floor["kept_gb"].items()}
    budget = KEEP_SHARE * 16.9
    if cell == "ouro":
        assert kept == {"ffn_in": 5.17, "ffn_out": 0.94, "flash_out": 0.94,
                        "flash_lse": 0.01}
        assert 14.6 < floor["floor_gb"] < budget
        assert r.returncode != 0 and "RESOURCE_EXHAUSTED" in r.stderr
        return
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = lines["RESULT"]
    if cell == "olmo_hybrid":
        assert kept == {"ffn_in": 1.58, "ffn_out": 0.44, "scan_out": 0.19,
                        "scan_states": 0.75, "flash_out": 0.03,
                        "flash_lse": 0.0}
        assert 12.4 < floor["floor_gb"] < 12.6
        assert 13.4 < got["total_gb"] < 13.7
        # the scans run forward and backward, no replay
        assert got["pallas_custom_calls"] == 2 + 2 * 3
    else:
        assert kept == {"ffn_in": 2.68, "scan_out": 0.6, "scan_states": 0.6,
                        "flash_out": 0.03, "flash_lse": 0.0}
        assert 13.4 < floor["floor_gb"] < 13.7
        assert 14.7 < got["total_gb"] < budget
    assert floor["floor_gb"] < got["total_gb"]


# The full-width SmallThinker train step (examples/lm/smallthinker_21b_*:
# published layers 0-3, global, window, window, window; 16 of 64 experts held,
# an eighth of the untied vocabulary) as `train --bf16 --remat <the solver
# header's flags>` builds it at sequences of 16,384, for one abstract v5e
# chip: the compiler's memory accounting that fixed the cell's batch
# (benchmark/cells/smallthinker.e16of64.pack16k.json), at the batch chosen
# and one sequence more.
_SMALLTHINKER_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 1 + {deeper}, 16384, 0").replace(
    "ouro_2_6b_solver", "smallthinker_21b_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers)')
assert _SMALLTHINKER_STEP.count("smallthinker") == 1 \
    and "ouro_2" not in _SMALLTHINKER_STEP


@pytest.mark.slow
@pytest.mark.parametrize("more", [0, 1])
def test_smallthinker_full_width_step_fits_one_v5e_at_its_batch_and_no_larger(
        more):
    """At one sequence of 16,384 the step with one checkpoint a layer is
    under 85% of the 16.9 GB the compiler allows (PR 22's sizing rule); at
    two it was just over until PR 47 and is under since. The global layer's
    three flash kernels run on the
    causal grid (136 live of 256 visited programs a head), the window
    layers' on the band's (70 of 80 at W 4096); k and v reach the 28 query
    heads by a repeat of 7; each MOE layer's held rows run in chunks of
    24,576 (the even quarter of 98,304) under one loop a pass, ReLU in the
    gate."""
    import json
    r = subprocess.run(
        [sys.executable, "-c",
         _SMALLTHINKER_STEP.format(repo=REPO, deeper=more)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next cut
    assert got["depth"] == 4 and got["parameters"] == 559_290_880
    # embed, head, final norm; a layer: 2 norms, q k v o, router, 3 stacks
    assert got["leaves"] == 3 + 4 * 10
    assert got["segments"] == 4 + 1
    tiles = "fwd 1024x1024 {0}, bwd 1024x1024 {0}; " \
        "block_q x block_k, live/visited programs a head"
    rows = 16384 * 6 * (1 + more)
    assert got["routes"] == [
        "attention=pallas_flash (" + tiles.format("136/256")
        + "; operands token-major (B,S,HxD)); 4 kv heads repeated x7; "
        "no positions",
        "attention=pallas_flash (" + tiles.format("70/80")
        + "; window 4096: the band's grid; operands token-major (B,S,HxD)); "
        "4 kv heads repeated x7",
        f"grouped_matmul=ragged_dot; held rows: chunks of {rows // 4} of "
        f"{rows}; act=relu"]
    # 3 flash calls a layer (forward, its replay, the backward); a MoE layer's
    # held arm is one loop a pass: 4 calls in the forward's, 10 in the
    # backward's, no replay (see the Kimi test), and since PR 53, at this
    # width alone (``held_sum_on_mxu``: 2,560), the grouped product that
    # sums a trip's rows into (T, D) and its group-metadata call in each
    assert got["pallas_custom_calls"] == 3 * 4 + (14 + 4) * 4
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    if more:
        # 13.284 = 78.6% since PR 47 (temporaries 6.57 GB; 14.411 = 85.3%
        # and 7.70 at PR 45, when the ATTENTION layers' rotary ran on f32
        # (1, 28, 16384, 128) arrays): two sequences would fit the rule
        # now; the cell's batch is the accepted benchmark's
        assert 0.75 * 16.9 < got["total_gb"] < 0.85 * 16.9
    else:
        # 10.725 = 63.5% (PR 47; temporaries 4.01 GB; 11.156 at PR 45)
        assert 0.60 * 16.9 < got["total_gb"] < 0.72 * 16.9


# The full-width GLM-4.7-Flash train step (examples/lm/glm_4_7_flash_*:
# published layers 0-4, the dense one and four sparse ones, 8 of 64 experts
# held, an eighth of the untied vocabulary, and the prediction module that
# shares the table and the head) as `train --bf16 --remat <the solver header's
# flags>` builds it at sequences of 8,192, for one abstract v5e chip: the
# compiler's memory accounting that fixed the cell's batch
# (benchmark/cells/glm_flash.e8of64.pack8k.json), at the batch chosen, one
# sequence fewer and one more.
_GLM_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 2 + {deeper}, 8192, 0").replace(
    "ouro_2_6b_solver", "glm_4_7_flash_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers)')
assert _GLM_STEP.count("glm_4_7_flash") == 1 and "ouro_2" not in _GLM_STEP


@pytest.mark.slow
@pytest.mark.parametrize("more", [-1, 0, 1])
def test_glm_full_width_step_fits_one_v5e_at_its_batch_and_no_larger(more):
    """At two sequences of 8,192 the step with one checkpoint a layer, two
    around the prediction module and one around the head is under 85% of
    the 16.9 GB the compiler allows (PR 22's sizing rule); at three it is
    over. All six latent-attention blocks' three flash kernels run at heads
    of 256 / 256, token-major, in 1024 x 1024 tiles (Mosaic compiles them
    for the v5e here: every operand tile twice as wide as any other
    cell's), the shared key part rotated once and joined to the 20 heads
    along the lanes; each MOE layer's held rows run in chunks of 8,192 under
    one loop a pass; the embedding and the head are ONE leaf each."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _GLM_STEP.format(repo=REPO, deeper=more)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next cut
    # six blocks: layers 0-4 and the module's
    assert got["depth"] == 6 and got["parameters"] == 706_518_848
    # embed, head, final norm; a block: 2 norms, 6 projections, 2 latent
    # gains; the dense layer's 3; a sparse block's router 2, 3 stacks,
    # shared 3; the module's 2 norms, W_eh, head norm
    assert got["leaves"] == 3 + 6 * 10 + 3 + 5 * 8 + 4
    # five layers, the module's block, the module's head, the head
    assert got["segments"] == 5 + 2 + 1
    rows = 8192 * 4 * (2 + more)
    assert got["routes"] == [
        "attention=pallas_flash (fwd 1024x1024 36/64, bwd 1024x1024 36/64; "
        "block_q x block_k, live/visited programs a "
        "head; operands token-major (B,S,HxD)); k_pe rotated once, joined "
        "x20",
        f"grouped_matmul=ragged_dot; held rows: chunks of "
        f"{max(8192, 4096 * (2 + more))} of {rows}"]
    # 3 flash calls a block (forward, its replay, the backward); a sparse
    # block's held arm is one loop a pass: 4 calls in the forward's, 10 in
    # the backward's
    assert got["pallas_custom_calls"] == 3 * 6 + 14 * 5
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    if more > 0:
        # 15.45 = 91.4% (PR 56; temporaries 6.98 GB)
        assert got["total_gb"] > 0.85 * 16.9
    elif more == 0:
        # 13.50 = 79.9% (PR 56; temporaries 5.02 GB): what the cell's `why`
        # quotes
        assert 0.72 * 16.9 < got["total_gb"] < 0.85 * 16.9
    else:
        # 11.62 = 68.7% (PR 56; temporaries 3.14 GB)
        assert 0.60 * 16.9 < got["total_gb"] < 0.72 * 16.9


# The full-width Xing4.0-29B-A4B train step (examples/lm/xing4_0_29b_a4b_*:
# published layers 0 and 2-5, the dense one and four sparse ones, on a
# residual stream of four hidden states; 8 of 64 experts held, an eighth of
# the untied vocabulary, no prediction module) as `train --bf16 --remat <the
# solver header's flags>` builds it at sequences of 8,192, for one abstract
# v5e chip: the compiler's memory accounting that fixed the cell's batch
# (benchmark/cells/xing4.e8of64.hc4.json), at the batch chosen and at the
# next.
_XING_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 1 + {deeper}, 8192, 0").replace(
    "ouro_2_6b_solver", "xing4_0_29b_a4b_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers)')
assert _XING_STEP.count("xing4_0_29b_a4b") == 1 \
    and "ouro_2" not in _XING_STEP


@pytest.mark.slow
@pytest.mark.parametrize("more", [0, 1])
def test_xing_full_width_step_fits_one_v5e_at_its_batch_and_no_larger(more):
    """At ONE sequence of 8,192 the step with one checkpoint a layer and one
    around the head is under 85% of the 16.9 GB the compiler allows (PR 22's
    sizing rule): state alone is 72% of the chip, and what a step stores
    between the layers is the four-stream residual state, 235 MB a
    boundary; at two it is over. All five latent-attention blocks' three
    flash kernels run at heads of 192 / 128 on the head-major form (Mosaic
    compiles them for the v5e here with ``rotary_shared``: the shared key
    part and q's tails rotated by YaRN's angles after the head split, the
    shared part joined to the 32 heads); each MOE layer's held rows run in
    chunks of 8,192 under one loop a pass; the stream's passes are XLA
    fusions (no kernel of their own)."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _XING_STEP.format(repo=REPO, deeper=more)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next cut
    assert got["depth"] == 5 and got["parameters"] == 759_346_446
    # embed, head, final norm; a block: 2 norms, 6 projections, 2 latent
    # gains and two mappings of nine leaves; the dense layer's 3; a sparse
    # block's router 2, 3 stacks, shared 3
    assert got["leaves"] == 3 + 5 * (10 + 2 * 9) + 3 + 4 * 8
    # five layers and the head
    assert got["segments"] == 5 + 1
    rows = 8192 * 4 * (1 + more)
    assert got["routes"] == [
        "attention=pallas_flash (fwd 1024x1024 36/64, bwd 1024x1024 36/64; "
        "block_q x block_k, live/visited programs a "
        "head; flash d 192/128; operands head-major (Dh 192, not "
        "lane-aligned)); k_pe rotated once, joined x32; yarn x64",
        f"grouped_matmul=ragged_dot; held rows: chunks of 8192 of {rows}"]
    # 3 flash calls a block (forward, its replay, the backward); a sparse
    # block's held arm is one loop a pass, 18 calls a block at hidden 3584
    # (GLM's 14 at 2048)
    assert got["pallas_custom_calls"] == 3 * 5 + 18 * 4
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    if more:
        # 16.13 = 95.5% (PR 60; temporaries 7.02 GB)
        assert got["total_gb"] > 0.85 * 16.9
    else:
        # 14.24 = 84.3% (PR 60; temporaries 5.13 GB): what the cell's `why`
        # quotes
        assert 0.80 * 16.9 < got["total_gb"] < 0.85 * 16.9


# The full-width Nemotron-3-Nano-30B-A3B train step
# (examples/lm/nemotron_3_nano_30b_a3b_*: published layers 0-8, MEMEM*EME; 8
# of 128 ungated experts held, an eighth of the table's and the untied head's
# rows) as `train --bf16 --remat <the solver header's flags>` builds it at
# sequences of 8,192, for one abstract v5e chip: the compiler's memory
# accounting that fixed the cell's batch
# (benchmark/cells/nemotron_h.e8of128.pack8k.json), at one sequence and at
# the two chosen.
_NEMOTRON_STEP = _OURO_STEP.replace(
    "batch, seq, deeper = 1, 8192, {deeper}",
    "batch, seq, deeper = 1 + {deeper}, 8192, 0").replace(
    "ouro_2_6b_solver", "nemotron_3_nano_30b_a3b_solver").replace(
    'depth = sum(l.type == "ATTENTION" for l in net_param.layers) // 4',
    'depth = sum(l.type in ("ATTENTION", "SSD_SCAN", "MOE") '
    'for l in net_param.layers)')
assert _NEMOTRON_STEP.count("nemotron_3_nano_30b_a3b") == 1 \
    and "ouro_2" not in _NEMOTRON_STEP


@pytest.mark.slow
@pytest.mark.parametrize("more", [0, 1])
def test_nemotron_full_width_step_fits_one_v5e_at_one_and_two_sequences(more):
    """At TWO sequences of 8,192 (the cell's batch) the step with one
    checkpoint a layer and one around the head is under 85% of the 16.9 GB
    the compiler allows (PR 22's sizing rule); one sequence is recorded
    beside it. The four Mamba-2 layers' recurrences are the scan's Pallas
    kernels with EIGHT groups of B / C, a group a program (Mosaic compiles
    the grouped index maps for the v5e here); the attention layer's flash
    kernels run at 32 / 2 heads of 128 token-major with no positions; each
    MOE layer's held rows of UNGATED experts run in chunks of 8,192 under
    one loop a pass."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _NEMOTRON_STEP.format(repo=REPO, deeper=more)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)            # the accounting, for whoever sizes the next cut
    # nine layers; the trained parameters and the four selection biases
    assert got["depth"] == 9 and got["parameters"] == 666_962_944 + 512
    # embed, head, final norm; a norm a layer; a Mamba-2 mixer 8 (in, conv
    # w + b, A_log + dt_bias, D, out-norm, out), the attention one 4, a
    # sparse one 6 (router w + bias, TWO stacks, shared up + down)
    assert got["leaves"] == 3 + 9 + 4 * 8 + 4 + 4 * 6
    assert got["segments"] == 9 + 1
    rows = 8192 * 6 * (1 + more)
    assert got["routes"] == [
        "attention=pallas_flash (fwd 1024x1024 36/64, bwd 1024x1024 36/64; "
        "block_q x block_k, live/visited programs a "
        "head; operands token-major (B,S,HxD)); 2 kv heads repeated x16; no "
        "positions",
        f"grouped_matmul=ragged_dot; held rows: chunks of 8192 of {rows}; "
        f"act=relu2; ungated",
        "ssd_scan=pallas (Q 256, 32 chunks, 8 heads a program, 2 a lane "
        "block, one C B^T grid a program, f32 states in VMEM, passes 0.47 / "
        "0.47 of six a product); groups=8"]
    # weights + two moments, 12 bytes a parameter
    assert abs(got["argument_gb"] - 12e-9 * got["parameters"]) < 0.01
    assert got["total_gb"] < 0.85 * 16.9


# The LRN kernels at the CNN cells' norm layers (AlexNet's two at batch 512,
# GoogLeNet's two at 128: batch-minor) and at GoogLeNet's published batch 32
# (channel-minor), forward and backward, through Mosaic; then a stand-in for
# the layers around a norm layer — conv -> ReLU -> LRN -> max-pool -> conv,
# value_and_grad — in which the kernels' operand orientation has to be the
# one the compiler keeps its neighbours in: no copy, pad, slice or transpose
# of the LRN operand's size in the entry computation (four copies each until
# PR 33, and a pad and a slice at GoogLeNet's norm2).
_LRN_BOUNDARY = r"""
import json, math, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["POSEIDON_FORCE_PALLAS"] = "1"      # lower as for the TPU
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from jax import lax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("SKIP:", e)
    sys.exit(3)
from poseidon_tpu.config import set_perf_policy
from poseidon_tpu.ops import nn as NN, pallas_kernels as PK
set_perf_policy()
sh = SingleDeviceSharding(topo.devices[0])
S = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sh)
calls = lambda text: text.count('custom_call_target="tpu_custom_call"')
kernels = {{}}
for n, c, side in ((512, 96, 55), (512, 256, 27), (128, 64, 56),
                   (128, 192, 56), (32, 64, 56), (32, 192, 56)):
    x = S(n, c, side, side)
    fwd = jax.jit(lambda x: PK.lrn_fused(x, 5, 1e-4, 0.75, 1.0,
                                         interpret=False))
    bwd = jax.jit(lambda x, g: PK.lrn_fused_bwd(x, g, 5, 1e-4, 0.75, 1.0,
                                                interpret=False))
    kernels["%dx%dx%d" % (n, c, side * side)] = [
        PK.lrn_route(side * side, c, n, 2)[1],
        calls(fwd.lower(x).compile().as_text()),
        calls(bwd.lower(x, x).compile().as_text())]

def conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))

def standin(n, cin, side, c, k, stride, pad, cout):
    def loss(w1, w2, x):
        h = jnp.maximum(conv(x, w1, stride, pad), 0)
        h = PK.maybe_lrn_fused(h, 5, 1e-4, 0.75)
        h = NN.max_pool(h, (3, 3), (2, 2), (0, 0))
        return jnp.sum(conv(h, w2, 1, 1).astype(jnp.float32) ** 2)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        S(c, cin, k, k), S(cout, c, 3, 3), S(n, cin, side, side)
    ).compile().as_text()
    out = (side + 2 * pad - k) // stride + 1
    lines = text.splitlines()
    entry = lines[next(i for i, l in enumerate(lines)
                       if l.startswith("ENTRY ")):]
    moved = []
    for l in entry:
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* "
                     r"(copy|pad|slice|transpose)\(", l)
        if m and math.prod(map(int, m.group(1).split(","))) \
                >= n * c * out * out:
            moved.append(m.group(2) + " " + m.group(1))
    return {{"pallas_custom_calls": calls(text), "moved": moved}}

print("RESULT " + json.dumps({{"kernels": kernels, "standin": {{
    "alexnet_norm1": standin(512, 3, 227, 96, 11, 4, 0, 256),
    "googlenet_norm1": standin(128, 3, 112, 64, 1, 2, 0, 192),
    "googlenet_norm2": standin(128, 64, 56, 192, 3, 1, 1, 128)}}}}))
"""


def test_lrn_kernels_meet_their_neighbours_layout_for_v5e():
    """Mosaic takes the LRN kernels at the cells' geometries in the
    orientation the rule gives them, and around a norm layer the compiled
    text moves no array of the operand's size."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _LRN_BOUNDARY.format(repo=REPO)],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)
    assert got["kernels"] == {
        "512x96x3025": ["batch-minor HWxCxN, block 10x96x512", 1, 1],
        "512x256x729": ["batch-minor HWxCxN, block 4x256x512", 1, 1],
        "128x64x3136": ["batch-minor HWxCxN, block 64x64x128", 1, 1],
        "128x192x3136": ["batch-minor HWxCxN, block 21x192x128", 1, 1],
        "32x64x3136": ["channel-minor HWxNxC, block 256x32x64", 1, 1],
        "32x192x3136": ["channel-minor HWxNxC, block 84x32x192", 1, 1]}
    for name, standin in got["standin"].items():
        # LRN forward and backward, and since PR 35 the pool's backward
        assert standin == {"pallas_custom_calls": 3, "moved": []}, name


# An ATTENTION layer between its projections at two cells' geometries —
# ouro.loop4.pack8k's (one sequence of 8,192, 16 heads of 128) and
# trinity.e16of128.pack8k's window layers (32 query / 4 key-value heads of
# 128, W 2048) — x -> q, k, v projections -> rope_attention -> out
# projection, gradients of everything, plain and under a checkpoint's
# replay: the kernels take (B, S, H·Dh) where the projections leave it
# (``flash_operand_form``), so the entry computation holds no copy or
# transpose of q's (or o's) size — four a layer until PR 47, the head merge
# forward and the three gradients' in the backward — and no array of that
# size with a head's width as its minor axis at all (under the (8, 128)
# tiling (B, S, H, Dh) is another layout than (B, S, H·Dh)), with exactly
# two Pallas calls, three with the replay.
_ATTENTION_BOUNDARY = r"""
import json, math, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["POSEIDON_FORCE_PALLAS"] = "1"      # lower as for the TPU
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("SKIP:", e)
    sys.exit(3)
from poseidon_tpu.config import set_perf_policy
from poseidon_tpu.models.transformer import _dense, rope_attention
from poseidon_tpu.ops import pallas_kernels as PK
set_perf_policy()
sh = SingleDeviceSharding(topo.devices[0])
S = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sh)

def standin(b, s, h, g, d, window, replay):
    def layer(x, wq, wk, wv, wo):
        q, k, v = _dense(x, wq), _dense(x, wk), _dense(x, wv)
        return _dense(rope_attention(q, k, v, h, 1e4, g, window=window), wo)
    f = jax.checkpoint(layer) if replay else layer
    loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        S(b, s, 2048), S(h * d, 2048), S(g * d, 2048), S(g * d, 2048),
        S(2048, h * d)).compile().as_text()
    lines = text.splitlines()
    entry = lines[next(i for i, l in enumerate(lines)
                       if l.startswith("ENTRY ")):]
    moved, four_axes = [], []
    for l in entry:
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w\-]+)\(", l)
        if not m:
            continue
        dims = [int(n) for n in m.group(1).split(",")]
        if math.prod(dims) < b * s * h * d:
            continue
        if m.group(2) in ("copy", "transpose"):
            moved.append(m.group(2) + " " + m.group(1))
        if dims[-1] == d:
            four_axes.append(m.group(2) + " " + m.group(1))
    return {{"pallas_custom_calls":
             text.count('custom_call_target="tpu_custom_call"'),
             "moved": moved, "four_axes": four_axes}}

print("RESULT " + json.dumps({{
    "form": [PK.flash_operand_form(8192, 128)[1],
             PK.flash_operand_form(8192, 192, 128)[1]],
    "ouro": standin(1, 8192, 16, 16, 128, 0, False),
    "ouro_replay": standin(1, 8192, 16, 16, 128, 0, True),
    "trinity_window": standin(1, 8192, 32, 4, 128, 2048, False),
    "trinity_window_replay": standin(1, 8192, 32, 4, 128, 2048, True)}}))
"""


def test_flash_kernels_meet_the_projections_layout_for_v5e():
    """Between the projections' matmuls and the flash kernels the compiled
    text moves no array of q's size, forward or backward, and a layer is
    two Pallas calls (three with a checkpoint's replay)."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _ATTENTION_BOUNDARY.format(repo=REPO)],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)
    assert got.pop("form") == [
        "operands token-major (B,S,HxD)",
        "operands head-major (Dh 192, not lane-aligned)"]
    for name, standin in got.items():
        assert standin == {
            "pallas_custom_calls": 3 if name.endswith("replay") else 2,
            "moved": [], "four_axes": []}, name


# The max-pool backward kernel (PR 35) at the thirteen MAX geometries the two
# CNN cells hold (batch-minor at 512 / 128 images a chip; the route leaves
# the 7 x 7 one on select-and-scatter, its block is too small) and two of
# GoogLeNet's at its published 32 (channel-minor), through Mosaic with the
# block the rule gives; then stand-ins for the layers around a pool — conv ->
# ReLU -> LRN -> pool -> conv (AlexNet's pool1; GoogLeNet's ceil-mode pool1)
# and concat -> pool 3x3 s1 p1 -> 1x1 conv beside a second reader of the
# concat (an inception module's pool branch), value_and_grad — in which the
# entry computation holds no copy, pad, convert or transpose of the pool
# operand's size, no f32 array of it, and no select-and-scatter (with the
# `sas` arm: an f32 copy or `pad_convert_fusion` of the input, the scatter
# and a `reduce-precision_convert_fusion` behind it at every pool).
_POOL_BOUNDARY = r"""
import json, math, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["POSEIDON_FORCE_PALLAS"] = "1"      # lower as for the TPU
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from jax import lax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("SKIP:", e)
    sys.exit(3)
from poseidon_tpu.config import set_perf_policy
from poseidon_tpu.ops import nn as NN, pallas_kernels as PK
set_perf_policy()
sh = SingleDeviceSharding(topo.devices[0])
S = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sh)
calls = lambda text: text.count('custom_call_target="tpu_custom_call"')
S2, S1 = ((3, 3), (2, 2), (0, 0)), ((3, 3), (1, 1), (1, 1))
kernels = {{}}
for n, c, side, geom in (
        (512, 96, 55, S2), (512, 256, 27, S2), (512, 256, 13, S2),
        (128, 64, 112, S2), (128, 192, 56, S2), (128, 480, 28, S2),
        (128, 832, 14, S2), (128, 192, 28, S1), (128, 256, 28, S1),
        (128, 480, 14, S1), (128, 512, 14, S1), (128, 528, 14, S1),
        (128, 832, 7, S1), (32, 64, 112, S2), (32, 192, 28, S1)):
    out = NN.pool_out_size(side, geom[0][0], geom[1][0], geom[2][0])
    text = jax.jit(lambda x, g: PK.maxpool_bwd(x, g, *geom, interpret=False)
                   ).lower(S(n, c, side, side), S(n, c, out, out)
                           ).compile().as_text()
    kernels["%dx%dx%d s%d" % (n, c, side, geom[1][0])] = [
        NN.pool_bwd_route(geom[0], geom[1], geom[2], "max",
                          (n, c, side, side), 2)[1], calls(text)]

def conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))

def boundary(text, count):
    # entry-level instructions that move or widen an array of the pool
    # operand's size, and what is left of select-and-scatter
    lines = text.splitlines()
    entry = lines[next(i for i, l in enumerate(lines)
                       if l.startswith("ENTRY ")):]
    moved = []
    for l in entry:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]+)\]\S* "
                     r"(copy|pad|convert|transpose|fusion)\(", l)
        if not m or math.prod(map(int, m.group(3).split(","))) < count:
            continue
        op = m.group(4)
        if op == "fusion":
            # a fusion that only converts, pads or copies says so in its name
            if not re.search(r"(^|_)(copy|pad|convert|transpose)(_|$)",
                             m.group(1).split(".")[0]):
                continue
        moved.append("%s %s %s[%s]" % (op, m.group(1), m.group(2), m.group(3)))
    # f32 arrays of the operand's size that the entry computation holds
    # in memory (inside a fusion f32 is the VPU's arithmetic, not an array)
    wide = set()
    for l in entry:
        if " = " in l:
            result = re.split(r" [\w-]+\(", l.split(" = ", 1)[1], 1)[0]
            wide.update(w for w in re.findall(r"f32\[[\d,]+\]", result)
                        if math.prod(map(int, w[4:-1].split(","))) == count)
    wide = sorted(wide)
    return {{"pallas_custom_calls": calls(text), "moved": moved,
            "f32_of_operand_size": wide,
            "select_and_scatter": text.count(" select-and-scatter(")}}

def alexnet_standin(n, cin, side, c, k, stride, pad, cout):
    def loss(w1, w2, x):
        h = jnp.maximum(conv(x, w1, stride, pad), 0)
        h = PK.maybe_lrn_fused(h, 5, 1e-4, 0.75)
        h = NN.max_pool(h, (3, 3), (2, 2), (0, 0))
        return jnp.sum(conv(h, w2, 1, 1).astype(jnp.float32) ** 2)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        S(c, cin, k, k), S(cout, c, 3, 3), S(n, cin, side, side)
    ).compile().as_text()
    out = (side + 2 * pad - k) // stride + 1
    return boundary(text, n * c * out * out)

def inception_standin(n, c1, c2, side, cout):
    def loss(w0, w1, w2, w3, x):
        a = jnp.maximum(conv(x, w0, 1, 0), 0)
        b = jnp.maximum(conv(x, w1, 1, 1), 0)
        h = jnp.concatenate([a, b], axis=1)
        p = jnp.maximum(conv(NN.max_pool(h, (3, 3), (1, 1), (1, 1)), w2, 1, 0), 0)
        q = jnp.maximum(conv(h, w3, 1, 0), 0)
        return jnp.sum(jnp.concatenate([p, q], axis=1).astype(jnp.float32) ** 2)
    c = c1 + c2
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        S(c1, c, 1, 1), S(c2, c, 3, 3), S(cout, c, 1, 1), S(cout, c, 1, 1),
        S(n, c, side, side)).compile().as_text()
    return boundary(text, n * c * side * side)

print("RESULT " + json.dumps({{"kernels": kernels, "standin": {{
    "alexnet_pool1": alexnet_standin(512, 3, 227, 96, 11, 4, 0, 256),
    "googlenet_pool1_ceil": alexnet_standin(128, 3, 224, 64, 7, 2, 3, 192),
    "inception_3a_pool": inception_standin(128, 64, 128, 28, 32),
    "inception_4e_pool": inception_standin(128, 256, 272, 14, 128)}}}}))
"""


def test_maxpool_bwd_kernel_meets_its_neighbours_layout_for_v5e():
    """Mosaic takes the max-pool backward kernel at the cells' geometries
    with the block the rule gives, and around a MAX pool the compiled text
    moves or widens no array of the operand's size and keeps no
    select-and-scatter."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _POOL_BOUNDARY.format(repo=REPO)],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)
    bm, cm = "batch-minor HxWxCxN, block ", "channel-minor HxWxNxC, block "
    assert got["kernels"] == {
        "512x96x55 s2": [bm + "14x55x16x128", 1],
        "512x256x27 s2": [bm + "28x27x16x128", 1],
        "512x256x13 s2": [bm + "14x13x16x128", 1],
        "128x64x112 s2": [bm + "6x112x16x128", 1],
        "128x192x56 s2": [bm + "14x56x16x128", 1],
        "128x480x28 s2": [bm + "28x28x16x128", 1],
        "128x832x14 s2": [bm + "14x14x16x128", 1],
        "128x192x28 s1": [bm + "14x28x16x128", 1],
        "128x256x28 s1": [bm + "14x28x16x128", 1],
        "128x480x14 s1": [bm + "14x14x16x128", 1],
        "128x512x14 s1": [bm + "14x14x16x128", 1],
        "128x528x14 s1": [bm + "14x14x16x128", 1],
        # Mosaic takes it; the route keeps XLA's op there and says why
        "128x832x7 s1": ["a dx block of 196 KB is all per-program overhead",
                         1],
        "32x64x112 s2": [cm + "6x112x16x64", 1],
        "32x192x28 s1": [cm + "10x28x16x192", 1]}
    for name, standin in got["standin"].items():
        calls = 1 if name.startswith("inception") else 3   # + LRN fwd, bwd
        assert standin == {
            "pallas_custom_calls": calls, "moved": [],
            "f32_of_operand_size": [], "select_and_scatter": 0}, name




# AlexNet's train step as `train --bf16` builds it at the benchmark's batch,
# for ONE abstract v5e chip: with nobody to all-reduce with, the step builder
# packs nothing (PR 26) — no arena scope, no buffer-length array or constant,
# no collective left in the compiled program.
_CNN_STEP = r"""
import json, os, re, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["POSEIDON_FORCE_PALLAS"] = "1"      # lower as for the TPU
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
jax.config.update("jax_enable_compilation_cache", False)
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("SKIP:", e)
    sys.exit(3)
from poseidon_tpu.config import resolve_conv_layout, set_perf_policy
from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.parallel import (CommConfig, build_train_step,
                                   init_train_state)
from poseidon_tpu.proto.messages import SolverParameter
set_perf_policy()
batch = 512
# the plan `--conv_layout auto` resolves to on the chip (channels-last since
# PR 55); this process's backend is the CPU
net = Net(zoo.alexnet(with_accuracy=False), "TRAIN",
          source_shapes=zoo.alexnet_shapes(batch),
          conv_layout=resolve_conv_layout("auto", "tpu"))
sp = SolverParameter(base_lr=0.01, lr_policy="step", stepsize=100000,
                     gamma=0.1, momentum=0.9, weight_decay=0.0005)
mesh = Mesh(np.array(topo.devices[:{chips}]), ("data",))
comm = CommConfig()
ts = build_train_step(net, sp, mesh, comm, donate=True, donate_batch=True)
rep = NamedSharding(mesh, P())
shaped = lambda t, sh: jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), t)
params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
state = jax.eval_shape(lambda p: init_train_state(p, comm, {chips}), params)
g = batch * {chips}
b = {{"data": jax.ShapeDtypeStruct((g, 3, 227, 227), jnp.float32,
                                  sharding=ts.batch_sharding),
     "label": jax.ShapeDtypeStruct((g,), jnp.int32,
                                   sharding=ts.batch_sharding)}}
compiled = ts.lowerable.lower(
    shaped(params, rep), shaped(state, rep), b,
    jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)).compile()
ma, text = compiled.memory_analysis(), compiled.as_text()
from poseidon_tpu.runtime.hlo_comm import gradient_all_reduce_census
n = net.param_count()
print("RESULT " + json.dumps({{
    "parameters": n, "arena": ts.arena is not None,
    "update_route": ts.update_route,
    "arena_op_names": sorted(set(re.findall(r"arena_[a-z]+", text))),
    "optimizer_update_ops": text.count("optimizer_update"),
    "buffer_length_arrays": text.count("f32[%d]" % n),
    "all_reduces": len(re.findall(r" all-reduce(-start)?\(", text)),
    "gradient_all_reduces": gradient_all_reduce_census(text),
    "grad_sync_bucket_ops": text.count("grad_sync_bucket"),
    "bucket_buffers": text.count("f32[1000000]"),
    "pallas_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
    "conv_layout": net.layout_plan,
    "lrn_operand_copies": len(re.findall(
        r"\[512,(?:96,3025|256,729|96,55,55|256,27,27|3025,96|729,256"
        r"|55,55,96|27,27,256)\]\S* copy\(", text)),
    "select_and_scatter": text.count(" select-and-scatter("),
    "temp_gb": ma.temp_size_in_bytes / 1e9}}))
"""


@pytest.mark.slow
def test_alexnet_one_chip_step_has_no_arena_for_one_v5e():
    """The one-chip CNN step the benchmark's cells run: the update is
    there (``optimizer_update`` op names), the arena is not — no
    ``arena_*`` op name, no array of the flat buffer's length, no
    collective — and the LRN and max-pool backward kernels are Pallas,
    with no relayout copy at their boundary and no select-and-scatter."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _CNN_STEP.format(repo=REPO, chips=1)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)
    assert got["parameters"] == 60_965_224
    assert got["conv_layout"]["resolved"] == "NHWC"
    assert got["conv_layout"]["boundaries"] == "pool5->fc6"
    assert got["arena"] is False and got["update_route"] == "leaf"
    assert got["arena_op_names"] == []
    assert got["optimizer_update_ops"] >= 16     # one fusion a leaf at least
    assert got["buffer_length_arrays"] == 0
    assert got["all_reduces"] == 0
    # norm1, norm2: fwd and bwd; pool1, pool2, pool5: bwd (PR 35)
    assert got["pallas_custom_calls"] == 7
    assert got["select_and_scatter"] == 0
    # the kernels take their operands as the compiler holds them (nine
    # copies until PR 33; two, pool2's select-and-scatter's own, until the
    # pools' backward took the LRN kernels' orientation in PR 35)
    assert got["lrn_operand_copies"] == 0
    assert got["temp_gb"] < 3.0                  # 3.42 with the arena


@pytest.mark.slow
def test_alexnet_four_chip_step_packs_nothing_for_four_v5e():
    """The four-chip step of ``alexnet.dp4.resident`` since PR 59: each of
    the 16 gradient leaves is summed where backward makes it, so the
    compiled program holds no ``arena_*`` / ``grad_sync_bucket`` op name,
    no 4 MB bucket buffer (1,491 mentions of ``f32[1000000]`` before) and
    at most one gradient all-reduce a leaf (the compiler merges 15 of them
    and keeps fc6's 151 MB apart: 2, none asynchronous, where the 61
    bucket all-reduces were; PERF.md section 6, PR 59), under less
    temporary memory than the bucketed step's 2.73 GB."""
    import json
    r = subprocess.run(
        [sys.executable, "-c", _CNN_STEP.format(repo=REPO, chips=4)],
        capture_output=True, text=True, timeout=1500, cwd=REPO)
    if r.returncode == 3 or "lockfile" in (r.stdout + r.stderr):
        pytest.skip(f"libtpu AOT unavailable: "
                    f"{(r.stdout + r.stderr).strip()[-200:]}")
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    got = json.loads(next(l for l in r.stdout.splitlines()
                          if l.startswith("RESULT "))[7:])
    print(got)
    assert got["arena"] is False and got["update_route"] == "leaf"
    assert got["arena_op_names"] == [] and got["grad_sync_bucket_ops"] == 0
    assert got["bucket_buffers"] == 0 and got["buffer_length_arrays"] == 0
    total, n_async = got["gradient_all_reduces"]
    assert 1 <= total <= 16 and n_async <= total
    assert got["pallas_custom_calls"] == 7
    assert got["temp_gb"] < 2.5
