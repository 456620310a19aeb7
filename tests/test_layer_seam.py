"""What a layer says of itself (``Layer.kernel_route`` / ``stats_sections`` /
``display_counters`` / ``forward_flops``) reaches ``Net`` and the Engine
unchanged: for every net of the zoo, at the cut sizes its own test file
uses, the routes, the ``[kernel_route]`` lines, the stats sections, the held
counters and the cost table equal ``layer_seam_golden.json``, which was
captured from the accessors of the commit BEFORE the facts moved into the
layer classes (``Net._plan_kernel_routes``'s per-type chain,
``Net.expert_share`` / ``recurrent_state`` / ``held_row_ladders`` with the
Engine's arithmetic, ``attribution.layer_cost_table``; PR 58). PR 61
re-took the ATTENTION routes' notes for the TPU (``fwd ..., bwd ...`` where
they read ``fwd ..., dq ..., dkv ...``: the flash backward became one sweep,
and ``attention_route`` names the backward that runs); nothing else in the
file moved."""

import json
import os

import jax.numpy as jnp
import pytest

from poseidon_tpu.config import policy_scope
from poseidon_tpu.core.net import Net
from poseidon_tpu.models import moe, zoo
from poseidon_tpu.proto.messages import load_net_from_string

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "layer_seam_golden.json")

# builder, its arguments (the SIZES of tests/test_<net>.py), (N, S) on the
# CPU mesh
TOKEN_NETS = {
    "olmoe": ("olmoe", dict(
        n_layers=2, hidden=64, heads=4, experts=8, top_k=2, expert_width=32,
        vocab=512), (2, 64)),
    "ouro": ("ouro", dict(
        n_layers=2, passes=4, hidden=64, heads=4, ffn_width=96, vocab=512,
        entropy_weight=0.1), (2, 64)),
    "zaya1": ("zaya1", dict(
        n_layers=2, hidden=64, heads=4, kv_heads=2, head_dim=16, experts=8,
        expert_width=32, router_hidden=16, vocab=128, held=4), (2, 64)),
    "trinity_mini": ("trinity_mini", dict(
        n_layers=5, dense_layers=1, hidden=64, heads=8, kv_heads=2,
        head_dim=16, window=16, first_global=4, dense_width=96, experts=16,
        top_k=4, expert_width=32, shared_width=32, vocab=128, held=8),
        (2, 64)),
    "smallthinker": ("smallthinker", dict(
        n_layers=4, hidden=64, heads=8, kv_heads=2, head_dim=16, window=16,
        experts=16, top_k=3, expert_width=32, vocab=128, held=4), (2, 64)),
    "kimi_linear": ("kimi_linear", dict(
        n_layers=5, dense_layers=1, hidden=64, heads=4, head_dim=16,
        kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16, dense_width=96,
        experts=16, top_k=4, expert_width=32, shared_width=32, vocab=128,
        held=8), (2, 128)),
    "glm_flash": ("glm_flash", dict(
        n_layers=3, hidden=64, heads=4, q_rank=24, kv_rank=32, nope_dim=12,
        rope_dim=4, v_dim=16, dense_width=96, experts=16, top_k=4,
        expert_width=32, shared_width=32, vocab=128, held=8), (2, 48)),
    "olmo_hybrid": ("olmo_hybrid", dict(
        n_layers=4, hidden=64, heads=4, key_head_dim=12, value_head_dim=24,
        attn_head_dim=16, ffn_width=96, vocab=128), (2, 128)),
    "granite_hybrid": ("granite_hybrid", dict(
        layers=10, vocab_rows=128, hidden=64, heads=8, kv_heads=2,
        ssd_heads=16, ssd_head_dim=8, state=16, ffn_width=96), (2, 48)),
}
# 2 of 16 experts held, 4 sequences, and models/moe's chunk floor (8,192
# rows) lowered to a row tile as tests/test_trinity.py does (``build``), so
# that these few rows run under the held arm's loop as the cells' do
for _name in ("trinity_mini", "smallthinker", "kimi_linear", "glm_flash"):
    _builder, _kw, (_, _s) = TOKEN_NETS[_name]
    TOKEN_NETS[f"{_name}.held2"] = (_builder, dict(_kw, held=2), (4, _s))

CNNS = {
    "alexnet": (zoo.alexnet, lambda: zoo.alexnet_shapes(16), "TRAIN"),
    "googlenet": (zoo.googlenet, lambda: zoo.googlenet_shapes(16), "TRAIN"),
    "lenet": (zoo.lenet, lambda: zoo.lenet_shapes(64), "TRAIN"),
    "lenet.test": (zoo.lenet, lambda: zoo.lenet_shapes(64), "TEST"),
}

# "cpu": f32 on the CPU mesh. "tpu": bf16, every route as it is lowered for
# the chip (POSEIDON_FORCE_PALLAS), one sequence of 256 tokens.
CASES = [(name, backend) for name in list(CNNS) + list(TOKEN_NETS)
         for backend in ("cpu", "tpu")]

COST_COLUMNS = ("flops", "bytes", "act_bytes", "intensity")


def build(name, backend, monkeypatch):
    """The net of one case, built under the case's backend and policy."""
    monkeypatch.delenv("POSEIDON_POOL_BWD", raising=False)
    monkeypatch.delenv("POSEIDON_PALLAS_LRN", raising=False)
    if backend == "tpu":
        monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    else:
        monkeypatch.delenv("POSEIDON_FORCE_PALLAS", raising=False)
    if name.endswith(".held2"):
        monkeypatch.setattr(moe, "_CHUNK_FLOOR", 128)
    dtype = jnp.bfloat16 if backend == "tpu" else jnp.float32
    with policy_scope(compute_dtype=dtype):
        if name in CNNS:
            make, shapes, phase = CNNS[name]
            return Net(make(), phase, source_shapes=shapes())
        builder, kw, (n, s) = TOKEN_NETS[name]
        if backend == "tpu":
            n, s = 1, 256
        # through the text form: what a user's prototxt goes through
        text = zoo.to_prototxt(getattr(zoo, builder)(batch=n, **kw))
        return Net(load_net_from_string(text), "TRAIN",
                   source_shapes={"tokens": (n, s), "targets": (n, s)})


def even_share(name):
    kw = TOKEN_NETS.get(name, (None, {}))[1]
    return kw.get("held", 0) / kw.get("experts", 1)


def observe(net, name, backend, logged):
    """Everything the seam carries for one net, as JSON holds it."""
    dtype = jnp.bfloat16 if backend == "tpu" else jnp.float32
    with policy_scope(compute_dtype=dtype):
        sections = net.layer_facts()
    seen = {
        "kernel_routes": net.kernel_routes,
        "kernel_route_lines": [line for line in logged.splitlines()
                               if line.startswith("[kernel_route]")],
        "sections": sections,
        "held_counts": {
            top: [counts(share) for share in (0.0, even_share(name), 1.0)]
            for top, counts in net.display_counters().items()},
    }
    if backend == "cpu" and not name.endswith(".held2"):
        # shapes only: one copy a net, a row a layer
        seen["cost_table"] = {
            layer: [row[k] for k in COST_COLUMNS]
            for layer, row in net.cost_table().items()}
    return json.loads(json.dumps(seen))


@pytest.mark.parametrize("name,backend", CASES)
def test_what_the_layers_state_equals_the_golden(name, backend, monkeypatch,
                                                 capsys):
    with open(GOLDEN) as f:
        want = json.load(f)[f"{name}/{backend}"]
    capsys.readouterr()
    net = build(name, backend, monkeypatch)
    got = observe(net, name, backend, capsys.readouterr().out)
    assert sorted(got) == sorted(want)
    for key in want:
        assert got[key] == want[key], key
    # the case looks at something: every net names a route or is LeNet's
    # TEST phase, whose pools have no backward
    assert got["kernel_routes"] or name == "lenet.test"
    if name.endswith(".held2"):
        assert got["held_counts"]
