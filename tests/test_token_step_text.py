"""The token configurations that share the flash kernels, the grouped
matmuls and the routers (OLMoE, Ouro, ZAYA1; since PR 41 Trinity-Mini and
Kimi-Linear, whose hashes are PR 43's own: it changed their held arm; since
PR 48 Olmo-Hybrid, which ADAPTED ops/kda.py and the KDA layers and left
Kimi's text as it was, to the byte; since PR 56 GLM-4.7-Flash, which added
``rotary_shared`` beside them and left all six texts as they were; since PR
60 Xing4.0, which added YaRN's frequencies to ``rope_tables`` /
``_rope_lanes`` and a stream option to ``zoo.glm_flash``, left all seven as
they were and took SmallThinker's and Granite's on its parent, so that all
nine token configurations the benchmark had are held; since PR 64
Nemotron-3-Nano, which ADAPTED ops/ssd*.py, models/moe.expert_ffn and the
MOE / SSD_SCAN / RMS_NORM layers and left all ten as they were) trace
to the
program they traced to before Trinity's window, sigmoid router and per-head
norm, and before Kimi-Linear's two head widths in the flash kernels,
arrived: the gradient's whole jaxpr at a small size, as lowered for the TPU
(``POSEIDON_FORCE_PALLAS=1``: the Pallas arm, its kernel bodies, grids and
index maps are in the text), hashed. The hashes were taken on the parent of
PR 36 and are equal on it and on the change; at the cells' own sizes the
lowered StableHLO of the three steps was compared on both trees in the
sandbox (abstract v5e): equal outside the Mosaic payloads, and the payloads
equal once their debug locations (pallas_kernels.py's line numbers) are
stripped (CHANGES.md, PR 36).

A PR that means to change what these configurations trace to (PR 32 did,
and cost ``ouro.loop4.pack8k`` 9 s of set-up; PR 47 did, for the four whose
heads are 128 wide, and left Kimi-Linear's alone; PR 61 did, for all ten:
the flash backward became one sweep, so every ATTENTION layer's backward is
one ``flash_bwd`` call where it was ``flash_bwd_dq`` and ``flash_bwd_dkv``,
and all ten hashes below were PR 61's own; PR 62 did, for Granite alone:
the scan kernels' products, the nine other hashes stayed) updates the
hashes and says so; one that does not has tripped over a shared path."""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.proto.messages import load_net_from_string

N, S = 1, 2048      # 2 x 2 tiles of 1024: the causal clamp is in the maps
BUILD = {
    "olmoe": lambda: zoo.olmoe(
        batch=N, n_layers=1, hidden=128, heads=1, experts=8, top_k=2,
        expert_width=32, vocab=128),
    "ouro": lambda: zoo.ouro(
        batch=N, n_layers=1, passes=2, hidden=128, heads=1, ffn_width=64,
        vocab=128),
    "zaya": lambda: zoo.zaya1(
        batch=N, n_layers=2, hidden=64, heads=2, kv_heads=1, head_dim=128,
        experts=8, held=4, expert_width=32, router_hidden=16, vocab=128),
    # a dense layer and a MoE layer behind a window and a global layer
    "trinity": lambda: zoo.trinity_mini(
        batch=N, n_layers=2, dense_layers=1, hidden=128, heads=2, kv_heads=1,
        head_dim=128, window=1024, first_global=1, dense_width=64,
        experts=16, top_k=2, held=2, expert_width=32, shared_width=32,
        vocab=128),
    # KDA + dense; KDA, KDA, MLA (192 / 128) with a MoE each
    "kimi": lambda: zoo.kimi_linear(
        batch=N, n_layers=4, hidden=64, heads=2, head_dim=16, kv_rank=32,
        nope_dim=128, rope_dim=64, v_dim=128, dense_width=64, experts=16,
        top_k=2, held=2, expert_width=32, shared_width=32, vocab=128),
    # one period, Gated DeltaNet x3 (one decay a head, heads of 16 / 32 in
    # the kernels' padded lanes) + full attention, 1 of 2 heads held
    "olmo_hybrid": lambda: zoo.olmo_hybrid(
        batch=N, n_layers=4, hidden=128, heads=2, heads_held=1,
        key_head_dim=16, value_head_dim=32, attn_head_dim=128, ffn_width=64,
        vocab=128),
    # the dense layer, a sparse layer and the prediction module: three
    # latent-attention blocks of 256 / 256 (token-major), the shared key
    # part rotated once and joined along the lanes (``rotary_shared``)
    "glm": lambda: zoo.glm_flash(
        batch=N, n_layers=2, held=2, vocab=128, hidden=64, heads=2,
        q_rank=32, kv_rank=32, nope_dim=192, rope_dim=64, v_dim=256,
        dense_width=64, experts=16, top_k=2, expert_width=32,
        shared_width=32),
    # a window layer and a global layer, 2 query heads on 1 key-value head,
    # the softmax router on the pre-attention state, 4 of 8 experts held
    "smallthinker": lambda: zoo.smallthinker(
        batch=N, n_layers=2, hidden=128, heads=2, kv_heads=1, head_dim=128,
        window=1024, global_every=2, first_global=1, experts=8, top_k=2,
        held=4, expert_width=32, vocab=128),
    # a Mamba-2 layer and an attention layer (no positions, a scale of its
    # own), the four multipliers, the tied table
    "granite": lambda: zoo.granite_hybrid(
        batch=N, layers=2, vocab_rows=128, hidden=128, attn_every=2,
        attn_at=1, heads=2, kv_heads=1, ssd_heads=8, ssd_head_dim=64,
        state=128, ffn_width=64),
    # the dense layer and a sparse layer on a stream of four: two
    # latent-attention blocks of 192 / 128 (head-major), the shared key part
    # and q's tails rotated by YaRN's angles after the head split
    "xing": lambda: zoo.xing4(
        batch=N, n_layers=2, dense_layers=1, held=2, vocab=128, mtp=0,
        hidden=128, heads=2, q_rank=32, kv_rank=32, dense_width=64,
        experts=16, top_k=2, expert_width=32, shared_width=32),
    # one layer of each letter: a Mamba-2 layer with two groups of B / C (a
    # group a program of eight heads), a sparse layer of ungated experts, 2
    # of 16 held, and an attention layer of 2 / 1 heads of 128
    "nemotron": lambda: zoo.nemotron_h(
        batch=N, pattern="ME*", held=2, vocab_rows=128, hidden=128,
        ssd_heads=16, ssd_head_dim=64, state=128, groups=2, heads=2,
        kv_heads=1, head_dim=128, experts=16, top_k=2, expert_width=32,
        shared_width=32),
}
PARENT = {       # sha256 of the text, its length, its pallas_call equations
    # PR 61's own, all ten: it meant to change them (the docstring's rule).
    # Every ATTENTION layer's backward is ONE ``flash_bwd`` equation, the
    # dK/dV sweep with the head's dQ rows resident, where the parent traced
    # ``flash_bwd_dq`` and ``flash_bwd_dkv``: one pallas_call equation fewer
    # an attention application (OLMoE 3 -> 2, Ouro 6 -> 4, Olmo-Hybrid 9 ->
    # 8, GLM 9 -> 6, ...), and the ``(B·H, S, 1)`` reshapes of lse and delta
    # the dQ sweep read are gone. What each text moves with besides is what
    # PRs 43-60 wrote here: the operand form (PR 47: 128-wide heads reach
    # the kernels token-major, Kimi's and Xing4's 192 / 128 and Granite's 64
    # head-major), ops/kda.py and the KDA layers (Kimi, Olmo-Hybrid),
    # ``rotary_shared`` and zoo.glm_flash (GLM, Xing4), ops/ssd*.py
    # (Granite), the window arm and the routers (Trinity, SmallThinker)
    "olmoe": ("ad18f4e82814adf5f6058aaae4a664f60c51ef3ad3e97f16008defb"
              "553bd647c", 84417, 2),
    "ouro": ("947ada320c05c63e413ddff896969b9dd219d1e6f072bbd63cef1259"
             "7a8fad5e", 135371, 4),
    "zaya": ("19e15fa91a0c60aad395ce83fbf745d29635f4450a194cd31b6f0203"
             "4aa06015", 189673, 4),
    "trinity": ("bbb01cf1b159d5de251b82acd660eb0f97b3606c4117afd38769c"
                "4865e36632a", 150089, 4),
    "kimi": ("5fad9c60eaa421edf5f59a086cc9ff4327cb461fcb9808cdbb804f11"
             "dbf5e2e2", 998335, 2),
    "olmo_hybrid": ("7ac299981440efeb95547d8224b924765b207a945ac3a500d"
                    "dd36bec3b17f29c", 819329, 8),
    "glm": ("6aa53f1db20fdc5e855994c029543c277197fe0d3b57d831ab5aa644d"
            "ff93cad", 239053, 6),
    "smallthinker": ("d58e40d50e4d06270599ea9f3bdc827adc3cae30713298d2"
                     "cae9efddb7655618", 156693, 4),
    # PR 62: ``ops/ssd_pallas._mm`` chooses a product's passes by its
    # operands' types. This f32 trace keeps the parent's 119 products, all
    # at HIGHEST; B and C are read once a program where they were read
    # twice, and the selector ``pick`` is cast to x's type
    "granite": ("9ffa4295b12cccfe41d7a1add11f495f042cc4ae7a9765022e629"
                "b815a735f73", 194050, 4),
    "xing": ("b8f6a68b149c539aa29cd8abac18c9e8238b00bb3152b9b0c653cb1d"
             "e7012d7f", 386577, 4),
    # PR 64's own (a new configuration; the ten above are its parent's, to
    # the byte: the groups of B / C, the ungated expert and the grouped
    # norm are arguments whose defaults trace to what was there)
    "nemotron": ("926313e00323c7d16deb1ab32b3f505579109572098ef4424362c31d"
                 "32b058bf", 205773, 4),
}


def traced(name: str) -> str:
    net = Net(load_net_from_string(zoo.to_prototxt(BUILD[name]())), "TRAIN",
              source_shapes={"tokens": (N, S), "targets": (N, S)})
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((N, S), jnp.int32)
             for k in ("tokens", "targets")}
    text = str(jax.make_jaxpr(jax.grad(
        lambda p, b: net.apply(p, b, train=True).loss))(params, batch))
    return re.sub(r"0x[0-9a-f]+", "0x", text)      # function addresses


# PR 49: the forward kernels' results carry ``checkpoint_name`` tags (one
# ``name`` equation a result that is used: o in the forward, o and the row
# statistics / chunk states in the backward's rule). Outside a checkpoint
# that asks for them they are the identity, so the hashes above are taken
# with the tags out, and what the tags add is counted.
# PR 57: so do a gated unit's products (``core/layers.FFN_SAVED``), one
# ``name`` equation a product, in the forward: three a SILU_GATE (the two
# products it reads and the one that reads it; Olmo-Hybrid's linear mixer's
# z and gated output projection two).
NAMES = {"olmoe": 2, "ouro": 4 + 3 * 2, "zaya": 4, "trinity": 4 + 3 * 2,
         "kimi": 8 + 3 * 4, "olmo_hybrid": 8 + 18, "glm": 6 + 3 * 3,
         "smallthinker": 4, "granite": 8, "xing": 4 + 3 * 2, "nemotron": 4}


@pytest.mark.parametrize("name", sorted(BUILD))
def test_token_configuration_traces_to_the_parent_s_program(name,
                                                            monkeypatch):
    from poseidon_tpu.core import layers
    from poseidon_tpu.ops import (kda, kda_pallas, pallas_kernels, ssd,
                                  ssd_pallas)
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    assert traced(name).count("= name[") == NAMES[name]
    tagged = [m for m in (ssd, ssd_pallas) if hasattr(m, "checkpoint_name")]
    for module in [kda, kda_pallas, pallas_kernels, layers] + tagged:
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    text = traced(name)
    sha, chars, calls = PARENT[name]
    assert "name=flash_fwd" in text and re.search(r"name=flash_bwd\b", text)
    assert "flash_bwd_dq" not in text and "flash_bwd_dkv" not in text
    assert (text.count("pallas_call["), len(text)) == (calls, chars)
    assert hashlib.sha256(text.encode()).hexdigest() == sha


def test_a_window_does_change_the_text(monkeypatch):
    """The hash sees what it should: the same OLMoE net with a window on
    its ATTENTION layer traces to another program."""
    monkeypatch.setenv("POSEIDON_FORCE_PALLAS", "1")
    net = BUILD["olmoe"]()
    for layer in net.layers:
        if layer.type == "ATTENTION":
            layer.attention_param.window = 512
    monkeypatch.setitem(BUILD, "olmoe_window", lambda: net)
    assert traced("olmoe_window") != traced("olmoe")


def _control_flow(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("cond", "while", "scan"):
            yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _control_flow(sub)


@pytest.mark.parametrize("held, per_layer", [(2, 1), (8, 0)])
def test_a_held_share_under_half_traces_one_loop_a_moe_layer(
        held, per_layer, monkeypatch):
    """A small Trinity-Mini net (1 dense + 2 MoE layers, top-4 of 16, the
    chunk rule's floor lowered to a row tile): with an eighth of the
    experts held each MOE layer's forward holds ONE ``while``
    (``expert_ffn``'s chunks of the sorted rows, as many trips as the live
    rows need) and the gradient one more (the backward makes the same
    trips); with half held two chunks hold every row, the rows run as
    straight-line code and no loop is traced, which is why ZAYA1's hash
    above stands. No conditional either way."""
    from poseidon_tpu.models import moe
    monkeypatch.setattr(moe, "_CHUNK_FLOOR", 128)
    n, s = 1, 128
    net = Net(load_net_from_string(zoo.to_prototxt(zoo.trinity_mini(
        batch=n, n_layers=3, dense_layers=1, hidden=64, heads=4, kv_heads=2,
        head_dim=16, window=32, first_global=2, dense_width=96, experts=16,
        top_k=4, held=held, expert_width=32, shared_width=32, vocab=128))),
        "TRAIN", source_shapes={"tokens": (n, s), "targets": (n, s)})
    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((n, s), jnp.int32)
             for k in ("tokens", "targets")}

    def loss(p, b):
        return net.apply(p, b, train=True).loss

    forward = list(_control_flow(jax.make_jaxpr(loss)(params, batch).jaxpr))
    assert forward == ["while"] * (2 * per_layer)
    both = list(_control_flow(
        jax.make_jaxpr(jax.grad(loss))(params, batch).jaxpr))
    assert both == ["while"] * (4 * per_layer)
