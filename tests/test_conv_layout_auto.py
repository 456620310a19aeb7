"""``--conv_layout auto`` as a TPU start resolves it (PR 55): the channels-last
plan. The suite runs on the CPU, where ``auto`` is NCHW, so the cases that
need the TPU's row steer ``jax.default_backend`` from the test (the program
has no option for it) and keep the Pallas kernels on their CPU arm. What is
held here: which plan a net takes and what it holds, that a token net plans
nothing and lowers to one text under every plan, that a run's log and
``stats.yaml`` say which plan it took and why, and that two optimizer steps of
the CNN configurations (cut) under ``auto``-as-TPU land where explicit ``nchw``
lands, through the Engine's LMDB path and through device-resident batches as
the benchmark's resident feed dispatches them."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from poseidon_tpu import config
from poseidon_tpu.core.net import Net
from poseidon_tpu.models import zoo
from poseidon_tpu.proto.messages import load_net_from_string
from test_caffe_reference import _googlenet_cut, _he_scaled

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLASSES = 1000


@pytest.fixture
def as_tpu(monkeypatch):
    """``auto`` resolves as on a TPU start; the kernels stay on the CPU's
    arms (``_interpret_default`` would otherwise compile Mosaic here)."""
    from poseidon_tpu.ops import pallas_kernels as PK
    monkeypatch.setattr(PK, "_interpret_default", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture
def ambient_policy():
    """``train`` writes its flags into the process's numeric policy."""
    pol = config.policy()
    with config.policy_scope(
            compute_dtype=pol.compute_dtype, conv_s2d=pol.conv_s2d,
            conv_layout=pol.conv_layout, conv_strategy=pol.conv_strategy):
        yield


# --------------------------------------------------------------------------- #
# the benchmark's two CNN configurations, cut to what the CPU can afford
# --------------------------------------------------------------------------- #

# model: (configuration folder, crop, record side, batch, cut)
CONFIGS = {"alexnet": ("bvlc_alexnet", 67, 72, 2, None),
           "googlenet": ("bvlc_googlenet", 224, 232, 1, _googlenet_cut)}


def _net_text(model: str, data: dict = None) -> str:
    folder, crop, _, batch, cut = CONFIGS[model]
    with open(os.path.join(ROOT, "benchmark", "configs", folder,
                           "train_val.prototxt")) as f:
        text = f.read()
    if cut:
        text = cut(text)
    text = re.sub(r"num_output: (\d+)", lambda m: "num_output: %d" % (
        int(m[1]) if int(m[1]) == CLASSES else max(4, int(m[1]) // 8)), text)
    text = re.sub(r"crop_size: \d+", f"crop_size: {crop}", text)
    text = re.sub(r"batch_size: \d+", f"batch_size: {batch}", text)
    if data:
        text = text.replace("examples/imagenet/ilsvrc12_train_lmdb",
                            data["train"])
        text = text.replace("examples/imagenet/ilsvrc12_mean.binaryproto",
                            data["mean"])
    return text


def _job(model: str, tmp_path, max_iter: int = 2) -> str:
    """A seeded LMDB (the benchmark's own writer), the cut net and a solver
    for ``max_iter`` steps; returns the solver's path."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_datagen", os.path.join(ROOT, "benchmark", "datagen.py"))
    datagen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(datagen)
    _, _, side, batch, _ = CONFIGS[model]
    data = datagen.build_lmdb(str(tmp_path / "data"), records=4 * batch,
                              side=side, channels=3, classes=CLASSES, seed=5)
    (tmp_path / "net.prototxt").write_text(_net_text(model, data))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path / "net.prototxt"}"\nbase_lr: 0.001\n'
        f'lr_policy: "fixed"\nmomentum: 0.9\nweight_decay: 0.0005\n'
        f'display: 1\nmax_iter: {max_iter}\nsnapshot: 0\n'
        f'snapshot_after_train: false\nsnapshot_prefix: "snap/x"\n'
        f'random_seed: 5\n')
    return str(tmp_path / "solver.prototxt")


def _train(argv, monkeypatch, resident_steps: int = 0, reseed: bool = False):
    """``train`` through the CLI, handing back the Engine it built. With
    ``resident_steps`` the Engine's own loop is skipped and its step is
    dispatched on batches made on the device instead, as the benchmark's
    resident feed does (canonical shapes, the step's batch sharding)."""
    from poseidon_tpu.runtime import cli
    from poseidon_tpu.runtime.engine import Engine
    built = []
    real = cli._engine_from_args

    def build(args):
        built.append(real(args))
        if reseed:
            # AlexNet's gaussian(0.01) fillers let two steps move fc8
            # alone, whatever the layers below it do
            built[-1].params = jax.device_put(
                _he_scaled(jax.tree.map(jnp.asarray, built[-1].params), 5),
                built[-1].train_step.replicated)
        return built[-1]

    monkeypatch.setattr(cli, "_engine_from_args", build)
    if resident_steps:
        def resident(eng, *a, **k):
            # the net's shapes are a device's: the batch spans the mesh
            shapes = {k: (v[0] * eng.n_dev,) + tuple(v[1:]) for k, v in (
                (k, eng.train_net.blob_shapes[k])
                for k in eng.train_net.input_names)}
            for it in range(resident_steps):
                key = jax.random.PRNGKey(7 + it)
                batch = jax.device_put({
                    name: (jax.random.randint(key, shape, 0, CLASSES,
                                              jnp.int32) if len(shape) == 1
                           else 64.0 * jax.random.normal(key, shape,
                                                         jnp.float32))
                    for name, shape in shapes.items()},
                    eng.train_step.batch_sharding)
                eng.params, eng.state, m = eng._dispatch_train_step(
                    batch, jax.random.fold_in(eng.rng, it))
                assert np.isfinite(float(m["loss"]))
            return {}
        monkeypatch.setattr(Engine, "train", resident)
    assert cli.main(argv) == 0
    return built[-1]


# --------------------------------------------------------------------------- #
# (a) which plan a net takes, and what it holds
# --------------------------------------------------------------------------- #

def test_alexnet_under_auto_as_tpu_holds_exactly_the_fc_boundary(as_tpu,
                                                                 capsys):
    """The benchmark's AlexNet (cut image and widths) under ``auto`` on a
    TPU start: conv / pool / LRN and the ReLUs between them run
    channels-last, the classifier canonical, and the one conversion the
    plan holds is pool5's flatten into fc6."""
    net = Net(load_net_from_string(_net_text("alexnet")), "TRAIN",
              source_shapes={"data": (2, 3, 67, 67), "label": (2,)},
              conv_layout="auto")
    assert net.conv_layout == "NHWC"
    by_type = {}
    for layer in net.layers:
        by_type.setdefault(layer.TYPE, set()).add(layer.run_layout)
    assert by_type["CONVOLUTION"] == by_type["POOLING"] == by_type["LRN"] \
        == {"NHWC"}
    assert by_type["INNER_PRODUCT"] == by_type["DROPOUT"] == {"NCHW"}
    assert net.input_layouts == {"data": "NHWC", "label": "NCHW"}
    plan = net.layout_plan
    assert plan == {
        "asked": "auto", "resolved": "NHWC", "why": "tpu",
        "layers_with_4d_blob": 16, "channels_last_layers": 15,
        "boundary_conversions": 1, "boundaries": "pool5->fc6",
        "inputs_channels_last": 1}
    assert ("[conv_layout] auto -> NHWC (tpu): 15 of 16 layers with a 4-D "
            "blob run channels-last, 1 boundary conversion(s): pool5->fc6"
            ) in capsys.readouterr().out


@pytest.mark.parametrize("asked,resolved,line", [
    ("auto", "NCHW", "[conv_layout] auto -> NCHW (cpu): 16 layers with a "
                     "4-D blob run canonical, no boundary"),
    ("nchw", "NCHW", "[conv_layout] NCHW (asked for): 16 layers"),
    ("nhwc", "NHWC", "[conv_layout] NHWC (asked for): 15 of 16 layers"),
])
def test_explicit_plans_and_the_cpu_row_are_as_before(asked, resolved, line,
                                                      capsys):
    net = Net(load_net_from_string(_net_text("alexnet")), "TRAIN",
              source_shapes={"data": (2, 3, 67, 67), "label": (2,)},
              conv_layout=asked)
    assert net.conv_layout == net.layout_plan["resolved"] == resolved
    assert net.layout_plan["why"] == ("cpu" if asked == "auto"
                                      else "asked for")
    assert net.layout_plan["channels_last_layers"] == \
        (15 if resolved == "NHWC" else 0)
    assert line in capsys.readouterr().out


def test_googlenet_plan_holds_its_three_heads(as_tpu):
    """Whole GoogLeNet under the TPU's row: every conv, pool, LRN, ReLU and
    CONCAT channels-last; the boundaries are the three classifiers' (the
    two auxiliary heads' flatten, loss3's dropout over the 1 x 1 pool)."""
    with open(os.path.join(ROOT, "benchmark", "configs", "bvlc_googlenet",
                           "train_val.prototxt")) as f:
        net = Net(load_net_from_string(f.read()), "TRAIN",
                  source_shapes={"data": (1, 3, 224, 224), "label": (1,)},
                  conv_layout="auto")
    plan = net.layout_plan
    assert (plan["resolved"], plan["why"]) == ("NHWC", "tpu")
    assert plan["boundaries"] == ("loss1/conv->loss1/fc, loss2/conv->"
                                  "loss2/fc, pool5/7x7_s1->pool5/drop_7x7_s1")
    assert plan["channels_last_layers"] == plan["layers_with_4d_blob"] - 4
    assert {l.run_layout for l in net.layers
            if l.TYPE in ("CONVOLUTION", "POOLING", "LRN", "CONCAT")} \
        == {"NHWC"}


def test_a_token_net_plans_nothing_and_lowers_to_one_text(as_tpu, capsys):
    """OLMoE's block (cut): no blob has four dimensions, so the plan
    assigns nothing under any choice, says so, and the gradient lowers to
    the same text under ``NCHW``, ``NHWC`` and ``auto`` on a TPU start —
    why the token cells cannot move with the TPU's row."""
    n, s = 1, 64
    text = zoo.to_prototxt(zoo.olmoe(
        batch=n, n_layers=1, hidden=64, heads=4, experts=8, top_k=2,
        expert_width=32, vocab=128))
    lowered = {}
    for layout in ("NCHW", "NHWC", "auto"):
        net = Net(load_net_from_string(text), "TRAIN",
                  source_shapes={"tokens": (n, s), "targets": (n, s)},
                  conv_layout=layout)
        assert net.layout_plan["layers_with_4d_blob"] == 0
        assert net.layout_plan["channels_last_layers"] == 0
        assert net.layout_plan["boundary_conversions"] == 0
        assert {l.run_layout for l in net.layers} == {"NCHW"}
        params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
        batch = {k: jax.ShapeDtypeStruct((n, s), jnp.int32)
                 for k in ("tokens", "targets")}
        lowered[layout] = jax.jit(jax.grad(
            lambda p, b: net.apply(p, b, train=True).loss)).lower(
                params, batch).as_text()
    assert "[conv_layout] auto -> NHWC (tpu): no 4-D blob, nothing to plan" \
        in capsys.readouterr().out
    assert "dot_general" in lowered["NCHW"]
    assert lowered["NCHW"] == lowered["NHWC"] == lowered["auto"]


# --------------------------------------------------------------------------- #
# (b) the run says which plan it took
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("flags,tpu,want", [
    # the cells' own argv shape: `train --solver=... --bf16`, no layout
    ((), True, ("auto", "NHWC", "tpu", 15, 1)),
    ((), False, ("auto", "NCHW", "cpu", 0, 0)),
    (("--conv_layout", "nchw"), True, ("NCHW", "NCHW", "asked for", 0, 0)),
])
def test_a_run_logs_and_publishes_its_plan(flags, tpu, want, tmp_path,
                                           monkeypatch, request, capsys,
                                           ambient_policy):
    from poseidon_tpu.runtime.metrics import read_stats_yaml
    if tpu:
        request.getfixturevalue("as_tpu")
    solver = _job("alexnet", tmp_path, max_iter=1)
    eng = _train(["train", f"--solver={solver}",
                  f"--output_dir={tmp_path / 'out'}", "--bf16", *flags],
                 monkeypatch)
    asked, resolved, why, channels_last, boundaries = want
    assert eng.train_net.conv_layout == resolved
    section = read_stats_yaml(str(tmp_path / "out" / "stats.yaml"))[
        "conv_layout"]
    assert (section["asked"], section["resolved"], section["why"]) \
        == (asked, resolved, why)
    assert int(section["layers_with_4d_blob"]) == 16
    assert int(section["channels_last_layers"]) == channels_last
    assert int(section["boundary_conversions"]) == boundaries
    assert section["boundaries"] == ("pool5->fc6" if boundaries else "none")
    head = (f"[conv_layout] auto -> {resolved} ({why}): " if asked == "auto"
            else f"[conv_layout] {resolved} (asked for): ")
    assert head in capsys.readouterr().out


# --------------------------------------------------------------------------- #
# (c) two optimizer steps: auto as the TPU resolves it against explicit nchw
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("feed", ["lmdb", "resident"])
@pytest.mark.parametrize("model", sorted(CONFIGS))
def test_two_steps_under_auto_as_tpu_land_where_nchw_lands(
        model, feed, tmp_path, monkeypatch, request, ambient_policy):
    """The f32 policy, momentum and weight decay, two steps from He-scaled
    weights (at a rate that keeps the cut nets' second step the size of
    their first): parameters (canonical by construction) within the
    tolerance ``test_layout_parity.test_full_net_optimizer_step_parity``
    holds one step to. ``lmdb``: reader, mirror + crop + mean on the host, prefetcher,
    ``Engine.train``. ``resident``: the Engine's step dispatched on
    device-resident canonical batches."""
    solver = _job(model, tmp_path)
    params = {}
    for side, flags in (("nchw", ("--conv_layout", "nchw")), ("auto", ())):
        if side == "auto":
            request.getfixturevalue("as_tpu")
        eng = _train(["train", f"--solver={solver}",
                      f"--output_dir={tmp_path / side}", *flags],
                     monkeypatch, resident_steps=2 * (feed == "resident"),
                     reseed=True)
        assert eng.train_net.conv_layout == \
            {"nchw": "NCHW", "auto": "NHWC"}[side]
        if feed == "lmdb":
            assert eng.iteration() == 2
        params[side] = jax.tree.map(np.asarray, eng.params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(
            params["nchw"]), jax.tree.leaves(params["auto"])):
        assert np.all(np.isfinite(a)), path
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-6,
                                   err_msg=f"{model}/{feed}: {path}")
