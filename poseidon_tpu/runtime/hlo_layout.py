"""Layout evidence: transposes extracted from the compiled train step.

The net-level NHWC plan (core/net.py) claims a transpose-free spatial
chain: activations enter channels-last once, every conv/pool/LRN/concat
runs natively, and layout converts back to canonical NCHW only at genuine
boundaries (FC flatten, blob export). This module makes that claim
compiler-verifiable without hardware — the analog of ``hlo_comm.py`` for
the layout plan: parse the program text, count the layout transposes, and
let ``scripts/aot_tpu_check.py`` and the layout tests pin the number (the
round-3 shim lost 1.9x precisely because the per-op boundary transposes
did NOT cancel; a count pins the regression).

Two program levels are parsed by the same entry points:

- **StableHLO** (``jit(f).lower(...).as_text()``): the compiler's INPUT —
  exactly the transposes OUR program asks for, on any backend. This is
  the tier-1 CPU assertion level.
- **Optimized HLO** (``...compile().as_text()``): what the backend kept.
  On the TPU compiler (AOT for an abstract v5e via
  ``jax.experimental.topologies`` — no hardware needed) this is the
  acceptance-grade count; the CPU backend is NOT meaningful here (its
  conv canonicalization materializes its own transposes for every conv
  gradient, ~77 for NCHW AlexNet, independent of our layout plan).

What counts as a LAYOUT transpose: a rank-4 transpose whose permutation
reorders non-degenerate (size > 1) dims. Rank-5+ transposes are excluded —
they are grouped-conv weight-gradient internals jax emits under either
layout — as are degenerate permutations (e.g. (N,1,1,C) -> (N,C,1,1)),
which every backend folds to a bitcast.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional

# optimized HLO:  %t.1 = f32[4,6,6,256]{3,2,1,0} transpose(%p), dimensions={0,3,1,2}
_HLO_RE = re.compile(
    r"= [a-z0-9]+\[([\d,]*)\](?:\{[\d,]*\})? transpose\(")
_HLO_DIMS_RE = re.compile(r"dimensions=\{([\d,]+)\}")
# StableHLO:  %1 = stablehlo.transpose %0, dims = [0, 3, 1, 2] :
#             (tensor<4x6x6x256xf32>) -> tensor<4x256x6x6xf32>
_SHLO_RE = re.compile(
    r"stablehlo\.transpose .*?dims = \[([\d, ]+)\].*?-> tensor<([^>]+)>")


@dataclass
class TransposeOp:
    out_shape: tuple
    perm: tuple

    @property
    def rank(self) -> int:
        return len(self.perm)

    @property
    def nontrivial(self) -> bool:
        """Reorders dims that actually have extent (> 1)?"""
        # operand dim d has size out_shape[i] where perm[i] == d
        op_size = {d: self.out_shape[i] for i, d in enumerate(self.perm)}
        nondeg = [d for d in self.perm if op_size.get(d, 1) > 1]
        return nondeg != sorted(nondeg)

    @property
    def is_layout(self) -> bool:
        return self.rank == 4 and self.nontrivial


def parse_transposes(text: str) -> List[TransposeOp]:
    """Every transpose op in an optimized-HLO or StableHLO module text."""
    out: List[TransposeOp] = []
    for line in text.splitlines():
        m = _HLO_RE.search(line)
        if m is not None:
            dims = tuple(int(x) for x in m.group(1).split(",") if x)
            d = _HLO_DIMS_RE.search(line)
            perm = (tuple(int(x) for x in d.group(1).split(","))
                    if d else tuple(range(len(dims))))
            out.append(TransposeOp(out_shape=dims, perm=perm))
            continue
        s = _SHLO_RE.search(line)
        if s is not None:
            perm = tuple(int(x) for x in s.group(1).replace(" ", "").split(","))
            shape = tuple(int(x) for x in s.group(2).split("x")[:-1])
            out.append(TransposeOp(out_shape=shape, perm=perm))
    return out


def count_layout_transposes(text: str) -> int:
    """Rank-4, non-degenerate transposes — the activation layout changes."""
    return sum(1 for t in parse_transposes(text) if t.is_layout)


def layout_report(text: str) -> Dict:
    """The evidence row: total / layout / per-shape detail."""
    ops = parse_transposes(text)
    layout_ops = [t for t in ops if t.is_layout]
    return {
        "transposes_total": len(ops),
        "layout_transposes": len(layout_ops),
        "layout_transpose_shapes": [
            {"shape": list(t.out_shape), "perm": list(t.perm)}
            for t in layout_ops],
    }


def build_plain_step(net, sp, input_layout: Optional[str] = None):
    """A mesh-free optimizer step (grad + solver update) over ``net`` —
    jit-compilable on any backend including an abstract AOT topology,
    with none of the shard_map machinery that would distract the count.
    Returns ``step(params, solver_state, batch, rng)``."""
    import jax

    from ..parallel.trainer import param_mults
    from ..solvers.updates import make_update_fn

    if input_layout is None:
        input_layout = net.conv_layout
    update_fn = make_update_fn(sp, param_mults(net))

    def step(params, state, batch, rng):
        def loss_fn(p):
            return net.apply(p, batch, train=True, rng=rng,
                             input_layout=input_layout).loss

        grads = jax.grad(loss_fn)(params)
        return update_fn(params, grads, state)

    return step


def step_avals(net, per_dev_batch: int, image: int,
               input_layout: Optional[str] = None, sharding=None):
    """(params, state, batch, rng) abstract values for ``build_plain_step``
    — enough to ``jit(...).lower(...)`` without materializing anything.
    ``sharding`` (e.g. a NamedSharding over an abstract v5e mesh) tags
    every aval for AOT compilation against a TPU topology."""
    import jax
    import jax.numpy as jnp

    from ..solvers.updates import init_state

    if input_layout is None:
        input_layout = net.conv_layout

    def aval(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pshape = jax.eval_shape(net.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree_util.tree_map(lambda x: aval(x.shape), pshape)
    state = jax.tree_util.tree_map(
        lambda x: aval(x.shape, x.dtype),
        jax.eval_shape(lambda: init_state(params)))
    data = ((per_dev_batch, image, image, 3) if input_layout == "NHWC"
            else (per_dev_batch, 3, image, image))
    batch = {"data": aval(data), "label": aval((per_dev_batch,), jnp.int32)}
    rng = aval((2,), jnp.uint32)
    return params, state, batch, rng


def net_transpose_report(net, sp=None, per_dev_batch: int = 4,
                         image: int = 227, optimized: bool = False,
                         sharding=None) -> Dict:
    """Lower (and optionally backend-compile) one full optimizer step of
    ``net`` and report its layout-transpose counts. With ``sharding`` from
    an abstract TPU topology and ``optimized=True`` this is the
    no-hardware v5e acceptance check; without it, the StableHLO-level
    count on the local backend (the tier-1 test)."""
    import jax

    from ..proto.messages import SolverParameter

    sp = sp or SolverParameter(base_lr=0.01, lr_policy="fixed", momentum=0.9)
    step = build_plain_step(net, sp)
    avals = step_avals(net, per_dev_batch, image, sharding=sharding)
    lowered = jax.jit(step).lower(*avals)
    text = lowered.compile().as_text() if optimized else lowered.as_text()
    rep = layout_report(text)
    rep["level"] = "optimized_hlo" if optimized else "stablehlo"
    rep["conv_layout"] = net.conv_layout
    return rep
