"""SPMD sharding planner: named-axis mesh (data/fsdp/tp) + per-layer plan.

ROADMAP item 1. The flat ``("data",)`` mesh replicates every parameter and
psums every gradient; models and batch sizes one chip cannot hold are out
of reach. This module grows the mesh into a first-class named-axis layer
and PLANS the collective schedule per layer at step-build time — the
comm-characterization literature (arXiv:1810.11112) and the XLA-on-TPU
compilation story (arXiv:1810.09868) both locate the win in planning the
schedule rather than bolting sharding on afterward, and the checked-in
HLO contract gates (analysis/contracts.py, ``collective_schedule``
section) verify the planned census compiles as planned.

Axes (``config.MeshConfig``, ``--mesh dp2,fsdp2,tp1``):

- ``data``  — classic data parallelism: batch shards, replicated params.
- ``fsdp``  — batch shards PLUS a sharded parameter arena (the ZeRO
  trade): every arena bucket aligns to the fsdp size, gradients
  REDUCE-SCATTER over fsdp then all-reduce over data, the fused optimizer
  update touches only each device's 1/fsdp shard (multiplier segments
  arrive sharded too), and updated shards ALL-GATHER back. With
  ``sharded_state=True`` the gather moves to the step prologue and
  params + momentum LIVE sharded between steps — the 1/fsdp persistent
  param+grad+momentum footprint the AOT memory estimate records.
- ``tp``    — tensor parallelism for FC layers: column shards (output
  dim) by default, with the planner choosing row shards (input dim) and
  the activation resharding points for FC chains whose intermediate
  layers are elementwise-safe — the Megatron pairing, one psum instead
  of gather+regather. Conv/LRN/pool layers replicate over tp; SFB/TOPK/
  LOCAL layers opt out of tp entirely and keep their custom comm paths.
  (The LM family's attention tp lives in models/transformer.py's
  ``build_dp_tp_train_step`` — same axis vocabulary, same mesh shape.)

Gradient-sync numerics are HIERARCHICAL by construction — reduce-scatter
(or psum) over ``fsdp`` first, then psum over ``data`` on the shard — so
a sharded run and a replicated run on the same mesh reduce in the same
association order: LeNet final params are bitwise identical between the
``dp2,fsdp2`` sharded and replicated arms (tests/test_mesh_spmd.py). TP
runs agree to float-associativity tolerance (a sharded contraction
necessarily re-associates its reduction, and XLA blocks a (M/t, K)
matmul differently than the (M, K) one).

Named scopes label every collective with its mesh axis —
``grad_rs_bucket<i>`` (fsdp), ``grad_ar_bucket<i>`` (data),
``param_ag_bucket<i>`` / ``hist_ag_bucket<i>`` (fsdp),
``tp_fwd_<layer>`` / ``tp_dx_<layer>`` (tp) — so
runtime/attribution.py bills comm time per axis instead of lumping it
into the residual row.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field as dc_field
from typing import (Callable, Dict, List, NamedTuple, Optional, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import MeshConfig, matmul_precision, policy
from .mesh import SPMD_AXES, make_mesh
from .strategies import (CommConfig, CommContext, DENSE, DENSE_FUSED, LOCAL,
                         TOPK, WIRE_DTYPES, budget_topk_fraction, comm_salt,
                         topk_compress, wire_psum)

# layer types that may consume a tp-sharded activation unchanged (pure
# elementwise, no rng): the planner only keeps an activation sharded
# through these. Dropout is NOT safe — its mask layout is keyed by the
# rng stream, which must not depend on the tp shard.
TP_ELEMENTWISE_SAFE = frozenset({"RELU"})

COL = "column"   # weight (M, K) sharded over M; output feature shards
ROW = "row"      # weight (M, K) sharded over K; input arrives sharded


def named_mesh(cfg: MeshConfig, devices=None) -> Mesh:
    """The (data, fsdp, tp) mesh for a MeshConfig. Uses the first
    ``cfg.n_devices`` jax devices; fails loudly when fewer exist
    (mesh.make_mesh's contract)."""
    return make_mesh(num_devices=cfg.n_devices, axes=SPMD_AXES,
                     shape=(cfg.data, cfg.fsdp, cfg.tp), devices=devices)


def mesh_config_of(mesh: Mesh) -> MeshConfig:
    """Recover the MeshConfig from a named mesh (axis sizes; absent axes
    count 1) — the inverse of ``named_mesh`` for tools holding only the
    Mesh."""
    return MeshConfig(data=int(mesh.shape.get("data", 1)),
                      fsdp=int(mesh.shape.get("fsdp", 1)),
                      tp=int(mesh.shape.get("tp", 1)))


@dataclass(frozen=True)
class TPDecision:
    """One FC layer's tensor-parallel assignment."""
    mode: str            # COL | ROW
    gather: bool         # COL only: all-gather the output (the resharding
    #                      point) vs keep it sharded for a downstream ROW
    shard_dim: int       # weight dim carrying the tp shard (0=M, 1=K)


@dataclass(frozen=True)
class LeafPlan:
    """One parameter leaf's placement — every DENSE leaf gets one
    (planner contract, pinned by tests/test_mesh_spmd.py)."""
    placement: str       # "arena_fsdp" | "tp" | "replicated"
    spec: P              # shard_map PartitionSpec for the leaf


@dataclass
class ShardingPlan:
    """Per-layer PartitionSpec plan for one Net on one MeshConfig.

    Computed once at step-build time (pure Python over static shapes);
    the trainer consumes it through shard_map specs and the spmd device
    step; ``collective_schedule`` states the planned collective census
    the HLO contract gates diff against the lowered program."""

    mesh_cfg: MeshConfig
    shard_params: bool = True          # False = replicated control arm
    tp_layers: Dict[str, TPDecision] = dc_field(default_factory=dict)
    arena_layers: frozenset = frozenset()
    leaf_plan: Dict[Tuple[str, str], LeafPlan] = dc_field(
        default_factory=dict)
    # blobs that stay tp-sharded between a COL producer and a ROW consumer
    sharded_blobs: frozenset = frozenset()

    # ---------------------------------------------------------------- #
    @property
    def active(self) -> bool:
        return self.mesh_cfg.active

    @property
    def n_dp(self) -> int:
        """Distinct batch shards = data * fsdp (tp replicas share one)."""
        return self.mesh_cfg.data * self.mesh_cfg.fsdp

    def batch_spec(self, extra_lead: int = 0) -> P:
        """Batch dim sharded jointly over (data, fsdp); tp replicated."""
        return P(*([None] * extra_lead), ("data", "fsdp"))

    def param_spec(self, layer: str, pname: str) -> P:
        lp = self.leaf_plan.get((layer, pname))
        return lp.spec if lp is not None else P()

    # ---------------------------------------------------------------- #
    @classmethod
    def build(cls, net, mesh_cfg: MeshConfig,
              comm: Optional[CommConfig] = None,
              shard_params: bool = True,
              enable_tp: bool = True) -> "ShardingPlan":
        """Plan a Net: TP assignments for eligible FC layers, the fsdp
        arena cover for everything DENSE that stays canonical, and a
        placement for every DENSE leaf. ``shard_params=False`` /
        ``enable_tp=False`` build the replicated control arm on the SAME
        mesh — identical batch shards and reduction association, only the
        sharding mechanism removed (the A/B the parity tests pin)."""
        comm = comm or CommConfig()
        plan = cls(mesh_cfg=mesh_cfg, shard_params=shard_params)
        if mesh_cfg.fsdp > 1 and shard_params and not comm.param_arena:
            raise ValueError(
                "fsdp sharding rides the flat parameter arena "
                "(--param_arena true); an fsdp mesh with the arena off "
                "has nothing to shard")

        tp_layers: Dict[str, TPDecision] = {}
        sharded_blobs: set = set()
        if mesh_cfg.tp > 1 and enable_tp:
            tp_layers, sharded_blobs = cls._plan_tp(net, comm, mesh_cfg.tp)
        plan.tp_layers = tp_layers
        plan.sharded_blobs = frozenset(sharded_blobs)

        arena = {lname for lname in net.param_defs
                 if comm.strategy_for(lname) == DENSE
                 and lname not in tp_layers}
        plan.arena_layers = frozenset(arena) if comm.param_arena \
            else frozenset()

        leaf_plan: Dict[Tuple[str, str], LeafPlan] = {}
        for lname, defs in net.param_defs.items():
            for pdef in defs:
                if lname in tp_layers:
                    dec = tp_layers[lname]
                    if pdef.name == "w":
                        spec = (P("tp", None) if dec.shard_dim == 0
                                else P(None, "tp"))
                    elif pdef.name == "b" and dec.mode == COL:
                        spec = P("tp")
                    else:
                        spec = P()
                    leaf_plan[(lname, pdef.name)] = LeafPlan("tp", spec)
                elif lname in plan.arena_layers:
                    leaf_plan[(lname, pdef.name)] = LeafPlan(
                        "arena_fsdp"
                        if (mesh_cfg.fsdp > 1 and shard_params)
                        else "replicated", P())
                else:
                    # SFB/TOPK/LOCAL/DENSE_FUSED keep their custom comm
                    # paths: replicated storage, tp opt-out
                    leaf_plan[(lname, pdef.name)] = LeafPlan("replicated",
                                                             P())
        plan.leaf_plan = leaf_plan
        return plan

    @staticmethod
    def _plan_tp(net, comm: CommConfig, tp: int):
        """TP assignment walk. COLUMN by default (output dim M % tp == 0);
        a candidate whose bottom is fed — through TP-elementwise-safe
        layers only — by a COL candidate whose sharded path has no other
        consumers becomes ROW (K % tp == 0), and the COL producer keeps
        its output sharded (gather=False): the Megatron pairing, with the
        resharding point moved from the COL output to the ROW psum."""
        consumers: Dict[str, List] = {}
        writers: Dict[str, List[Tuple[int, object]]] = {}
        layer_index: Dict[str, int] = {}
        for idx, layer in enumerate(net.layers):
            layer_index[layer.name] = idx
            for b in layer.lp.bottom:
                consumers.setdefault(b, []).append(layer)
            for t in layer.lp.top:
                writers.setdefault(t, []).append((idx, layer))

        def producer_before(blob: str, idx: int):
            """Last writer of ``blob`` before layer ``idx`` — in-place
            chains reuse one blob name, so plain top->layer maps loop."""
            prev = None
            for widx, wlayer in writers.get(blob, ()):
                if widx >= idx:
                    break
                prev = wlayer
            return prev

        def eligible(layer) -> bool:
            if layer.TYPE != "INNER_PRODUCT":
                return False
            if comm.strategy_for(layer.name) != DENSE:
                return False        # SFB/TOPK/... opt out of tp
            if layer.name not in net.param_defs:
                return False        # shared-storage sharer: skip
            wdef = next((p for p in net.param_defs[layer.name]
                         if p.name == "w"), None)
            if wdef is None or len(wdef.shape) != 2:
                return False
            if any(layer.loss_weights(len(layer.lp.top))):
                return False        # a sharded top would mis-sum the loss
            return wdef.shape[0] % tp == 0

        decisions: Dict[str, TPDecision] = {}
        sharded_blobs: set = set()
        cands = [l for l in net.layers if eligible(l)]
        cand_names = {l.name for l in cands}
        for layer in cands:
            decisions[layer.name] = TPDecision(COL, True, 0)
        for layer in cands:
            # try ROW: walk the bottom back through safe elementwise layers
            bottom = layer.lp.bottom[0]
            idx = layer_index[layer.name]
            chain_blobs = [bottom]
            chain_layers = {layer.name}
            src = producer_before(bottom, idx)
            while src is not None and src.TYPE in TP_ELEMENTWISE_SAFE:
                chain_layers.add(src.name)
                idx = layer_index[src.name]
                bottom = src.lp.bottom[0]
                if bottom not in chain_blobs:
                    chain_blobs.append(bottom)
                src = producer_before(bottom, idx)
            if src is None or src.name not in cand_names or \
                    decisions[src.name] != TPDecision(COL, True, 0):
                continue
            wdef = next(p for p in net.param_defs[layer.name]
                        if p.name == "w")
            if wdef.shape[1] % tp:
                continue
            # every blob on the would-be-sharded path may feed only the
            # chain itself (plus the ROW consumer), and none may be a net
            # output (exports must stay canonical)
            chain_layers.add(src.name)
            ok = all(
                all(c.name in chain_layers for c in consumers.get(b, []))
                and b not in net.output_names
                for b in chain_blobs)
            if not ok:
                continue
            decisions[layer.name] = TPDecision(ROW, False, 1)
            decisions[src.name] = TPDecision(COL, False, 0)
            sharded_blobs.update(chain_blobs)
        return decisions, sharded_blobs

    # ---------------------------------------------------------------- #
    def collective_schedule(self, layout, net=None,
                            comm: Optional[CommConfig] = None,
                            min_elements: int = 256,
                            sharded_state: bool = False) -> Dict:
        """The PLANNED collective census of one train step under this
        plan — what the lowered program must carry, diffed in CI exactly
        like the arena's bucket count (analysis/contracts.py
        ``collective_schedule`` golden section). Payloads smaller than
        ``min_elements`` f32 elements sit below the census threshold
        (scalar metrics, tiny biases) and are excluded on both sides."""
        comm = comm or CommConfig()
        d, f = self.mesh_cfg.data, self.mesh_cfg.fsdp
        fsdp_on = f > 1 and self.shard_params
        n_buckets = layout.n_buckets if layout is not None else 0
        names: List[Dict] = []
        counts = {"all_reduce": 0, "reduce_scatter": 0, "all_gather": 0}

        def add(name, kind, axis, elems):
            if elems < min_elements:
                return
            names.append({"name": name, "kind": kind, "axis": axis,
                          "elems": int(elems)})
            counts[kind] += 1

        for i in range(n_buckets):
            lo, hi = (layout.bucket_ranges[i] if layout is not None
                      else (0, 0))
            if fsdp_on:
                # thresholded on the op's RESULT (the 1/fsdp shard) — the
                # same tensor the lowered-census regex sees; a full-bucket
                # threshold would disagree with the census on a small
                # tail bucket
                add(f"grad_rs_bucket{i}", "reduce_scatter", "fsdp",
                    (hi - lo) // f)
            elif f > 1:
                add(f"grad_rs_bucket{i}", "all_reduce", "fsdp", hi - lo)
            if d > 1:
                add(f"grad_ar_bucket{i}", "all_reduce", "data",
                    (hi - lo) // f if fsdp_on else hi - lo)
            if fsdp_on:
                # canonical-boundary steps gather params AND momentum
                # back; sharded-state steps gather params once, up front,
                # and momentum never crosses the wire
                add(f"param_ag_bucket{i}", "all_gather", "fsdp", hi - lo)
                if not sharded_state:
                    add(f"hist_ag_bucket{i}", "all_gather", "fsdp",
                        hi - lo)
        if net is not None:
            t = self.mesh_cfg.tp
            for lname, dec in self.tp_layers.items():
                layer = next(l for l in net.layers if l.name == lname)
                b_loc = net.blob_shapes[layer.lp.top[0]][0]
                wdef = next(p for p in net.param_defs[lname]
                            if p.name == "w")
                m, k = wdef.shape
                if dec.mode == COL and dec.gather:
                    add(f"tp_fwd_{lname}", "all_gather", "tp", b_loc * m)
                if dec.mode == COL:
                    add(f"tp_dx_{lname}", "all_reduce", "tp", b_loc * k)
                else:
                    add(f"tp_fwd_{lname}", "all_reduce", "tp", b_loc * m)
                for pdef in net.param_defs[lname]:
                    elems = (pdef.count // t
                             if pdef.name == "w" or dec.mode == COL
                             else pdef.count)
                    if f > 1:
                        add(f"grad_tp_{lname}_{pdef.name}_fsdp",
                            "all_reduce", "fsdp", elems)
                    if d > 1:
                        add(f"grad_tp_{lname}_{pdef.name}_data",
                            "all_reduce", "data", elems)
            # non-default strategies the step still emits collectives for
            # (the census must state EVERYTHING the plan schedules):
            # TOPK — one joint (data, fsdp) psum of the compressed-dense
            # gradient per leaf; DENSE_FUSED — hierarchical per-axis
            # psums; DENSE with the arena OFF — one in-backward joint tap
            # psum per leaf; SFB — the two tiled factor gathers + the
            # bias psum.
            for lname, defs in net.param_defs.items():
                strat = comm.strategy_for(lname)
                if lname in self.tp_layers or lname in self.arena_layers \
                        or strat == LOCAL:
                    continue
                layer = next(l for l in net.layers if l.name == lname)
                if strat == TOPK:
                    for pdef in defs:
                        add(f"grad_topk_{lname}_{pdef.name}",
                            "all_reduce", "data+fsdp", pdef.count)
                elif strat == DENSE_FUSED:
                    for pdef in defs:
                        if f > 1:
                            add(f"grad_fused_{lname}_{pdef.name}_fsdp",
                                "all_reduce", "fsdp", pdef.count)
                        if d > 1:
                            add(f"grad_fused_{lname}_{pdef.name}_data",
                                "all_reduce", "data", pdef.count)
                elif strat == DENSE:
                    # arena off: the in-backward sync tap's joint psum
                    for pdef in defs:
                        add(f"grad_tap_{lname}_{pdef.name}",
                            "all_reduce", "data+fsdp", pdef.count)
                else:   # SFB: backward gathers both factors, psums bias
                    b_glob = net.blob_shapes[layer.lp.top[0]][0] * \
                        self.n_dp
                    wdef = next(p for p in defs if p.name == "w")
                    m, k = wdef.shape
                    add(f"sfb_gfactor_{lname}", "all_gather",
                        "data+fsdp", b_glob * m)
                    add(f"sfb_xfactor_{lname}", "all_gather",
                        "data+fsdp", b_glob * k)
                    if any(p.name == "b" for p in defs):
                        add(f"sfb_bias_{lname}", "all_reduce",
                            "data+fsdp", m)
        return {
            "mesh": self.mesh_cfg.describe(),
            "shard_params": self.shard_params,
            "sharded_state": sharded_state,
            "min_elements": min_elements,
            "arena_buckets": n_buckets,
            "counts": counts,
            "collectives": names,
        }

    def describe(self) -> str:
        tp = {l: d.mode + ("" if d.gather or d.mode == ROW
                           else "+sharded-out")
              for l, d in self.tp_layers.items()}
        return (f"mesh {self.mesh_cfg.describe()}"
                f"{'' if self.shard_params else ' (replicated control)'}: "
                f"{len(self.arena_layers)} arena layer(s) over fsdp, "
                f"tp {tp or 'none'}")


# --------------------------------------------------------------------------- #
# fsdp shard geometry
# --------------------------------------------------------------------------- #

def fsdp_shard_ranges(layout, f: int) -> List[List[Tuple[int, int]]]:
    """Per-device list of [lo, hi) flat-buffer ranges: device d owns the
    d-th 1/f slice of every bucket. The union over devices is a DISJOINT
    cover of [0, padded_total) — the planner contract the unit tests
    pin."""
    out: List[List[Tuple[int, int]]] = [[] for _ in range(f)]
    for lo, hi in layout.bucket_ranges:
        s = (hi - lo) // f
        for dd in range(f):
            out[dd].append((lo + dd * s, lo + (dd + 1) * s))
    return out


def to_shard_major(flat: np.ndarray, layout, f: int) -> np.ndarray:
    """Canonical flat order -> shard-major order: row block d holds device
    d's per-bucket shard segments concatenated in bucket order. A
    P("fsdp") sharding over the result hands each device exactly its
    contiguous shard — the persistent layout of ``SpmdState.flat_w`` and
    of the sharded multiplier segments."""
    ranges = fsdp_shard_ranges(layout, f)
    return np.concatenate([flat[lo:hi] for dd in range(f)
                           for lo, hi in ranges[dd]])


def from_shard_major(sm: np.ndarray, layout, f: int) -> np.ndarray:
    """Inverse of ``to_shard_major``."""
    out = np.empty_like(sm)
    pos = 0
    ranges = fsdp_shard_ranges(layout, f)
    for dd in range(f):
        for lo, hi in ranges[dd]:
            out[lo:hi] = sm[pos:pos + (hi - lo)]
            pos += hi - lo
    return out


def _shard_mult_vectors(layout, sp, f: int):
    """(lr, decay) multiplier vectors in shard-major order (see
    ``to_shard_major``): shard_map's P("fsdp") slice hands device d its
    per-bucket segments directly."""
    lr, dec = layout.mult_vectors(sp.weight_decay)
    return to_shard_major(lr, layout, f), to_shard_major(dec, layout, f)


# --------------------------------------------------------------------------- #
# tp matmuls (the Megatron f/g operators as custom VJPs)
# --------------------------------------------------------------------------- #

def _dot(a, b, dims, accum=False):
    p = policy()
    kw = {"preferred_element_type": p.accum_dtype} if accum else {}
    return lax.dot_general(a.astype(p.compute_dtype),
                           b.astype(p.compute_dtype), dims,
                           precision=matmul_precision(), **kw)


@functools.lru_cache(maxsize=None)
def _tp_col_matmul(tp_axis: str, gather: bool, with_bias: bool,
                   layer: str):
    """Column-parallel FC: w_loc is (M/t, K); forward computes the local
    output shard and (optionally) all-gathers the feature dim — the
    planner's resharding point. Backward: the weight/bias grads are the
    exact local shard computations (no tp collective — each rank owns its
    rows), and dx sums the partial contractions over tp ranks (the
    Megatron ``f`` operator's backward all-reduce).

    The gathered output's cotangent is IDENTICAL on every tp rank
    (everything downstream of the gather is tp-replicated), so the
    gather's backward takes this rank's slice of ONE copy — a plain
    dynamic-slice, not the psum-scatter autodiff would emit, which would
    overcount every upstream gradient by a factor of tp."""

    def fwd_math(x2, w_loc, b_loc):
        y = _dot(x2, w_loc, (((1,), (1,)), ((), ())))
        if with_bias:
            y = y + b_loc.astype(y.dtype)
        if gather:
            with jax.named_scope(f"tp_fwd_{layer}"):
                y = lax.all_gather(y, tp_axis, axis=1, tiled=True)
        return y

    @jax.custom_vjp
    def fn(x2, w_loc, b_loc):
        return fwd_math(x2, w_loc, b_loc)

    def fwd(x2, w_loc, b_loc):
        return fwd_math(x2, w_loc, b_loc), (x2, w_loc)

    def bwd(res, gy):
        x2, w_loc = res
        if gather:
            m_loc = w_loc.shape[0]
            tidx = lax.axis_index(tp_axis)
            gy = lax.dynamic_slice_in_dim(gy, tidx * m_loc, m_loc, axis=1)
        gw = _dot(gy, x2, (((0,), (0,)), ((), ())),
                  accum=True).astype(w_loc.dtype)
        gb = (jnp.sum(gy.astype(jnp.float32), axis=0) if with_bias
              else None)
        with jax.named_scope(f"tp_dx_{layer}"):
            gx = lax.psum(_dot(gy, w_loc, (((1,), (0,)), ((), ())),
                               accum=True), tp_axis).astype(x2.dtype)
        return gx, gw, gb

    fn.defvjp(fwd, bwd)
    return fn


@functools.lru_cache(maxsize=None)
def _tp_row_matmul(tp_axis: str, with_bias: bool, layer: str):
    """Row-parallel FC: x arrives tp-sharded on features (a COL producer
    kept its output sharded), w_loc is (M, K/t); the partial products
    psum over tp (the Megatron ``g`` operator) and the REPLICATED bias
    adds once, after the sum. Backward is purely local: dx_loc and
    dw_loc are exact shard computations; the bias grad is tp-replicated."""

    def fwd_math(x_loc, w_loc, b):
        part = _dot(x_loc, w_loc, (((1,), (1,)), ((), ())))
        with jax.named_scope(f"tp_fwd_{layer}"):
            y = lax.psum(part, tp_axis)
        if with_bias:
            y = y + b.astype(y.dtype)
        return y

    @jax.custom_vjp
    def fn(x_loc, w_loc, b):
        return fwd_math(x_loc, w_loc, b)

    def fwd(x_loc, w_loc, b):
        return fwd_math(x_loc, w_loc, b), (x_loc, w_loc)

    def bwd(res, gy):
        x_loc, w_loc = res
        gx = _dot(gy, w_loc, (((1,), (0,)), ((), ())),
                  accum=True).astype(x_loc.dtype)
        gw = _dot(gy, x_loc, (((0,), (0,)), ((), ())),
                  accum=True).astype(w_loc.dtype)
        gb = (jnp.sum(gy.astype(jnp.float32), axis=0) if with_bias
              else None)
        return gx, gw, gb

    fn.defvjp(fwd, bwd)
    return fn


class SpmdCommContext(CommContext):
    """CommContext for a planned mesh: routes TP layers' FC matmuls to
    the column/row custom VJPs, leaves arena + TP params untapped, and
    rides DENSE taps / SFB factor gathers over the joint (data, fsdp)
    axes (the inner CommConfig's sync_axes)."""

    def __init__(self, cfg: CommConfig, plan: ShardingPlan, arena_layers):
        super().__init__(cfg)
        self.plan = plan
        # layers whose gradients come back packed in the flat buffer's
        # buckets (``ArenaLayout.views``' cotangent) and are summed there
        self.arena_layers = frozenset(arena_layers)

    def is_tp_leaf(self, layer: str, pname: str) -> bool:
        """Net._layer_params' size-mismatch escape hatch: ONLY a leaf the
        plan tensor-shards may arrive smaller than its definition."""
        lp = self.plan.leaf_plan.get((layer, pname))
        return lp is not None and lp.placement == "tp"

    def tap_param(self, layer: str, pname: str, w):
        if layer in self.plan.tp_layers or layer in self.arena_layers:
            return w            # synced after backward: per leaf / bucket
        return super().tap_param(layer, pname, w)

    def inner_product(self, layer: str, x, w, b):
        dec = self.plan.tp_layers.get(layer)
        if dec is None:
            return super().inner_product(layer, x, w, b)
        x2 = x.reshape(x.shape[0], -1)
        if dec.mode == COL:
            return _tp_col_matmul("tp", dec.gather, b is not None,
                                  layer)(x2, w, b)
        return _tp_row_matmul("tp", b is not None, layer)(x2, w, b)


# --------------------------------------------------------------------------- #
# hierarchical gradient sync (fsdp reduce-scatter -> data all-reduce)
# --------------------------------------------------------------------------- #

def _wire_cast(g, wire: Optional[str]):
    wd = WIRE_DTYPES.get(wire) if wire else None
    if wd is None or g.dtype == wd:
        return g, False
    return g.astype(wd), True


def hierarchical_psum(g, plan: ShardingPlan, reduce: str,
                      wire: Optional[str], scope: str):
    """psum over fsdp, then data — the same association order as the
    sharded reduce-scatter path, so the two arms are bitwise comparable.
    Mean scaling divides by the static dp count in f32 (no divisor
    psum). Returns f32."""
    d, f = plan.mesh_cfg.data, plan.mesh_cfg.fsdp
    g, casted = _wire_cast(g, wire)
    if f > 1:
        with jax.named_scope(scope + "_fsdp"):
            g = lax.psum(g, "fsdp")
    if d > 1:
        with jax.named_scope(scope + "_data"):
            g = lax.psum(g, "data")
    g = g.astype(jnp.float32) if casted or reduce == "mean" else g
    if reduce == "mean":
        g = g / plan.n_dp
    return g.astype(jnp.float32)


def sharded_bucket_sync(bufs, plan: ShardingPlan, reduce: str,
                        wire: Optional[str]):
    """The flat buffer's gradient sync: per DWBP-ordered bucket,
    reduce-scatter over fsdp (the gradient lands as
    this device's 1/fsdp shard) then all-reduce the shard over data,
    chained by the finite-token gate so XLA's combiners cannot re-merge
    buckets (distinctness is the prerequisite for mid-backward overlap).
    Returns per-bucket SHARDS when the plan shards params, full buckets
    otherwise (the replicated control arm — hierarchical psums in the
    same association order, bitwise comparable)."""
    d, f = plan.mesh_cfg.data, plan.mesh_cfg.fsdp
    fsdp_on = f > 1 and plan.shard_params
    out = []
    tok = None
    for i, g in enumerate(bufs):
        if tok is not None:
            g = jnp.where(tok < jnp.inf, g, jnp.full_like(g, jnp.nan))
        g, casted = _wire_cast(g, wire)
        if fsdp_on:
            with jax.named_scope(f"grad_rs_bucket{i}"):
                g = lax.psum_scatter(g, "fsdp", tiled=True)
        elif f > 1:
            with jax.named_scope(f"grad_rs_bucket{i}"):
                g = lax.psum(g, "fsdp")
        if d > 1:
            with jax.named_scope(f"grad_ar_bucket{i}"):
                g = lax.psum(g, "data")
        g = g.astype(jnp.float32) if casted else g
        if reduce == "mean":
            g = g.astype(jnp.float32) / plan.n_dp
        t = g[0].astype(jnp.float32)
        tok = t if tok is None else jnp.minimum(tok, t)
        out.append(g)
    return tuple(out)


# --------------------------------------------------------------------------- #
# the spmd train step
# --------------------------------------------------------------------------- #

class SpmdState(NamedTuple):
    """Sharded-state carry (``build_spmd_train_step(sharded_state=True)``).

    ``flat_w``/``flat_h`` are the arena params/momentum in SHARD-MAJOR
    order (``to_shard_major``), living P("fsdp") sharded between steps —
    the 1/fsdp persistent param+grad+momentum footprint. ``excl_*`` carry
    the non-arena leaves (TP shards per the plan, custom-strategy leaves
    replicated). Snapshots convert through ``unshard_train_state`` and
    stay canonical per-leaf."""
    flat_w: jax.Array
    flat_h: jax.Array
    excl_params: Dict
    excl_hist: Dict
    it: jax.Array
    comm_error: Dict


class _BoundLowerable:
    """A jitted callable with trailing bound arguments (the sharded
    multiplier segments), keeping the contract/AOT
    ``.lower(params, state, batch, rng)`` signature."""

    def __init__(self, jitted, extra):
        self._jitted = jitted
        self._extra = tuple(extra)

    def lower(self, *args, **kw):
        return self._jitted.lower(*args, *self._extra, **kw)

    def __call__(self, *args, **kw):
        return self._jitted(*args, *self._extra, **kw)


def build_spmd_train_step(
    net,
    sp,
    mesh: Mesh,
    plan: ShardingPlan,
    comm: Optional[CommConfig] = None,
    donate: bool = True,
    donate_batch: bool = False,
    input_transform: Optional[Callable] = None,
    input_layout: str = "NCHW",
    sharded_state: bool = False,
    remat_plan=None,
):
    """Compiled SPMD train step over a (data, fsdp, tp) mesh.

    ``remat_plan`` (``core/remat.RematPlan``): the named layers' forward
    bodies run under ``jax.checkpoint`` inside ``Net.apply``, dropping
    their stored activations within the budget — orthogonal to the
    sharding plan (it changes what is stored, never the collectives or
    the math; remat arms are bitwise-equal to stored-activation arms).

    Canonical layout (default): keeps the
    ``(params, state, batch, rng) -> (params, state, metrics)`` contract
    with canonical per-leaf trees at the boundary (snapshots, eval and
    the engine are unchanged); inside, arena gradients reduce-scatter
    over fsdp, the fused update runs on each device's shard with its
    sharded multiplier segments, and updated shards all-gather back.

    ``sharded_state=True`` (needs fsdp > 1): the carry is an
    ``SpmdState`` whose arena params/momentum LIVE fsdp-sharded between
    steps (params all-gather in the step prologue; momentum never
    crosses the wire) — the ZeRO footprint the AOT memory estimate
    records. Convert at boundaries with ``shard_train_state`` /
    ``unshard_train_state``; the step signature is
    ``(state, batch, rng) -> (state, metrics)``.
    """
    import dataclasses

    from ..solvers.updates import (SolverState, _leafwise_update,
                                   learning_rate, make_flat_update_rule)
    from .trainer import (TrainState, TrainStep, param_mults,
                          refuse_layer_updates)

    comm = comm or CommConfig()
    comm.wire_jnp_dtype()
    refuse_layer_updates(net, "--mesh")
    if sp.solver_type == "ADAM" or sp.clip_gradients > 0:
        raise ValueError("solver_type ADAM and clip_gradients run on the "
                         "flat data mesh only: the fsdp-sharded update "
                         "carries one history buffer and no global norm")
    for axis in SPMD_AXES:
        if axis not in mesh.shape:
            raise ValueError(f"plan mesh needs axis {axis!r}; build it "
                             f"with spmd.named_mesh")
    if comm.dcn_axis is not None:
        raise ValueError("--mesh and --dcn_slices do not compose: the "
                         "named mesh's axes carry the whole topology")
    for lname in net.param_defs:
        if comm.strategy_for(lname) == LOCAL:
            raise ValueError(
                f"layer {lname!r}: LOCAL (unsynced) params diverge across "
                f"replicas; use build_ssp_train_step")
    if comm.dwbp_bucket_mb is not None:
        from ..runtime.metrics import log
        log("WARNING: dwbp_bucket_mb is superseded by the arena's "
            "bucketed reduce-scatter schedule on a named mesh; ignoring")
        comm = dataclasses.replace(comm, dwbp_bucket_mb=None)

    cfgm = plan.mesh_cfg
    d, f = cfgm.data, cfgm.fsdp
    fsdp_on = f > 1 and plan.shard_params
    if sharded_state and not fsdp_on:
        raise ValueError("sharded_state needs fsdp > 1 with sharded "
                         "params (the fsdp axis IS the shard dimension)")
    mults = param_mults(net)
    layout = None
    if plan.arena_layers:
        layout = net.arena_layout(plan.arena_layers, comm.arena_bucket_mb,
                                  align=f if fsdp_on else 1)
    if sharded_state and layout is None:
        raise ValueError("sharded_state needs at least one arena (DENSE) "
                         "layer to shard")
    flat_rule = make_flat_update_rule(sp)
    # joint-axes comm config for taps / SFB factor gathers: sync_axes ==
    # ("data", "fsdp") matches the batch spec's device order
    inner_cfg = dataclasses.replace(comm, axis="fsdp", dcn_axis="data")
    ctx = SpmdCommContext(inner_cfg, plan,
                          arena_layers=(layout.layers if layout is not None
                                        else frozenset()))

    topk_fraction = budget_topk_fraction(net, comm)
    shard_lens = ([(hi - lo) // f for lo, hi in layout.bucket_ranges]
                  if layout is not None and fsdp_on else [])
    shard_cum = [0]
    for s in shard_lens:
        shard_cum.append(shard_cum[-1] + s)

    # fsdp-sharded multiplier segments, fed as explicit trailing step
    # arguments so each device holds only its 1/fsdp slice — a closure
    # constant would be replicated into every device's program. The
    # replicated arm updates per leaf and needs no multiplier vectors.
    if layout is not None and fsdp_on:
        lr_np, dec_np = _shard_mult_vectors(layout, sp, f)
        mult_spec = P("fsdp")
        try:
            # pre-place the shards so the hot path never re-transfers
            mult_args = (jax.device_put(jnp.asarray(lr_np),
                                        NamedSharding(mesh, mult_spec)),
                         jax.device_put(jnp.asarray(dec_np),
                                        NamedSharding(mesh, mult_spec)))
        except Exception:  # noqa: BLE001 — abstract (AOT topology) mesh:
            # no real devices to place onto; raw host arrays lower fine
            mult_args = (lr_np, dec_np)
    else:
        mult_args = ()
        mult_spec = P()

    batch_spec = plan.batch_spec()
    err_spec = P(("data", "fsdp"))
    param_specs = {l: {p.name: plan.param_spec(l, p.name) for p in defs}
                   for l, defs in net.param_defs.items()}
    excl_specs = {l: ps for l, ps in param_specs.items()
                  if layout is None or l not in layout.layers}

    def _fold_rng(rng):
        flat_idx = lax.axis_index("data") * f + lax.axis_index("fsdp")
        # NOT folded by tp: dropout masks must match across tp replicas
        return jax.random.fold_in(rng, flat_idx)

    # layers whose forward bodies Net.apply wraps in jax.checkpoint
    _remat = (remat_plan.apply_args
              if remat_plan is not None and remat_plan.layers else {})

    def _forward_backward(arena_bufs, excl_params, batch, rng):
        if layout is not None:
            def loss_fn(bufs, excl):
                p = layout.merge(layout.views(*bufs), excl)
                o = net.apply(p, batch, train=True, rng=rng, comm=ctx,
                              input_layout=input_layout, **_remat)
                return o.loss, o

            (bucket_grads, excl_grads), out = jax.grad(
                loss_fn, argnums=(0, 1), has_aux=True)(arena_bufs,
                                                       excl_params)
        else:
            def loss_fn(excl):
                o = net.apply(excl, batch, train=True, rng=rng, comm=ctx,
                              input_layout=input_layout, **_remat)
                return o.loss, o

            excl_grads, out = jax.grad(loss_fn, has_aux=True)(excl_params)
            bucket_grads = ()
        return bucket_grads, excl_grads, out

    def _sync_excl(excl_grads, comm_error, it):
        """Per-leaf syncs for everything outside the arena buckets: TP
        layers and DENSE_FUSED via hierarchical psums, TOPK compressed
        exchange (per-device error feedback). SFB synced in-backward;
        DENSE taps likewise (arena off)."""
        new_errors = dict(comm_error)
        for lname in excl_grads:
            strat = comm.strategy_for(lname)
            if lname in plan.tp_layers or strat == DENSE_FUSED:
                prefix = ("grad_tp" if lname in plan.tp_layers
                          else "grad_fused")
                for pname, g in excl_grads[lname].items():
                    excl_grads[lname][pname] = hierarchical_psum(
                        g, plan, comm.reduce, comm.wire_dtype,
                        scope=f"{prefix}_{lname}_{pname}").astype(g.dtype)
            elif strat == TOPK:
                lerr = {}
                for pname, g in excl_grads[lname].items():
                    err = comm_error[lname][pname][0]
                    sent, resid = topk_compress(
                        g, topk_fraction, err, comm.topk_policy, it,
                        salt=comm_salt(lname, pname),
                        block=comm.topk_block, wire=comm.wire_dtype)
                    g_sync = wire_psum(sent, ("data", "fsdp"), "sum",
                                       comm.wire_dtype)
                    if comm.reduce == "mean":
                        g_sync = g_sync / plan.n_dp
                    excl_grads[lname][pname] = g_sync
                    lerr[pname] = resid[None]
                new_errors[lname] = lerr
        return excl_grads, new_errors

    def _metrics(out):
        ms = {"loss": out.loss}
        for name, val in out.outputs.items():
            if val.ndim == 0:
                ms[name] = val
        res = {}
        for name, val in ms.items():
            v = val.astype(jnp.float32)
            if f > 1:
                v = lax.psum(v, "fsdp")
            if d > 1:
                v = lax.psum(v, "data")
            res[name] = v / plan.n_dp
        return res

    # ------------------------------------------------------------------ #
    if sharded_state:
        def device_step(state: SpmdState, batch, rng, *mult):
            rng = _fold_rng(rng)
            if input_transform is not None:
                batch = input_transform(batch)
            # prologue: params all-gather per bucket (flat_w is the local
            # shard-major slice: bucket i's shard at shard_cum[i])
            bufs = []
            for i in range(len(shard_lens)):
                ws = lax.slice(state.flat_w, (shard_cum[i],),
                               (shard_cum[i + 1],))
                with jax.named_scope(f"param_ag_bucket{i}"):
                    bufs.append(lax.all_gather(ws, "fsdp", tiled=True))
            bucket_grads, excl_grads, out = _forward_backward(
                tuple(bufs), state.excl_params, batch, rng)
            bucket_grads = sharded_bucket_sync(
                bucket_grads, plan, comm.reduce, comm.wire_dtype)
            excl_grads, new_errors = _sync_excl(
                excl_grads, state.comm_error, state.it)
            with jax.named_scope("optimizer_update"):
                rate = learning_rate(sp, state.it)
                g_sh = (jnp.concatenate(list(bucket_grads))
                        if len(bucket_grads) > 1 else bucket_grads[0])
                new_w, new_h = flat_rule(state.flat_w, g_sh, state.flat_h,
                                         rate, *mult)
                new_excl, new_excl_hist = _leafwise_update(
                    sp, mults, rate, state.excl_params, excl_grads,
                    state.excl_hist)
            metrics = _metrics(out)
            return SpmdState(new_w, new_h, new_excl, new_excl_hist,
                             state.it + 1, new_errors), metrics

        state_spec = SpmdState(P("fsdp"), P("fsdp"), excl_specs,
                               excl_specs, P(), err_spec)
        sharded = shard_map(
            device_step, mesh=mesh,
            in_specs=(state_spec, batch_spec, P())
            + (mult_spec,) * len(mult_args),
            out_specs=(state_spec, P()),
            check_vma=False)
        argnums = (0,) if donate else ()
        if donate_batch:
            argnums = argnums + (1,)
        jitted = jax.jit(sharded, donate_argnums=argnums)
        lowerable = _BoundLowerable(jitted, mult_args)

        return TrainStep(
            step=lambda state, batch, rng: lowerable(state, batch, rng),
            mesh=mesh,
            batch_sharding=NamedSharding(mesh, batch_spec),
            replicated=NamedSharding(mesh, P()),
            lowerable=lowerable, input_layout=input_layout, arena=layout,
            update_route="flat_fsdp")

    # ------------------------------------------------------------------ #
    # canonical-boundary layout (the engine/CLI step)
    def device_step(params, state: TrainState, batch, rng, *mult):
        rng = _fold_rng(rng)
        fidx = lax.axis_index("fsdp")
        if input_transform is not None:
            batch = input_transform(batch)
        if layout is not None:
            arena_bufs = layout.split_buckets(layout.pack(params))
            excl_params = layout.residual(params)
        else:
            arena_bufs, excl_params = (), params
        bucket_grads, excl_grads, out = _forward_backward(
            arena_bufs, excl_params, batch, rng)
        bucket_grads = sharded_bucket_sync(bucket_grads, plan, comm.reduce,
                                           comm.wire_dtype)
        excl_grads, new_errors = _sync_excl(excl_grads, state.comm_error,
                                            state.solver.it)
        with jax.named_scope("optimizer_update"):
            rate = learning_rate(sp, state.solver.it)
            if layout is not None and fsdp_on:
                # shard update: slice this device's w/h shards, run the
                # fused rule on 1/fsdp of the buffer, gather back
                def my_shard(buf, i):
                    return lax.dynamic_slice(
                        buf, (fidx * shard_lens[i],), (shard_lens[i],))

                flat_h = layout.pack(state.solver.history)
                h_bufs = layout.split_buckets(flat_h)
                w_sh = [my_shard(b, i) for i, b in enumerate(arena_bufs)]
                h_sh = [my_shard(b, i) for i, b in enumerate(h_bufs)]
                cat = (lambda xs: jnp.concatenate(list(xs))
                       if len(xs) > 1 else xs[0])
                new_w_sh, new_h_sh = flat_rule(
                    cat(w_sh), cat(bucket_grads), cat(h_sh), rate, *mult)
                new_bufs, new_hufs = [], []
                for i in range(len(shard_lens)):
                    wsl = lax.slice(new_w_sh, (shard_cum[i],),
                                    (shard_cum[i + 1],))
                    hsl = lax.slice(new_h_sh, (shard_cum[i],),
                                    (shard_cum[i + 1],))
                    with jax.named_scope(f"param_ag_bucket{i}"):
                        new_bufs.append(
                            lax.all_gather(wsl, "fsdp", tiled=True))
                    with jax.named_scope(f"hist_ag_bucket{i}"):
                        new_hufs.append(
                            lax.all_gather(hsl, "fsdp", tiled=True))
                excl_hist = layout.residual(state.solver.history)
                new_excl, new_excl_hist = _leafwise_update(
                    sp, mults, rate, excl_params, excl_grads, excl_hist)
                new_params = layout.merge(
                    layout.unpack(layout.join_buckets(new_bufs)), new_excl)
                new_hist = layout.merge(
                    layout.unpack(layout.join_buckets(new_hufs)),
                    new_excl_hist)
                new_solver = SolverState(it=state.solver.it + 1,
                                         history=new_hist)
            else:
                # replicated arm (and no arena at all): the per-leaf rule
                # on the canonical leaves, the bucketed sums sliced back
                grads = excl_grads
                if layout is not None:
                    grads = layout.merge(
                        layout.unpack_buckets(bucket_grads), excl_grads)
                new_params, new_hist = _leafwise_update(
                    sp, mults, rate, params, grads, state.solver.history)
                new_solver = SolverState(it=state.solver.it + 1,
                                         history=new_hist)
        metrics = _metrics(out)
        return new_params, TrainState(new_solver, new_errors), metrics

    state_spec = TrainState(
        solver=SolverState(it=P(), history=param_specs),
        comm_error=err_spec)
    sharded = shard_map(
        device_step, mesh=mesh,
        in_specs=(param_specs, state_spec, batch_spec, P())
        + (mult_spec,) * len(mult_args),
        out_specs=(param_specs, state_spec, P()),
        check_vma=False)
    argnums = (0, 1) if donate else ()
    if donate_batch:
        argnums = argnums + (2,)
    jitted = jax.jit(sharded, donate_argnums=argnums)
    lowerable = _BoundLowerable(jitted, mult_args)

    return TrainStep(
        step=lambda p, s, b, r: lowerable(p, s, b, r),
        mesh=mesh,
        batch_sharding=NamedSharding(mesh, batch_spec),
        replicated=NamedSharding(mesh, P()),
        lowerable=lowerable, input_layout=input_layout, arena=layout,
        update_route="flat_fsdp" if layout is not None and fsdp_on
        else "leaf")


def sharded_state_avals(net, layout, plan: ShardingPlan,
                        mesh: Mesh) -> SpmdState:
    """ShapeDtypeStruct avals for an ``SpmdState`` with the plan's
    shardings attached — what AOT lowering against an abstract topology
    (scripts/aot_tpu_check.py) feeds ``lowerable.lower`` instead of real
    arrays."""

    def aval(shape, spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                    sharding=NamedSharding(mesh, spec))

    excl = {l: {p.name: aval(p.shape, plan.param_spec(l, p.name))
                for p in defs}
            for l, defs in net.param_defs.items()
            if l not in layout.layers}
    fs = P("fsdp")
    return SpmdState(
        flat_w=aval((layout.padded_total,), fs),
        flat_h=aval((layout.padded_total,), fs),
        excl_params=excl,
        excl_hist=excl,
        it=aval((), P(), jnp.int32),
        comm_error={})


# --------------------------------------------------------------------------- #
# sharded-state converters (snapshots stay canonical per-leaf)
# --------------------------------------------------------------------------- #

def shard_train_state(params, state, layout, mesh: Mesh,
                      plan: ShardingPlan) -> SpmdState:
    """Canonical (params, TrainState) -> SpmdState: arena buffers to
    shard-major order, placed P("fsdp"); TP leaves placed per the plan."""
    f = plan.mesh_cfg.fsdp
    flat_w = to_shard_major(np.asarray(layout.pack(params)), layout, f)
    flat_h = to_shard_major(np.asarray(layout.pack(state.solver.history)),
                            layout, f)
    fs = NamedSharding(mesh, P("fsdp"))

    def place_tree(tree):
        return {l: {k: jax.device_put(
            v, NamedSharding(mesh, plan.param_spec(l, k)))
            for k, v in lp.items()} for l, lp in tree.items()}

    return SpmdState(
        flat_w=jax.device_put(jnp.asarray(flat_w), fs),
        flat_h=jax.device_put(jnp.asarray(flat_h), fs),
        excl_params=place_tree(layout.residual(params)),
        excl_hist=place_tree(layout.residual(state.solver.history)),
        it=state.solver.it,
        comm_error=state.comm_error)


def unshard_train_state(spmd_state: SpmdState, layout,
                        plan: ShardingPlan):
    """SpmdState -> canonical (params, TrainState): the flat buffers
    materialize to host, invert the shard-major permutation, and unpack —
    exact copies, so a snapshot written from a sharded run restores
    bit-identically into a replicated one (cross-mesh portability)."""
    from ..solvers.updates import SolverState
    from .trainer import TrainState
    f = plan.mesh_cfg.fsdp
    flat_w = jnp.asarray(from_shard_major(
        np.asarray(spmd_state.flat_w), layout, f))
    flat_h = jnp.asarray(from_shard_major(
        np.asarray(spmd_state.flat_h), layout, f))
    params = layout.merge(layout.unpack(flat_w), spmd_state.excl_params)
    hist = layout.merge(layout.unpack(flat_h), spmd_state.excl_hist)
    return params, TrainState(
        solver=SolverState(it=spmd_state.it, history=hist),
        comm_error=spmd_state.comm_error)
