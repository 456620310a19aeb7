"""The arena: static offsets and bucket ranges of one flat f32 buffer.

The reference's server tier (Bösen) stores parameters as contiguous table
rows (server_table.cpp rows; SSPAggr ships row ranges). This module is the
contiguous-row analog for the two steps whose STATE lives in such a buffer:

- the fsdp-sharded step of parallel/spmd.py, which shards the buffer over
  the fsdp axis: its parameters are ``views`` of the buffer's buckets, the
  cotangent comes back packed (``pack_grad_buckets``), and each bucket is
  reduce-scattered over fsdp;
- the SSP tier's boundary delta exchange (``build_ssp_train_step``), which
  packs the accumulated delta and sums it one bucket at a time.

``--param_arena`` keeps its meaning there and only there. The synchronous
data-parallel step of ``parallel/trainer.build_train_step`` packs NOTHING
since PR 59: each DENSE gradient is summed by the tap in its own layer's
backward, in the layout the compiler keeps the leaf in. Bösen's reason for
contiguous rows (transmission cost over Ethernet must not scale with the
number of tensors) is, on ICI under XLA, the compiler's all-reduce
combiner's job; packing 244 MB of AlexNet gradients into 61 flat 4 MB
buffers, gating them into a chain and slicing them back cost 6.4 ms of a
40 ms step on four v5e chips (PERF.md section 6, PR 59).

- **Offset table** (``ArenaSlot``): every included f32 parameter leaf gets
  a static ``[offset, offset+size)`` range in one flat f32 index space.
  Slot order is the DWBP order — REVERSE forward layer order, i.e. the
  order gradients materialize during backward — so bucket 0's gradients
  exist first.
- **Buckets**: the flat range is cut at exact ``bucket_mb`` element
  boundaries (leaves may span buckets), so an exchange is exactly
  ``ceil(total_bytes / bucket_mb)`` collectives.

Checkpoints are canonical per-leaf throughout and ``--param_arena`` does
not change them.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Tree = Dict[str, Dict[str, jax.Array]]


@dataclass(frozen=True)
class ArenaSlot:
    """One parameter leaf's static range within the flat buffer."""
    layer: str
    pname: str
    shape: Tuple[int, ...]
    offset: int          # element offset within the flat f32 buffer
    size: int
    lr_mult: float
    decay_mult: float


class ArenaLayout:
    """Static offset table + bucket ranges for one Net's arena-eligible
    leaves. Everything here is computed once (plain Python/numpy); the jax
    ops it emits at trace time are slices, reshapes and concatenates."""

    def __init__(self, slots: Sequence[ArenaSlot],
                 bucket_mb: Optional[float], align: int = 1):
        if not slots:
            raise ValueError("empty arena")
        self.slots: Tuple[ArenaSlot, ...] = tuple(slots)
        self.total = slots[-1].offset + slots[-1].size
        self.dtype = jnp.float32
        itemsize = 4
        # ``align`` > 1 (the SPMD mesh's fsdp shard count, parallel/spmd.py):
        # every bucket boundary snaps to a multiple of align and the buffer
        # is zero-padded up to one, so each bucket splits into exactly
        # align equal shards (reduce-scatter / all-gather operands). The
        # padding tail carries zero lr/decay multipliers — the flat update
        # leaves it at zero — and pack/unpack ignore it, so the logical
        # (canonical per-leaf) contract is unchanged.
        self.align = max(1, int(align))
        self.padded_total = -(-self.total // self.align) * self.align
        if bucket_mb is None or bucket_mb <= 0:
            if self.align > 1:
                raise ValueError(
                    "per-leaf buckets (bucket_mb <= 0) cannot align to an "
                    "fsdp shard count; use a positive bucket_mb")
            # per-leaf buckets (the dwbp_bucket_mb=0 convention)
            self.bucket_ranges = [(s.offset, s.offset + s.size)
                                  for s in self.slots]
        else:
            b = max(1, int(bucket_mb * 1e6) // itemsize)
            b = -(-b // self.align) * self.align
            self.bucket_ranges = [(lo, min(lo + b, self.padded_total))
                                  for lo in range(0, self.padded_total, b)]
        self.n_buckets = len(self.bucket_ranges)
        self.layers: FrozenSet[str] = frozenset(s.layer for s in self.slots)
        self._index = {(s.layer, s.pname): s for s in self.slots}
        # slot -> pieces (bucket, global lo, global hi); bucket -> pieces
        # (slot_idx, global lo, global hi). Buckets cut at exact element
        # boundaries, so a leaf may contribute pieces to several buckets.
        self._slot_pieces: List[List[Tuple[int, int, int]]] = []
        self._bucket_pieces: List[List[Tuple[int, int, int]]] = \
            [[] for _ in self.bucket_ranges]
        for si, s in enumerate(self.slots):
            pieces = []
            for bi, (blo, bhi) in enumerate(self.bucket_ranges):
                lo, hi = max(s.offset, blo), min(s.offset + s.size, bhi)
                if lo < hi:
                    pieces.append((bi, lo, hi))
                    self._bucket_pieces[bi].append((si, lo, hi))
            self._slot_pieces.append(pieces)
        self._views = None

    # -------------------------------------------------------------- #
    def total_bytes(self) -> int:
        return self.total * 4

    def has(self, layer: str, pname: str) -> bool:
        return (layer, pname) in self._index

    def _leaf(self, tree: Tree, slot: ArenaSlot) -> jax.Array:
        v = tree[slot.layer][slot.pname]
        if v.dtype != self.dtype:
            raise TypeError(
                f"arena leaf {slot.layer}/{slot.pname} is {v.dtype}, not "
                f"{self.dtype}; the flat parameter arena is f32-homogeneous "
                f"(disable with param_arena=False)")
        return v

    def pack(self, tree: Tree) -> jax.Array:
        """Per-leaf tree -> flat 1-D buffer (zero tail up to
        ``padded_total`` under fsdp alignment), in slot (DWBP) order."""
        # named scopes here and below: xplane events from the pack/unpack
        # copies attribute to the arena phase, not to the residual row
        # (runtime/attribution.py joins these names back from op metadata)
        with jax.named_scope("arena_pack"):
            parts = [self._leaf(tree, s).reshape(-1) for s in self.slots]
            if self.padded_total > self.total:
                parts.append(jnp.zeros(self.padded_total - self.total,
                                       self.dtype))
            return jnp.concatenate(parts)

    def unpack(self, flat: jax.Array) -> Tree:
        """Flat buffer -> per-leaf tree (static slices + reshapes)."""
        with jax.named_scope("arena_unpack"):
            out: Tree = {}
            for s in self.slots:
                leaf = lax.slice(flat, (s.offset,), (s.offset + s.size,))
                out.setdefault(s.layer, {})[s.pname] = leaf.reshape(s.shape)
            return out

    def split_buckets(self, flat: jax.Array) -> Tuple[jax.Array, ...]:
        return tuple(lax.slice(flat, (lo,), (hi,))
                     for lo, hi in self.bucket_ranges)

    def join_buckets(self, bufs: Sequence[jax.Array]) -> jax.Array:
        return bufs[0] if len(bufs) == 1 else jnp.concatenate(list(bufs))

    # -------------------------------------------------------------- #
    def residual(self, tree: Tree) -> Tree:
        """The leaves NOT in the arena (SFB/TOPK/LOCAL/fused opt-outs)."""
        out: Tree = {}
        for lname, lp in tree.items():
            keep = {k: v for k, v in lp.items() if not self.has(lname, k)}
            if keep:
                out[lname] = keep
        return out

    @staticmethod
    def merge(a: Tree, b: Tree) -> Tree:
        """Leaf-level union of two disjoint {layer: {param: leaf}} trees."""
        out = {k: dict(v) for k, v in a.items()}
        for lname, lp in b.items():
            out.setdefault(lname, {}).update(lp)
        return out

    # -------------------------------------------------------------- #
    def _bucket_slices(self, bufs: Sequence[jax.Array]) -> Tree:
        out: Tree = {}
        for s, pieces in zip(self.slots, self._slot_pieces):
            parts = [lax.slice(bufs[bi],
                               (lo - self.bucket_ranges[bi][0],),
                               (hi - self.bucket_ranges[bi][0],))
                     for bi, lo, hi in pieces]
            leaf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            out.setdefault(s.layer, {})[s.pname] = leaf.reshape(s.shape)
        return out

    def unpack_buckets(self, bufs: Sequence[jax.Array]) -> Tree:
        """Per-bucket buffers -> per-leaf tree (static slices + reshapes;
        a leaf that spans buckets is concatenated from its pieces)."""
        with jax.named_scope("arena_unpack"):
            return self._bucket_slices(bufs)

    def pack_grad_buckets(self, tree: Tree) -> Tuple[jax.Array, ...]:
        """Per-leaf gradients -> one buffer per bucket, each concatenated
        from ITS OWN leaves' pieces only (one copy, no pad-and-add): bucket
        k depends on nothing but its layers' backward, so its psum can
        issue as soon as that is done. Leaves of ``tree`` outside the
        layout are ignored."""
        # "arena_grads": the copies between backward matmuls and the
        # buckets' reduce-scatters
        with jax.named_scope("arena_grads"):
            outs = []
            for bi, pieces in enumerate(self._bucket_pieces):
                parts = []
                covered = 0
                for si, lo, hi in pieces:
                    s = self.slots[si]
                    leaf = self._leaf(tree, s).reshape(-1)
                    parts.append(lax.slice(leaf, (lo - s.offset,),
                                           (hi - s.offset,)))
                    covered += hi - lo
                blo, bhi = self.bucket_ranges[bi]
                if covered < bhi - blo:
                    # alignment tail (no slot behind it): the bucket must
                    # still be bucket-shaped
                    parts.append(jnp.zeros(bhi - blo - covered, self.dtype))
                outs.append(parts[0] if len(parts) == 1 else
                            jnp.concatenate(parts))
            return tuple(outs)

    def views(self, *bufs: jax.Array) -> Tree:
        """Per-bucket PARAMETER buffers -> per-leaf tree, as a custom-vjp
        pair so the COTANGENT comes back packed (``pack_grad_buckets`` of
        the leaf cotangents). For a step whose parameters live in the flat
        buffer: the fsdp-sharded step of parallel/spmd.py."""
        if self._views is None:
            layout = self

            def slices(*bufs):
                with jax.named_scope("arena_views"):
                    return layout._bucket_slices(bufs)

            views_fn = jax.custom_vjp(slices)
            views_fn.defvjp(lambda *bufs: (slices(*bufs), None),
                            lambda _, ct: layout.pack_grad_buckets(ct))
            self._views = views_fn
        return self._views(*bufs)

    # -------------------------------------------------------------- #
    def mult_vectors(self, weight_decay: float):
        """(lr_mults, local_decays) as f32 numpy vectors over the buffer.
        Each segment holds exactly the scalars the per-leaf update rule
        uses: f32(lr_mult) and f32(weight_decay * decay_mult) — the
        products taken in Python float first, like the per-leaf path, so
        the flat rule (solvers/updates.make_flat_update_rule) is
        bit-identical. The alignment tail (if any) keeps zero multipliers,
        so that rule leaves it at zero."""
        lr = np.zeros(self.padded_total, np.float32)
        dec = np.zeros(self.padded_total, np.float32)
        for s in self.slots:
            lr[s.offset:s.offset + s.size] = np.float32(s.lr_mult)
            dec[s.offset:s.offset + s.size] = np.float32(
                weight_decay * s.decay_mult)
        return lr, dec


def build_arena(order: Sequence[Tuple[str, object]],
                include: FrozenSet[str],
                bucket_mb: Optional[float],
                align: int = 1) -> Optional[ArenaLayout]:
    """ArenaLayout over ``order`` — the Net's DWBP-ordered (layer, ParamDef)
    table — restricted to ``include`` layers. None when nothing qualifies.
    Both the trainer and any tool that needs to re-derive the layout call
    this with the same inputs, so offsets always agree."""
    slots: List[ArenaSlot] = []
    off = 0
    for lname, pdef in order:
        if lname not in include:
            continue
        slots.append(ArenaSlot(lname, pdef.name, tuple(pdef.shape), off,
                               pdef.count, pdef.lr_mult, pdef.decay_mult))
        off += pdef.count
    if not slots:
        return None
    return ArenaLayout(slots, bucket_mb, align=align)
