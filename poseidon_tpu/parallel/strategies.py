"""Gradient-communication strategies: DWBP overlap, SFB, managed compression.

This module is the TPU-native rebuild of the reference's three signature
mechanisms (SURVEY.md §2.3):

**DWBP — distributed wait-free backpropagation** (solver.cpp:405-531). The
reference spawns one sync thread per param blob the moment that layer's
backward completes, overlapping gradient communication with the remaining
backward pass. Here every parameter is routed through a ``custom_vjp``
"sync tap": identity on the forward pass, a ``lax.psum`` on the cotangent in
the backward pass. Because the psum is emitted *inside* the backward graph at
the exact point each layer's gradient materializes, XLA's latency-hiding
scheduler overlaps each collective with the remaining backward compute — the
compiled equivalent of Poseidon's per-layer sync threads.

**SFB/SVB — sufficient-factor broadcasting** (svb_worker.cpp,
inner_product_layer.cpp:126). For an FC layer, ∇W = gᵀ·x is rank-B; the
reference ships the factors (g, x) peer-to-peer instead of the M×N matrix.
Here the FC matmul gets a ``custom_vjp`` whose backward all-gathers the
factors along the data axis and reconstructs the *global* ∇W locally:
comm cost O(B(M+N)) vs O(MN) — the same trade, riding ICI instead of an
Ethernet ZMQ mesh.

**Managed communication** (ssp_aggr_*: bandwidth-budgeted,
magnitude-prioritized partial pushes). Maps to magnitude top-k gradient
compression with error feedback for the slow (DCN) tier: send only the
largest k% of gradient entries, accumulate the residual locally — the same
"most important bytes first under a budget" idea, compiled.

Strategy selection is per-layer (the reference's SACP: dense PS path for conv,
SFB for FC), via ``CommConfig.layer_strategies``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field as dc_field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import matmul_precision, policy

DENSE = "dense"      # psum in backward (DWBP-style overlap) — the default
SFB = "sfb"          # sufficient-factor broadcast for FC layers
LOCAL = "local"      # no sync (the reference's LOCAL blob mode)
TOPK = "topk"        # magnitude top-k compressed psum with error feedback
# All psums issued together after the whole backward finishes — the
# no-overlap baseline the reference compares DWBP against (one big sync at
# the end of ForwardBackward instead of per-layer threads). Exists for A/B
# measurement of the overlap win; not a production choice.
DENSE_FUSED = "dense_fused"

WIRE_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "f16": jnp.float16}


@dataclass
class CommConfig:
    axis: str = "data"
    # Optional second, slower tier (the multi-slice/DCN axis of a 2-D mesh).
    # When set, DENSE/SFB collectives ride both axes jointly, while TOPK
    # becomes hierarchical: dense psum intra-slice (fast ICI), then
    # magnitude-compressed exchange inter-slice (bandwidth-limited DCN) —
    # the SSPAggr deployment shape (ssp_aggr_server_thread.cpp:13-90:
    # full-rate updates inside a machine, budgeted prioritized bytes across).
    dcn_axis: Optional[str] = None
    default_strategy: str = DENSE
    layer_strategies: Dict[str, str] = dc_field(default_factory=dict)
    # "mean" is classic synchronous SGD: convergence matches single-machine
    # Caffe at the same global batch and solver settings. "sum" reproduces the
    # reference's PS accumulation (every worker BatchIncs its own update),
    # which scales the effective LR by the worker count — the reason PMLS
    # retuned lr per cluster size; select it only for strict reference parity.
    reduce: str = "mean"
    topk_fraction: float = 0.01
    # Which entries the TOPK budget spends on — the server's UpdateSortPolicy
    # (configs.hpp:27-33, server_table.cpp:263-297):
    #   "magnitude"   — largest |g+err| first (RelativeMagnitude, default)
    #   "random"      — uniform random subset each step (Random)
    #   "fixed_order" — contiguous 1/k slabs in rotation (FixedOrder; every
    #                   entry is sent exactly once per ceil(1/fraction) steps)
    # Measured (docs/performance-guide.md): at small fractions magnitude
    # converges nearly like dense, random lags, fixed_order can destabilize
    # (long rotation delay + momentum) — the reference's own reason for
    # defaulting to RelativeMagnitude importance ordering.
    topk_policy: str = "magnitude"
    # Optional bandwidth budget for the managed-comm (TOPK) tier, in MB per
    # step per device — the SSPAggr "client_bandwidth_mbps" analog
    # (trans_time_estimate.hpp). When set, topk_fraction is derived from the
    # budget over the TOPK layers' total parameter count.
    bandwidth_budget_mb: Optional[float] = None
    # Reduced-precision gradient exchange — the DenseRowFloat16 analog
    # (ps/src/petuum_ps_common/storage/dense_row_float16.hpp:10-16: the
    # reference could hold parameter rows in float16 to halve comm+storage).
    # One of None (exchange at gradient dtype), "bf16", "f16", "f32".
    # Gradients are cast to the wire dtype before every collective (psum /
    # all-gather) and the result is cast back up, with the mean division in
    # f32. The quantization error folds into the TOPK error-feedback residual
    # where one exists (nothing lost, only delayed — better than the
    # reference, which simply stored f16).
    wire_dtype: Optional[str] = None
    # SSP server-side update logic (abstract_server_table_logic.hpp):
    #   "inc"         — plain RowBatchInc: deltas add to the anchor (default)
    #   "adarevision" — delay-corrected AdaGrad (adarevision_server_table
    #                   _logic.cpp:52-175): the anchor update for each
    #                   group's accumulated gradient u is
    #                   -eta*u + (eta_old - eta)*g_bck, where g_bck is the
    #                   gradient mass applied since that group's snapshot
    #                   and eta = init_step/sqrt(z_max) with the revision-
    #                   corrected accumulator z += u*(u + 2*g_bck).
    # Only meaningful for build_ssp_train_step (the sync path has no
    # server); composes with staleness, not with TOPK compression.
    # NOTE: adarevision IGNORES ``reduce`` — the server applies every
    # group's full u in sequence (the reference's RowBatchInc sum
    # semantics; there is no mean in ApplyRowOpLog), so the effective step
    # scales with the group count. Size ``adarev_init_step`` accordingly
    # (~base_lr / n_groups is the stable regime — the same reason PMLS
    # retuned lr per cluster size).
    server_logic: str = "inc"
    # The adarevision server's init_step_size flag (its gflags default 0.1)
    adarev_init_step: float = 0.1
    # DWBP bucketing (solver.cpp:419-449 per-blob sync threads, recast).
    # None (default): plain in-backward taps, one psum a leaf; XLA's
    # all-reduce combiner may merge them. A number: chain the taps into
    # ~this-many-MB buckets via ordering tokens, forcing one DISTINCT
    # collective per bucket; 0 = one per parameter (the reference's exact
    # shape). What the chip showed (four v5e chips, AlexNet 4 x 512 bf16;
    # PERF.md section 6, PR 59): under None the compiler keeps fc6's 151 MB
    # all-reduce apart, merges the other 15 leaves into one of 93 MB, and
    # schedules both, synchronous, after the last convolution's backward
    # (4.27 ms a step, all exposed); under 0 the chain makes the
    # collectives a critical path, the scheduler starts fc8's, fc7's and
    # fc6's mid-backward inside async_collective_fusions, and the cell
    # reads 2.25% more (14,266 -> 14,586 images/s/chip in two pairs, for
    # 0.5 GB more memory). The default stays None: the gate is a data
    # dependency that costs a pass over each gradient, and a net of many
    # small leaves (GoogLeNet's 128) wants them merged, not chained.
    dwbp_bucket_mb: Optional[float] = None
    # The flat parameter buffer (core/arena.py) of the two steps whose
    # STATE lives in one: the fsdp-sharded step of parallel/spmd.py, which
    # shards the buffer itself and needs this on, and the SSP tier's
    # boundary delta exchange (build_ssp_train_step), which sums
    # ceil(bytes / arena_bucket_mb) buckets instead of one delta per leaf
    # (False). The synchronous data-parallel step of build_train_step
    # reads neither field since PR 59: it sums each DENSE gradient leaf by
    # the tap in its own backward. (Packing them into 4 MB buckets, the
    # Bösen contiguous-row analog, cost 6.4 ms of AlexNet's 40.2 ms step
    # on four v5e chips in copies and gates: 12,215 -> 14,262 images/s/chip
    # without it; PERF.md section 6, PR 59.)
    param_arena: bool = True
    arena_bucket_mb: float = 4.0
    # Blocked top-k selection: when set, magnitude/random TOPK picks the
    # top-k within fixed-size blocks of this many elements instead of one
    # global sort — the row-granular spirit of the reference's server, which
    # ranks cheap per-row importance scores rather than every element
    # (server_table.cpp:263-297). A batched top-k over (n_blocks, block) is
    # far cheaper on TPU than lax.top_k over tens of millions of elements.
    topk_block: Optional[int] = None

    def strategy_for(self, layer: str) -> str:
        return self.layer_strategies.get(layer, self.default_strategy)

    def wire_jnp_dtype(self):
        if self.wire_dtype is None:
            return None
        try:
            return WIRE_DTYPES[self.wire_dtype]
        except KeyError:
            raise ValueError(
                f"unknown wire_dtype {self.wire_dtype!r}; "
                f"choose from {sorted(WIRE_DTYPES)}") from None

    @property
    def sync_axes(self) -> tuple:
        """Axis names dense collectives ride, outer (slow) tier first —
        matches the batch layout P((dcn, data)) so tiled all_gathers
        reassemble the global batch in order."""
        if self.dcn_axis is not None:
            return (self.dcn_axis, self.axis)
        return (self.axis,)


def _maybe_mean(g, axes: tuple, reduce: str):
    if reduce == "mean":
        return g / lax.psum(jnp.ones((), g.dtype), axes)
    return g


def wire_psum(g, axes: tuple, reduce: str, wire: Optional[str]):
    """psum with an optional reduced-precision wire: cast the operand to the
    wire dtype so the collective itself moves (and reduces in) half-width
    values — the DenseRowFloat16 trade — then do the mean scaling in f32 and
    cast back to the gradient dtype."""
    wd = WIRE_DTYPES.get(wire) if wire else None
    if wd is None or g.dtype == wd:
        return _maybe_mean(lax.psum(g, axes), axes, reduce)
    s = lax.psum(g.astype(wd), axes).astype(jnp.float32)
    return _maybe_mean(s, axes, reduce).astype(g.dtype)


@functools.lru_cache(maxsize=None)
def _sync_tap(axes: tuple, reduce: str, wire: Optional[str] = None):
    @jax.custom_vjp
    def tap(w):
        return w

    def fwd(w):
        return w, None

    def bwd(_, g):
        return (wire_psum(g, axes, reduce, wire),)

    tap.defvjp(fwd, bwd)
    return tap


@functools.lru_cache(maxsize=None)
def _chained_sync_tap(axes: tuple, reduce: str, wire: Optional[str] = None):
    """Sync tap with an ordering token: identity on (w, token) forward; the
    backward psums the cotangent like ``_sync_tap`` but (a) gates the psum
    operand on the incoming token cotangent and (b) makes the outgoing token
    cotangent depend on the psum result.

    Tokens are threaded through taps in FORWARD layer order (conv1 -> fc8),
    so the cotangent chain runs fc8 -> conv1 — the order gradients
    materialize in backward. Chained psums are dependency-ordered, which
    makes it ILLEGAL for XLA's all-reduce combiner to merge them (a merge
    would create a cycle): the compiled program keeps one distinct,
    schedulable collective per chain stage instead of one giant fused
    all-reduce at the end of backward. This is the fix for the round-3
    degenerate DWBP A/B (XLA merged all 18 per-layer taps into ONE
    all-reduce identical to DENSE_FUSED), restoring
    the reference's per-layer overlap structure (solver.cpp:419-449) at
    bucket granularity (CommConfig.dwbp_bucket_mb).

    The gate is a real data dependency (``where(tok < inf, g, nan)``), not
    an ``optimization_barrier``: barriers are stripped before XLA's
    all-reduce combiner runs (measured on the cpu backend — the
    barrier-chained program still compiled to ONE merged all-reduce), while
    a select on a runtime scalar cannot be folded. The gate is the identity
    whenever the token is finite; a non-finite token means a non-finite
    psum result upstream, and the gate propagates that NaN into every
    earlier bucket so the divergence stays fail-loud instead of collapsing
    into silent zero gradients."""

    @jax.custom_vjp
    def tap(w, tok):
        return w, tok

    def fwd(w, tok):
        return (w, tok), None

    def bwd(_, cts):
        g, g_tok = cts
        gated = jnp.where(g_tok < jnp.inf, g, jnp.full_like(g, jnp.nan))
        s = wire_psum(gated, axes, reduce, wire)
        # outgoing token depends on the psum result; its VALUE is never used
        # numerically (only the dependency), so any finite combine works
        g_tok_out = jnp.minimum(g_tok, s.ravel()[0].astype(g_tok.dtype))
        return s, g_tok_out

    tap.defvjp(fwd, bwd)
    return tap


@functools.lru_cache(maxsize=None)
def _sfb_matmul(axes: tuple, reduce: str, with_bias: bool,
                wire: Optional[str] = None):
    """FC forward on the local shard; backward reconstructs global ∇W from
    all-gathered sufficient factors."""

    def fwd_math(x2, w, b):
        p = policy()
        y = lax.dot_general(
            x2.astype(p.compute_dtype), w.astype(p.compute_dtype),
            (((1,), (1,)), ((), ())),
            precision=matmul_precision())
        if with_bias:
            y = y + b.astype(y.dtype)
        return y

    @jax.custom_vjp
    def matmul(x2, w, b):
        return fwd_math(x2, w, b)

    def fwd(x2, w, b):
        return fwd_math(x2, w, b), (x2, w)

    def bwd(res, g):
        x2, w = res
        p = policy()
        # local input gradient — never leaves the chip
        # custom_vjp bwd is never differentiated through, so forcing f32
        # accumulation here is autodiff-safe (unlike the forward ops).
        gx = lax.dot_general(
            g.astype(p.compute_dtype), w.astype(p.compute_dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=p.accum_dtype,
            precision=matmul_precision()).astype(x2.dtype)
        # sufficient factors: a = top diff (B, M), b = bottom data (B, K);
        # with a wire dtype set the factors cross the interconnect at
        # reduced precision, the local outer product still accumulates f32
        wd = WIRE_DTYPES.get(wire) if wire else None
        g_w = g.astype(wd) if wd is not None and g.dtype != wd else g
        x_w = x2.astype(wd) if wd is not None and x2.dtype != wd else x2
        G = lax.all_gather(g_w, axes, tiled=True)     # (B_global, M)
        X = lax.all_gather(x_w, axes, tiled=True)     # (B_global, K)
        gw = lax.dot_general(
            G.astype(p.compute_dtype), X.astype(p.compute_dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=p.accum_dtype,
            precision=matmul_precision())     # (M, K) — global f32 sum
        gw = _maybe_mean(gw, axes, reduce).astype(w.dtype)
        if with_bias:
            gb = wire_psum(jnp.sum(g, axis=0), axes, reduce, wire)
            return gx, gw, gb
        return gx, gw, None

    matmul.defvjp(fwd, bwd)
    return matmul


def comm_salt(layer: str, pname: str) -> int:
    """Stable per-tensor salt for the random topk policy, so same-shaped
    tensors across layers don't select correlated index subsets (the
    reference's Random UpdateSortPolicy draws independently per table)."""
    import zlib
    return zlib.crc32(f"{layer}/{pname}".encode())


def _blocked_select(flat: jax.Array, scores: jax.Array, k: int,
                    block: int) -> jax.Array:
    """Keep the top-scoring entries *per fixed-size block* — the row-granular
    spirit of the reference server, which ranks cheap per-row importance
    scores instead of sorting every element (server_table.cpp:263-297). A
    batched ``lax.top_k`` over (n_blocks, block) rows is far cheaper on TPU
    than one global top-k over tens of millions of elements.

    The budget is honored from below: kb = k // n_blocks per block (total
    sent <= k; the remainder stays in the error-feedback residual). Callers
    must only take this path when k >= n_blocks — smaller budgets fall back
    to the exact global top-k, which is cheap at tiny k."""
    n = flat.size
    nb = -(-n // block)
    kb = max(1, k // nb)  # per-block budget; total <= k (caller ensures k>=nb)
    pad = nb * block - n
    # pad with -inf scores so padding never wins a slot
    fp = jnp.pad(flat, (0, pad)).reshape(nb, block)
    sp = jnp.pad(scores, (0, pad),
                 constant_values=-jnp.inf).reshape(nb, block)
    _, idx = lax.top_k(sp, kb)                       # (nb, kb)
    rows = jnp.arange(nb)[:, None]
    sent = jnp.zeros_like(fp).at[rows, idx].set(
        jnp.take_along_axis(fp, idx, axis=1))
    return sent.reshape(-1)[:n]


def topk_compress(g: jax.Array, fraction: float, error: jax.Array,
                  policy: str = "magnitude", step=None, salt: int = 0,
                  block: Optional[int] = None,
                  wire: Optional[str] = None):
    """Budgeted sparsification with error feedback.

    Returns (compressed_dense, new_error): ``compressed_dense`` keeps only a
    ``fraction`` of the entries of (g + error); the rest accumulates into the
    error for the next step — the SSPAggr idea of sending the most important
    bytes under a budget, with nothing lost, only delayed. ``policy`` selects
    WHICH entries (the server's UpdateSortPolicy): magnitude (default),
    random, or fixed_order rotation (needs ``step``). ``block`` switches the
    magnitude/random selection to per-block top-k (see ``_blocked_select``).
    ``wire`` additionally quantizes the sent values to the wire dtype, with
    the quantization error folded into the residual (nothing lost)."""
    flat = (g + error).reshape(-1)
    k = max(1, int(flat.size * fraction))
    # blocked selection only when every block gets a budget slot, so the
    # bandwidth contract (<= k entries) holds; tiny-k cases use the exact
    # global top-k, which is cheap at tiny k
    use_block = bool(block) and flat.size > block and \
        k >= -(-flat.size // block)
    if policy == "magnitude":
        if use_block:
            sent = _blocked_select(flat, jnp.abs(flat), k, block)
        else:
            _, idx = lax.top_k(jnp.abs(flat), k)
            vals = flat[idx]
            sent = jnp.zeros_like(flat).at[idx].set(vals)
    elif policy == "random":
        if step is None:
            # a fixed subset every call would strand the complement in the
            # error buffer forever — same contract as fixed_order
            raise ValueError("random policy needs the step counter")
        key = jax.random.fold_in(jax.random.PRNGKey(17 + salt), step)
        scores = jax.random.uniform(key, flat.shape)
        if use_block:
            sent = _blocked_select(flat, scores, k, block)
        else:
            _, idx = lax.top_k(scores, k)
            sent = jnp.zeros_like(flat).at[idx].set(flat[idx])
    elif policy == "fixed_order":
        if step is None:
            raise ValueError("fixed_order policy needs the step counter")
        n_slabs = -(-flat.size // k)  # ceil: full coverage per n_slabs steps
        start = (step % n_slabs) * k
        pos = jnp.arange(flat.size)
        mask = (pos >= start) & (pos < start + k)
        sent = jnp.where(mask, flat, 0.0)
    else:
        raise ValueError(f"unknown topk_policy {policy!r}")
    wd = WIRE_DTYPES.get(wire) if wire else None
    if wd is not None and sent.dtype != wd:
        # quantize to the wire width; the rounding error joins the residual
        sent = sent.astype(wd).astype(flat.dtype)
    new_error = (flat - sent).reshape(g.shape)
    return sent.reshape(g.shape), new_error


class CommContext:
    """Threaded through Net.apply; layers call back into it (core/layers.py)."""

    def __init__(self, cfg: CommConfig):
        self.cfg = cfg
        self._token = None
        self._pending: list = []
        self._bucket_bytes = 0.0

    def begin(self):
        """Reset per-trace chain state. Net.apply calls this at entry: the
        context is shared across traces (loss_fn is retraced by jax.grad,
        scan bodies, debug passes), and a token tracer leaked from a
        previous trace would poison the next one."""
        self._token = None
        self._pending = []
        self._bucket_bytes = 0.0

    def tap_param(self, layer: str, pname: str, w: jax.Array) -> jax.Array:
        # LAYOUT CONTRACT: ``w`` is always the CANONICAL parameter (OIHW
        # conv weights, (M, K=C*H*W) FC weights) — the layout plan presents
        # weights to NHWC convs via dimension numbers, never a reshaped
        # copy, so the cotangent psummed here is canonical under any plan.
        strat = self.cfg.strategy_for(layer)
        if strat in (LOCAL, TOPK, DENSE_FUSED):
            # LOCAL: never synced. TOPK: the trainer compresses + psums the
            # raw local gradient after backward, carrying the error-feedback
            # residual in TrainState.comm_error (trainer.py). DENSE_FUSED:
            # the trainer psums after the whole backward (no-overlap A/B).
            return w
        bucket_mb = self.cfg.dwbp_bucket_mb
        if bucket_mb is None:
            return _sync_tap(self.cfg.sync_axes, self.cfg.reduce,
                             self.cfg.wire_dtype)(w)
        # chained (bucketed DWBP) mode: close the current bucket when this
        # param would overflow it — the next bucket's taps then chain on a
        # token that depends on every psum in this one
        nbytes = w.size * w.dtype.itemsize
        if self._pending and self._bucket_bytes + nbytes > bucket_mb * 1e6:
            tok = self._pending[0]
            for t in self._pending[1:]:
                tok = tok + t
            self._token = tok
            self._pending = []
            self._bucket_bytes = 0.0
        if self._token is None:
            self._token = jnp.zeros((), jnp.float32)
        tap = _chained_sync_tap(self.cfg.sync_axes, self.cfg.reduce,
                                self.cfg.wire_dtype)
        w_out, tok_out = tap(w, self._token)
        self._pending.append(tok_out)
        self._bucket_bytes += nbytes
        return w_out

    def inner_product(self, layer: str, x, w, b) -> Optional[jax.Array]:
        """SFB entry point. LAYOUT CONTRACT: ``x`` arrives in canonical
        NCHW (the net-level layout plan converts at the FC boundary before
        this call — core/net.py), so the flattened bottom factor's K
        ordering always matches the canonical (M, C*H*W) weight. The
        all-gathered sufficient factors and the reconstructed global ∇W
        are therefore layout-portable: a checkpoint written by an NHWC run
        carries the exact same factor/gradient layout as an NCHW run."""
        if self.cfg.strategy_for(layer) != SFB:
            return None
        axes = self.cfg.sync_axes
        wire = self.cfg.wire_dtype
        x2 = x.reshape(x.shape[0], -1)
        if b is not None:
            return _sfb_matmul(axes, self.cfg.reduce, True, wire)(x2, w, b)
        return _sfb_matmul(axes, self.cfg.reduce, False, wire)(
            x2, w, jnp.zeros((w.shape[0],), w.dtype))


def budget_topk_fraction(net, cfg: CommConfig) -> float:
    """Derive the top-k fraction from a per-step bandwidth budget: each sent
    entry costs ~8 bytes (index + value); spread the budget across all TOPK
    layers' parameters."""
    if cfg.bandwidth_budget_mb is None:
        return cfg.topk_fraction
    total = sum(p.count for lname, defs in net.param_defs.items()
                for p in defs if cfg.strategy_for(lname) == TOPK)
    if total == 0:
        return cfg.topk_fraction
    entries = cfg.bandwidth_budget_mb * 1e6 / 8.0
    return float(min(1.0, max(entries / total, 1e-5)))


def auto_strategies(net, min_sfb_rank_saving: float = 2.0) -> Dict[str, str]:
    """SACP-style automatic per-layer choice (the reference hardwires SVB for
    INNER_PRODUCT weights when enabled; we pick by the actual cost model).

    For an FC layer with weight (M, K) and global batch B over N workers:
      dense psum moves  O(M*K)      per worker,
      SFB moves         O(B*(M+K))  per worker (gather both factors).
    Choose SFB when M*K > min_sfb_rank_saving * B*(M+K).
    """
    out: Dict[str, str] = {}
    for layer in net.layers:
        if layer.TYPE != "INNER_PRODUCT":
            continue
        wdef = next((p for p in layer.params if p.name == "w"), None)
        if wdef is None:
            continue
        m, k = wdef.shape
        batch = net.blob_shapes[layer.lp.bottom[0]][0]
        if m * k > min_sfb_rank_saving * batch * (m + k):
            out[layer.name] = SFB
    return out
