"""Iteration-level continuous batching for LLM decode.

The serving economics shift (ROADMAP item 2): a CNN request is one
dispatch, an LLM request is a SEQUENCE of hundreds of decode steps with
wildly varying lengths. Static batching pays the straggler tax — every
admitted batch runs until its LONGEST member finishes while finished rows
ride along as padding and waiting requests queue outside. Continuous
batching re-decides membership every single decode step: finished
sequences retire immediately (their pages return to the
:class:`~poseidon_tpu.serving.kv_pool.PagedKVPool` free list), waiting
sequences admit into the freed rows, and the device never spends a step
on a row nobody needs.

Two phases per sequence, compiled separately (the prefill/decode split):

- **prefill** — the whole prompt in ONE call at a prompt-length bucket
  (flash-attention causal self-attention, O(P) HBM), producing the first
  token's logits and the prompt's K/V, which scatter into the sequence's
  pages;
- **decode** — one token per step for the whole active set at a
  decode-batch RUNG (the smallest compiled batch >= active count), through
  the page-table indirection (``models/generate.py paged_decode_step``).

:class:`ContinuousScheduler` duck-types the :class:`DynamicBatcher`
surface exactly — ``submit`` raising ``ShedError``/``DeadlineError``,
``load_score``/``idle``/``wait_idle``/``close``, the telemetry attrs — so
the fleet's router, failover, rolling reload, and the socket front door
compose UNCHANGED: a replica whose batcher schedules sequences instead of
micro-batches is still just a replica. Failover comes free: a replica
dying mid-generation fans its error to every active sequence's ``submit``,
which re-enters the fleet router and RE-PREFILLS on a survivor.

Per-sequence SLO deadlines ride the batcher deadline machinery: expired in
queue -> ``DeadlineError`` before any compute (the DynamicBatcher
contract); expired mid-generation -> the sequence is cut at the next
iteration boundary (its reply would be late regardless; its pages free
immediately for live sequences).

Thread model: ONE scheduler thread owns the active set, the pool, and the
executor's decode path. Handler threads only touch the bounded queue and
the telemetry counters — both under ``_lock`` (THR004). ``close`` flips
flags under the lock and joins; the loop thread does all cleanup so no
sequence state is ever mutated from two threads.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..runtime.metrics import LatencyWindow, log
from .batcher import DeadlineError, ShedError, ShuttingDownError
from .kv_pool import PagedKVPool, PoolExhausted

__all__ = ["ContinuousScheduler", "GenerateExecutor", "DEFAULT_PAGE_SIZE",
           "DEFAULT_DECODE_RUNGS", "DEFAULT_PROMPT_BUCKETS"]

# KV page size, decode-batch rungs and prompt-length prefill buckets of a
# GenerateExecutor whose caller names none (`serve --generate`).
DEFAULT_PAGE_SIZE = 64
DEFAULT_DECODE_RUNGS = (1, 2, 4, 8)
DEFAULT_PROMPT_BUCKETS = (16, 64, 256)


def _align(n: int, m: int) -> int:
    return -(-int(n) // int(m)) * int(m)


# Cross-instance AOT compile memo: compiled executables are pure (params
# and caches arrive per call, donation is per-execution), so replicas
# with the same (model config, shape, placement) can share them — an
# N-replica fleet warms ONCE per admissible shape instead of N times.
# Keyed on everything that reaches the lowered program: cfg, page
# geometry, tp layout, and the concrete device/mesh placement (compiled
# executables are device-bound).
_COMPILE_MEMO: Dict[tuple, object] = {}
_COMPILE_MEMO_LOCK = threading.Lock()


# --------------------------------------------------------------------------- #
# the decode engine
# --------------------------------------------------------------------------- #


class GenerateExecutor:
    """AOT-compiled transformer decode over a paged KV pool.

    The LLM sibling of :class:`BucketedExecutor`: every admissible shape —
    each prompt bucket's prefill, each decode rung's step — compiles at
    construction with ``jit(...).lower(avals).compile()``; a request only
    ever pays (pad -> dispatch -> slice). Compiled executables are shared
    across instances through a process-wide memo (same model config,
    shape, and placement -> same executable), so an N-replica fleet warms
    once per admissible shape, not N times. The KV pool lives here (it is
    device state); the :class:`ContinuousScheduler` drives it.

    tp-sharded replicas (``mesh_cfg`` with tp > 1): params convert to the
    Megatron head-major layout (``to_tp_layout``) and land as
    ``NamedSharding`` over the PR-10 named (data, fsdp, tp) mesh per
    ``tp_param_specs``; KV pools shard on the HEAD axis (heads divide tp
    by construction), so each rank holds its own heads' pages and GSPMD
    keeps per-head attention local with one psum per block. A replica
    whose "device" is a mesh composes with fleet routing/failover/reload
    unchanged — the fleet only ever sees ``submit``/``swap_params``.
    """

    input_names = ("prompt",)

    def __init__(self, cfg, params, *,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 decode_rungs: Sequence[int] = DEFAULT_DECODE_RUNGS,
                 prompt_buckets: Sequence[int] = DEFAULT_PROMPT_BUCKETS,
                 max_seq_len: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 default_max_new: int = 32,
                 mesh_cfg=None, device=None, warm: bool = True):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.page_size = int(page_size)
        self.decode_rungs = tuple(sorted(set(int(r) for r in decode_rungs)))
        self.prompt_buckets = tuple(sorted(set(int(b)
                                               for b in prompt_buckets)))
        if not self.decode_rungs or self.decode_rungs[0] < 1:
            raise ValueError(f"need positive decode rungs, "
                             f"got {decode_rungs!r}")
        if not self.prompt_buckets or self.prompt_buckets[0] < 1:
            raise ValueError(f"need positive prompt buckets, "
                             f"got {prompt_buckets!r}")
        self.default_max_new = int(default_max_new)
        self.max_seq_len = int(max_seq_len or cfg.max_seq)
        if self.max_seq_len > cfg.max_seq:
            raise ValueError(f"max_seq_len {self.max_seq_len} exceeds the "
                             f"model's learned positions {cfg.max_seq}")
        if max(self.prompt_buckets) >= self.max_seq_len:
            raise ValueError(f"largest prompt bucket "
                             f"{max(self.prompt_buckets)} leaves no room "
                             f"to generate within {self.max_seq_len}")

        # ---- placement: one device, or a named mesh ---------------------- #
        self.device = device
        self.mesh = None
        self._tp_layout = False
        pool_shardings = None
        if mesh_cfg is not None and mesh_cfg.active:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..models.transformer import to_tp_layout, tp_param_specs
            from ..parallel.spmd import named_mesh
            if device is not None:
                raise ValueError("pass device= or mesh_cfg=, not both")
            if mesh_cfg.tp > 1 and (cfg.n_heads % mesh_cfg.tp
                                    or cfg.d_ff % mesh_cfg.tp):
                raise ValueError(
                    f"n_heads={cfg.n_heads} and d_ff={cfg.d_ff} must both "
                    f"divide tp={mesh_cfg.tp}")
            self.mesh = named_mesh(mesh_cfg)
            self.mesh_cfg = mesh_cfg
            self._tp_layout = mesh_cfg.tp > 1
            if self._tp_layout:
                params_dev = to_tp_layout(
                    jax.tree_util.tree_map(jnp.asarray, params), cfg)
                specs = tp_param_specs(params_dev, tp_axis="tp")
                self._param_shardings = jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s), specs,
                    is_leaf=lambda x: isinstance(x, P))
                params_dev = jax.tree_util.tree_map(
                    jax.device_put, params_dev, self._param_shardings)
                pool_shardings = NamedSharding(
                    self.mesh, P(None, "tp", None, None))
            else:
                self._param_shardings = None
                params_dev = jax.tree_util.tree_map(
                    lambda v: jax.device_put(
                        jnp.asarray(v), NamedSharding(self.mesh, P())),
                    params)
        else:
            self.mesh_cfg = None
            self._param_shardings = None
            if device is not None:
                params_dev = jax.device_put(
                    jax.tree_util.tree_map(jnp.asarray, params), device)
            else:
                params_dev = jax.tree_util.tree_map(jnp.asarray, params)
        self._params = params_dev

        # ---- the pool ---------------------------------------------------- #
        pages_per_seq = -(-self.max_seq_len // self.page_size)
        if num_pages is None:
            # every row of the largest rung can hold a max-length sequence
            num_pages = self.decode_rungs[-1] * pages_per_seq + 1
        self.pool = PagedKVPool(cfg, num_pages=num_pages,
                                page_size=self.page_size,
                                max_seq_len=self.max_seq_len,
                                device=device, shardings=pool_shardings)

        self._swap_lock = threading.Lock()
        # make_batcher() reads this so a fleet built from stock Replica
        # plumbing can run the static A/B control arm (bench serving_llm)
        self.scheduler_mode = "continuous"
        self.params_version = 0
        self.rows_served = 0          # tokens delivered to completed rows
        self.prefills = 0
        self.decode_calls: Dict[int, int] = {r: 0 for r in self.decode_rungs}

        # ---- AOT compile every admissible shape -------------------------- #
        self._compiled_prefill: Dict[int, object] = {}
        self._compiled_decode: Dict[int, object] = {}
        if warm:
            self.warm()

    # ---- compile cache ---------------------------------------------------- #
    def _aval(self, shape, dtype, spec=None):
        import jax
        import jax.numpy as jnp
        kw = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            kw["sharding"] = NamedSharding(self.mesh, spec or P())
        return jax.ShapeDtypeStruct(tuple(shape), dtype, **kw)

    def warm(self) -> None:
        """AOT-compile prefill at every prompt bucket and decode at every
        rung (construction IS the warm-up, the fleet's WARMING phase)."""
        import contextlib

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..models.generate import paged_decode_step, prefill_cached

        cfg, tp_layout = self.cfg, self._tp_layout
        params_avals = jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=v.sharding
                                           if self.mesh is not None
                                           else None),
            self._params)
        ctx = (jax.default_device(self.device) if self.device is not None
               else contextlib.nullcontext())
        head_spec = P(None, "tp", None, None) if tp_layout else P()
        if self.mesh is not None:
            placement = ("mesh", tuple(str(d) for d in
                                       self.mesh.devices.flat),
                         tuple(self.mesh.axis_names),
                         self.mesh.devices.shape)
        else:
            placement = ("dev", str(self.device
                                    if self.device is not None
                                    else jax.devices()[0]))
        base_key = (repr(cfg), self.page_size, tp_layout, placement)
        with ctx:
            for pb in self.prompt_buckets:
                if pb in self._compiled_prefill:
                    continue
                total = _align(pb, self.page_size)

                def pf(p, toks, last_idx, _total=total):
                    return prefill_cached(p, cfg, toks, last_idx, _total,
                                          tp_layout=tp_layout)

                key = base_key + ("prefill", pb, total)
                with _COMPILE_MEMO_LOCK:
                    fn = _COMPILE_MEMO.get(key)
                    if fn is None:
                        fn = jax.jit(pf).lower(
                            params_avals,
                            self._aval((1, pb), jnp.int32),
                            self._aval((1,), jnp.int32)).compile()
                        _COMPILE_MEMO[key] = fn
                self._compiled_prefill[pb] = fn
            cache_shape = tuple(self.pool.caches[0][0].shape)
            cache_aval = tuple(
                (self._aval(cache_shape, jnp.float32, head_spec),) * 2
                for _ in range(cfg.n_layers))
            for r in self.decode_rungs:
                if r in self._compiled_decode:
                    continue

                def dec(p, tok, caches, table, pos):
                    return paged_decode_step(p, cfg, tok, caches, table,
                                             pos, tp_layout=tp_layout)

                key = base_key + ("decode", r, cache_shape,
                                  self.pool.max_pages_per_seq)
                with _COMPILE_MEMO_LOCK:
                    fn = _COMPILE_MEMO.get(key)
                    if fn is None:
                        fn = jax.jit(dec, donate_argnums=(2,)).lower(
                            params_avals,
                            self._aval((r,), jnp.int32),
                            cache_aval,
                            self._aval((r, self.pool.max_pages_per_seq),
                                       jnp.int32),
                            self._aval((r,), jnp.int32)).compile()
                        _COMPILE_MEMO[key] = fn
                self._compiled_decode[r] = fn

    def prompt_bucket_for(self, p: int) -> int:
        for b in self.prompt_buckets:
            if p <= b:
                return b
        raise ValueError(f"prompt of {p} tokens exceeds the largest "
                         f"prompt bucket {self.prompt_buckets[-1]}")

    def rung_for(self, n: int) -> int:
        for r in self.decode_rungs:
            if n <= r:
                return r
        raise ValueError(f"{n} active rows exceed the largest decode "
                         f"rung {self.decode_rungs[-1]}")

    @property
    def max_batch(self) -> int:
        """Largest decode rung — the scheduler's active-set capacity (and
        the fleet router's load_score denominator)."""
        return self.decode_rungs[-1]

    def reserve_len(self, p: int, max_new: int) -> int:
        """Positions a request reserves pages for: the page-aligned
        prefill region and the last generated position, whichever is
        larger (reserve-at-admission — see kv_pool)."""
        return max(_align(self.prompt_bucket_for(p), self.page_size),
                   p + max_new)

    # ---- the two phases --------------------------------------------------- #
    def prefill(self, prompt: np.ndarray) -> np.ndarray:
        """Run one prompt (1-D int32) through the bucketed prefill and
        scatter nothing — returns (logits (V,), dense caches) for the
        scheduler to hand to ``pool.write_prefill``."""
        import jax.numpy as jnp
        p = int(prompt.shape[0])
        bucket = self.prompt_bucket_for(p)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :p] = np.asarray(prompt, np.int32)
        params = self._params           # one atomic read: swap-safe
        logits, caches = self._compiled_prefill[bucket](
            params, jnp.asarray(toks),
            jnp.asarray([p - 1], jnp.int32))
        self.prefills += 1
        return np.asarray(logits)[0], caches

    def decode(self, tok: np.ndarray, table: np.ndarray,
               pos: np.ndarray) -> np.ndarray:
        """One decode step for a full rung: tok/pos (R,), table
        (R, max_pages). Returns logits (R, V); the pool's caches update
        in place (donated)."""
        import jax.numpy as jnp
        r = int(tok.shape[0])
        if r not in self._compiled_decode:
            raise ValueError(f"no compiled decode rung of size {r} "
                             f"(rungs {self.decode_rungs})")
        params = self._params
        logits, new_caches = self._compiled_decode[r](
            params, jnp.asarray(tok, jnp.int32), self.pool.caches,
            jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32))
        self.pool.caches = new_caches
        self.decode_calls[r] += 1
        return np.asarray(logits)

    # ---- the fleet hooks --------------------------------------------------- #
    def make_batcher(self, max_delay_s: float = 0.005,
                     max_queue: int = 64) -> "ContinuousScheduler":
        """Replica._attach_batcher's executor-provided batcher: an LLM
        replica schedules sequences, not micro-batches. ``max_delay_s`` is
        accepted for signature compatibility and unused — continuous
        batching re-decides membership every step, so no request ever
        waits for batch company."""
        del max_delay_s
        return ContinuousScheduler(self, max_queue=max_queue,
                                   mode=self.scheduler_mode)

    def swap_params(self, new_params: Dict) -> int:
        """Rolling-reload contract (same as BucketedExecutor): validate
        the incoming STANDARD-layout tree against the serving one, convert
        to this replica's layout/placement, swap atomically. The compiled
        executables are shape-keyed, so a swap never recompiles."""
        import jax
        import jax.numpy as jnp

        new_params = jax.tree_util.tree_map(jnp.asarray, new_params)
        if self._tp_layout:
            from ..models.transformer import to_tp_layout
            new_params = to_tp_layout(new_params, self.cfg)
        cur_leaves, cur_tree = jax.tree_util.tree_flatten(self._params)
        new_leaves, new_tree = jax.tree_util.tree_flatten(new_params)
        if cur_tree != new_tree:
            raise ValueError("params tree structure mismatch: the snapshot "
                             "was taken from a different model")
        for c, n in zip(cur_leaves, new_leaves):
            if c.shape != n.shape or c.dtype != n.dtype:
                raise ValueError(
                    f"params leaf mismatch: {n.shape}/{n.dtype} vs serving "
                    f"{c.shape}/{c.dtype}")
        if self._param_shardings is not None:
            new_params = jax.tree_util.tree_map(
                jax.device_put, new_params, self._param_shardings)
        elif self.device is not None:
            new_params = jax.device_put(new_params, self.device)
        with self._swap_lock:
            self._params = new_params
            self.params_version += 1
            return self.params_version

    def snapshot(self) -> Dict:
        return {
            "page_size": self.page_size,
            "decode_rungs": list(self.decode_rungs),
            "prompt_buckets": list(self.prompt_buckets),
            "prefills": self.prefills,
            "decode_calls": dict(self.decode_calls),
            "pool": self.pool.snapshot(),
            "mesh": (self.mesh_cfg.describe()
                     if self.mesh_cfg is not None else None),
        }


# --------------------------------------------------------------------------- #
# the scheduler
# --------------------------------------------------------------------------- #


class _GenSeq:
    """One in-flight generation request (queued or active)."""
    __slots__ = ("prompt", "max_new", "eos_id", "deadline", "enqueued",
                 "event", "result", "error", "cancelled", "stream",
                 "seq_id", "pos", "next_tok", "out_tokens")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 eos_id: Optional[int], deadline: Optional[float],
                 stream=None):
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.deadline = deadline            # absolute monotonic, or None
        self.enqueued = time.monotonic()
        self.event = threading.Event()
        self.result: Optional[Dict] = None
        self.error: Optional[BaseException] = None
        self.cancelled = False
        self.stream = stream                # optional cumulative-tokens cb
        self.seq_id: Optional[int] = None   # set at admission
        self.pos = 0                        # abs position of next_tok
        self.next_tok = 0                   # last token, not yet fed back
        self.out_tokens: List[int] = []


class ContinuousScheduler:
    """Queue -> admit/retire every decode step -> fan results back out.

    Duck-types :class:`DynamicBatcher` (see module docstring) over a
    :class:`GenerateExecutor`. ``mode="static"`` is the A/B control arm:
    sequences admit only into an EMPTY active set and no admission happens
    until the whole batch drains — classic static batching, stragglers
    and all. Everything else (pool, deadlines, retirement) is identical,
    so the bench's continuous-vs-static delta isolates iteration-level
    scheduling itself."""

    def __init__(self, executor: GenerateExecutor, max_queue: int = 64,
                 mode: str = "continuous"):
        if mode not in ("continuous", "static"):
            raise ValueError(f"mode must be continuous|static, got {mode!r}")
        self.executor = executor
        self.max_queue = int(max_queue)
        self.max_batch = executor.max_batch
        self.mode = mode
        self._q: deque = deque()
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._closing = False
        self._drain = True
        self._seq_counter = 0
        self._active: List[_GenSeq] = []    # loop-thread-owned
        self._n_active = 0                  # lock-guarded mirror for stats
        # telemetry (the DynamicBatcher surface the fleet snapshot reads)
        self.latency = LatencyWindow()
        self.shed_count = 0
        self.deadline_expired = 0
        self.batches = 0                    # decode iterations dispatched
        self.batched_rows = 0               # active rows across iterations
        self.admitted = 0
        self.retired = 0
        self._fill_sum = 0.0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    # ---- submission side -------------------------------------------------- #
    def validate_request(self, inputs: Dict) -> int:
        """Admission-time validation: reject malformed requests with THEIR
        error before they hold a queue slot."""
        if "prompt" not in inputs:
            raise ValueError("request missing input 'prompt'")
        prompt = np.asarray(inputs["prompt"])
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError(f"prompt must be a non-empty 1-D int array, "
                             f"got shape {prompt.shape}")
        p = int(prompt.shape[0])
        max_new = int(inputs.get("max_new", self.executor.default_max_new))
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        ex = self.executor
        total = ex.reserve_len(p, max_new)      # raises on oversized prompt
        if total > ex.pool.max_seq_len:
            raise ValueError(
                f"prompt {p} + max_new {max_new} exceeds the pool's "
                f"max_seq_len {ex.pool.max_seq_len}")
        if ex.pool.pages_for(total) > ex.pool.num_pages - 1:
            raise ValueError(
                f"request needs {ex.pool.pages_for(total)} pages; the "
                f"whole pool holds {ex.pool.num_pages - 1}")
        return 1

    def submit(self, inputs: Dict, deadline_s: Optional[float] = None,
               timeout_s: float = 30.0) -> Dict:
        """Enqueue one generation request and block until it completes.
        Returns ``{"tokens": (n,) int32, "n_new": n, "prompt_len": p}``.
        Raises ShedError on a full queue, DeadlineError on SLO expiry,
        ValueError on malformed inputs — the DynamicBatcher contract."""
        t0 = time.monotonic()
        self.validate_request(inputs)
        # copy, not asarray: a codec-decoded prompt is a zero-copy VIEW
        # into its receive buffer, and a queued sequence would pin that
        # whole frame for its lifetime — detach it at admission
        prompt = np.array(inputs["prompt"], np.int32)
        max_new = int(inputs.get("max_new", self.executor.default_max_new))
        eos_id = inputs.get("eos_id")
        eos_id = None if eos_id is None else int(eos_id)
        deadline = None if deadline_s is None else t0 + float(deadline_s)
        req = _GenSeq(prompt, max_new, eos_id, deadline,
                      stream=inputs.get("stream"))
        with self._lock:
            if self._closing:
                raise ShuttingDownError("scheduler is shutting down")
            if len(self._q) >= self.max_queue:
                self.shed_count += 1
                raise ShedError(
                    f"queue full ({self.max_queue} requests queued)")
            self._q.append(req)
            self._wake.notify()
        if not req.event.wait(timeout_s):
            with self._lock:
                req.cancelled = True
                try:
                    self._q.remove(req)
                except ValueError:
                    pass                # already admitted; loop skips it
            raise TimeoutError(f"no reply within {timeout_s}s "
                               f"(scheduler wedged?)")
        if req.error is not None:
            raise req.error
        self.latency.record(time.monotonic() - t0)
        return req.result

    # ---- DynamicBatcher surface ------------------------------------------- #
    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._q)

    @property
    def inflight_rows(self) -> int:
        with self._lock:
            return self._n_active

    def load_score(self) -> float:
        with self._lock:
            return len(self._q) + self._n_active / self.max_batch

    def idle(self) -> bool:
        with self._lock:
            return not self._q and self._n_active == 0

    def wait_idle(self, timeout_s: float = 30.0,
                  poll_s: float = 0.005) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.idle():
                return True
            time.sleep(poll_s)
        return self.idle()

    def fill_ratio(self) -> Optional[float]:
        with self._lock:
            if not self.batches:
                return None
            return self._fill_sum / self.batches

    # ---- loop-thread internals -------------------------------------------- #
    def _complete(self, seq: _GenSeq, *, error: Optional[BaseException]
                  = None) -> None:
        """Retire one sequence: free its pages IMMEDIATELY, hand the
        submitter its result/error. Loop-thread only."""
        if seq.seq_id is not None:
            self.executor.pool.free(seq.seq_id)
        with self._lock:
            self.retired += 1
        if error is not None:
            seq.error = error
        else:
            toks = np.asarray(seq.out_tokens, np.int32)
            seq.result = {"tokens": toks, "n_new": int(toks.shape[0]),
                          "prompt_len": int(seq.prompt.shape[0])}
            self.executor.rows_served += int(toks.shape[0])
        seq.event.set()

    def _emit_stream(self, seq: _GenSeq) -> None:
        if seq.stream is None:
            return
        try:
            seq.stream(list(seq.out_tokens))
        except Exception:  # noqa: BLE001 — a broken stream sink must not
            seq.stream = None           # kill the sequence or the loop

    def _try_admit(self) -> bool:
        """Admit queued sequences into free active rows while pages last.
        Returns True if anything was admitted. Loop-thread only."""
        admitted = False
        with self._lock:
            # static mode gang-admits: a batch only FORMS into an empty
            # active set (but fills to the full rung within this round),
            # then runs to completion before the next batch — the honest
            # static-batching baseline, not a serial one
            gang_open = not self._active
        while True:
            with self._lock:
                if not self._q:
                    break
                if self.mode == "static" and not gang_open:
                    break               # a static batch is mid-flight
                if len(self._active) >= self.max_batch:
                    break
                req = self._q[0]
                if req.cancelled:
                    self._q.popleft()
                    continue
                now = time.monotonic()
                if req.deadline is not None and now > req.deadline:
                    self._q.popleft()
                    self.deadline_expired += 1
                    req.error = DeadlineError(
                        f"deadline expired after "
                        f"{now - req.enqueued:.3f}s in queue")
                    req.event.set()
                    continue
                total = self.executor.reserve_len(
                    int(req.prompt.shape[0]), req.max_new)
                if not self.executor.pool.can_admit(total):
                    break               # wait for retirements to free pages
                self._q.popleft()
                self._seq_counter += 1
                req.seq_id = self._seq_counter
            # pool alloc + prefill OUTSIDE the lock (device work)
            try:
                self.executor.pool.alloc(req.seq_id, total)
                logits, caches = self.executor.prefill(req.prompt)
                self.executor.pool.write_prefill(req.seq_id, caches)
            except PoolExhausted as e:
                # raced a stats reader's view; requeue and retry later
                self.executor.pool.free(req.seq_id)
                with self._lock:
                    self._q.appendleft(req)
                log(f"serving: admission raced the pool: {e}")
                break
            except BaseException as e:  # noqa: BLE001 — fan out, reroute
                self._complete(req, error=e)
                continue
            tok0 = int(np.argmax(logits))
            req.out_tokens.append(tok0)
            req.pos = int(req.prompt.shape[0])
            req.next_tok = tok0
            self._emit_stream(req)
            with self._lock:
                self.admitted += 1
            if (req.eos_id is not None and tok0 == req.eos_id) \
                    or req.max_new <= 1:
                self._complete(req)
            else:
                with self._lock:
                    self._active.append(req)
                    self._n_active = len(self._active)
            admitted = True
        return admitted

    def _decode_iteration(self) -> None:
        """One iteration: a single decode step for the whole active set at
        the smallest compiled rung, then per-row retirement. Loop-thread
        only."""
        act = self._active
        rung = self.executor.rung_for(len(act))
        tok = np.zeros((rung,), np.int32)
        pos = np.zeros((rung,), np.int32)
        seq_ids: List[Optional[int]] = [s.seq_id for s in act]
        seq_ids += [None] * (rung - len(act))
        for i, s in enumerate(act):
            tok[i] = s.next_tok
            pos[i] = s.pos
        table = self.executor.pool.table(seq_ids)
        try:
            logits = self.executor.decode(tok, table, pos)
        except BaseException as e:  # noqa: BLE001 — replica failure: fan
            # the error to every active sequence; each submit re-enters
            # the fleet router and re-prefills on a survivor
            for s in act:
                self._complete(s, error=e)
            with self._lock:
                self._active = []
                self._n_active = 0
            return
        with self._lock:
            self.batches += 1
            self.batched_rows += len(act)
            self._fill_sum += len(act) / rung
        now = time.monotonic()
        still: List[_GenSeq] = []
        for i, s in enumerate(act):
            new_tok = int(np.argmax(logits[i]))
            s.out_tokens.append(new_tok)
            s.pos += 1
            s.next_tok = new_tok
            self._emit_stream(s)
            if s.cancelled:
                self._complete(s, error=RuntimeError("cancelled"))
                continue
            done = (s.eos_id is not None and new_tok == s.eos_id) \
                or len(s.out_tokens) >= s.max_new
            if done:
                self._complete(s)
            elif s.deadline is not None and now > s.deadline:
                with self._lock:
                    self.deadline_expired += 1
                self._complete(s, error=DeadlineError(
                    f"SLO deadline expired mid-generation after "
                    f"{len(s.out_tokens)} tokens"))
            else:
                still.append(s)
        with self._lock:
            self._active = still
            self._n_active = len(still)

    def _loop(self) -> None:
        while True:
            with self._lock:
                while not self._q and not self._active and not self._closing:
                    self._wake.wait(timeout=0.25)
                closing, drain = self._closing, self._drain
                empty = not self._q and not self._active
            if closing and empty:
                return
            if closing and not drain:
                # complete leftovers (queued AND mid-generation) with the
                # typed shutdown shed so fleet submits reroute, free pages
                with self._lock:
                    leftovers = list(self._q)
                    self._q.clear()
                    act, self._active = self._active, []
                    self._n_active = 0
                for s in leftovers + act:
                    self._complete(s, error=ShuttingDownError(
                        "server shut down before completion"))
                return
            self._try_admit()
            if self._active:
                self._decode_iteration()

    # ---- shutdown ---------------------------------------------------------- #
    def close(self, drain: bool = True, timeout_s: float = 30.0) -> None:
        """Refuse new submissions; with ``drain`` finish everything
        admitted AND queued, else complete leftovers with the shutdown
        shed. Idempotent."""
        with self._lock:
            self._closing = True
            self._drain = drain
            self._wake.notify_all()
        self._thread.join(timeout=timeout_s)

    def snapshot(self) -> Dict:
        with self._lock:
            snap = {
                "mode": self.mode,
                "queue_depth": len(self._q),
                "active": self._n_active,
                "admitted": self.admitted,
                "retired": self.retired,
                "batches": self.batches,
                "batched_rows": self.batched_rows,
                "shed": self.shed_count,
                "deadline_expired": self.deadline_expired,
            }
        snap["fill"] = self.fill_ratio()
        snap["latency"] = self.latency.summary()
        snap["executor"] = self.executor.snapshot()
        return snap
