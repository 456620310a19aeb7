"""Per-layer MEASURED conv lowering strategy (Caffe con Troll's regime).

Caffe con Troll (arXiv:1504.04343) showed that choosing the convolution
lowering per layer from short measured runs — not one global policy — is
worth 3-4x in exactly the small-filter CNN regime these nets live in: the
lane-starved stem wants the space-to-depth rewrite, a 3x3 body may prefer
the direct MXU lowering, and a 1x1 inception branch is a plain GEMM that
im2col reaches without window machinery. This module is that optimizer for
the ``conv_strategy="auto"`` axis:

- **candidates** come from ``ops/nn.conv_strategy_applicable`` (never a
  strategy that cannot lower the layer);
- **measurement** is a short fwd+bwd micro-run per candidate on the
  layer's true (C, H, W, k, s, p, group) geometry at a clipped micro
  batch, min-wall over a few repeats (the one-sided-noise estimator
  bench.py uses);
- **the decision is made once** per (layer shape, backend, device kind,
  compute dtype): an in-process memo serves repeated layers (GoogLeNet's
  repeated inception branches measure once), and the winner document is
  persisted through ``runtime/compile_cache.py``'s tuned store so a
  restarted — or elastically admitted — process skips the measurement
  entirely.

``core/net.py`` calls :func:`resolve` for every conv layer when the net is
constructed under ``conv_strategy="auto"`` and prints the measured table;
explicit strategies bypass this module.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

# strategies "auto" may choose between (legacy "" is not a candidate: it
# just defers to the global conv_s2d policy)
CANDIDATES = ("direct", "im2col", "s2d")

MICRO_BATCH = 4      # micro-run batch: enough to load the MXU, cheap to jit
MICRO_ITERS = 2      # timed calls per interleaved window
TRIAL_WINDOWS = 3    # interleaved windows per candidate (min-of-k)
TRIAL_WARMUP = 2     # un-timed calls per candidate before ANY timing

_NAMESPACE = "conv_strategy"
_memo: Dict[str, Dict] = {}


def clear_memo() -> None:
    """Test hook: drop the in-process decisions (NOT the persisted ones)."""
    _memo.clear()


def strategy_key(parts: Dict) -> str:
    from ..runtime.compile_cache import step_key
    return step_key(kind=_NAMESPACE, **parts)


def _key_parts(c: int, h: int, w: int, kernel: Tuple[int, int],
               stride: Tuple[int, int], pad: Tuple[int, int], group: int,
               out_ch: int, layout: str, micro_batch: int) -> Dict:
    import jax

    from ..config import policy
    return {
        "c": c, "h": h, "w": w,
        "kh": kernel[0], "kw": kernel[1],
        "sh": stride[0], "sw": stride[1],
        "ph": pad[0], "pw": pad[1],
        "group": group, "out_ch": out_ch, "layout": layout,
        "micro_batch": micro_batch,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "compute_dtype": str(policy().compute_dtype.__name__
                             if hasattr(policy().compute_dtype, "__name__")
                             else policy().compute_dtype),
    }


def _micro_arrays(c, h, w, kernel, group, out_ch, layout, micro_batch):
    import jax
    import jax.numpy as jnp
    x_shape = ((micro_batch, h, w, c) if layout == "NHWC"
               else (micro_batch, c, h, w))
    kx, kw_, kb = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, x_shape, jnp.float32)
    wgt = jax.random.normal(kw_, (out_ch, c // group) + tuple(kernel),
                            jnp.float32) * 0.05
    b = jax.random.normal(kb, (out_ch,), jnp.float32) * 0.05
    return x, wgt, b


def _make_step(strategy: str, x, wgt, b, stride, pad, group,
               layout: str):
    """One candidate's jitted fwd+bwd (dx AND dw — both matter in
    training) as a zero-arg blocked callable for the interleaved timer."""
    import jax
    import jax.numpy as jnp

    from . import nn as NN

    def loss(x_, w_, b_):
        y = NN.conv2d(x_, w_, b_, stride, pad, group, layout=layout,
                      strategy=strategy)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    step = jax.jit(jax.grad(loss, argnums=(0, 1)))

    def run():
        jax.block_until_ready(step(x, wgt, b))

    return run


def _measure_candidates(cands, x, wgt, b, stride, pad, group,
                        layout: str) -> Dict[str, float]:
    """Trial hygiene (the bench.py ``pipeline_speedup`` estimator idiom):
    EVERY candidate warms TRIAL_WARMUP times before any timing — the first
    call pays trace+compile and the second can still pay one-time runtime
    work, and neither may decide a tuned winner — then candidates run in
    interleaved order-alternating windows with a min-of-k estimator, so
    host-load drift during the micro-run cannot bias one strategy."""
    from ..runtime.tuned_plan import interleaved_min_ms
    fns = {s: _make_step(s, x, wgt, b, stride, pad, group, layout)
           for s in cands}
    return interleaved_min_ms(fns, windows=TRIAL_WINDOWS,
                              iters=MICRO_ITERS, warmup=TRIAL_WARMUP)


def resolve(name: str, c: int, h: int, w: int, kernel: Tuple[int, int],
            stride: Tuple[int, int], pad: Tuple[int, int], group: int,
            out_ch: int, layout: str, batch: int,
            cache_dir: Optional[str] = None) -> Dict:
    """The decision document for one conv layer geometry:
    ``{"winner", "timings_ms", "source", "key", ...}`` where ``source`` is
    "memo" | "persisted" | "measured" | "only-candidate". ``name`` is
    informational (the first layer that triggered the measurement); the
    key is purely geometric, so shape-identical layers share."""
    from . import nn as NN
    if cache_dir is None:
        from ..config import compile_cache_config
        cache_dir = compile_cache_config().cache_dir
        if not cache_dir:
            # a TunedPlan auto-load (runtime/tuned_plan.py) that resolved
            # conv_strategy="auto" points here at the plan's own store, so
            # the per-layer winners the tune run persisted memo-hit even
            # in a process that never enabled the compile cache
            from ..runtime.tuned_plan import active_store_dir
            cache_dir = active_store_dir()

    micro_batch = max(1, min(batch, MICRO_BATCH))
    parts = _key_parts(c, h, w, kernel, stride, pad, group, out_ch, layout,
                       micro_batch)
    key = strategy_key(parts)
    if key in _memo:
        return dict(_memo[key], source="memo")

    from ..runtime.compile_cache import load_tuned, save_tuned
    doc = load_tuned(cache_dir, _NAMESPACE, key)
    if doc is not None and doc.get("winner") in CANDIDATES:
        _memo[key] = doc
        return dict(doc, source="persisted")

    x, wgt, b = _micro_arrays(c, h, w, kernel, group, out_ch, layout,
                              micro_batch)
    cands = [s for s in CANDIDATES
             if NN.conv_strategy_applicable(s, x, wgt, stride, group,
                                            layout)]
    doc = {"key": key, "layer": name, "parts": parts, "timings_ms": {}}
    if len(cands) == 1:
        doc.update(winner=cands[0], source="only-candidate")
    else:
        timings = _measure_candidates(cands, x, wgt, b, stride, pad, group,
                                      layout)
        doc["timings_ms"] = {s: round(ms, 4) for s, ms in timings.items()}
        doc.update(
            winner=min(doc["timings_ms"], key=doc["timings_ms"].get),
            source="measured",
            measured_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
        save_tuned(cache_dir, _NAMESPACE, key, doc)
    _memo[key] = doc
    return dict(doc)


def describe(doc: Dict) -> str:
    """One human line per decision, for the construction-time table."""
    times = " | ".join(f"{s} {ms:.3f}ms"
                       for s, ms in sorted(doc.get("timings_ms", {}).items(),
                                           key=lambda kv: kv[1]))
    return (f"{doc.get('layer', '?')}: -> {doc['winner']} "
            f"[{doc.get('source', '?')}]"
            + (f" ({times})" if times else ""))
