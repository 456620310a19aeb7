"""Global numeric policy (the TPU analog of Caffe's Dtype template parameter).

Parameters and optimizer state stay float32. Forward/backward matmul and conv
inputs are cast to ``compute_dtype`` (bfloat16 for TPU perf configs; the MXU
accumulates bf16 products in f32 internally) and produce compute-dtype
activations — forcing f32 outputs via preferred_element_type breaks conv
transposes under autodiff, so it is used only where autodiff never looks:
custom_vjp backward dots (SFB gradient reconstruction) and softmax/online-
softmax statistics, which are always f32 (``accum_dtype``). Set compute dtype
to float32 (the default) for Caffe-parity numerics; matmul precision is then
forced to HIGHEST (see ``matmul_precision``).

This module owns the jax dependency; ``config`` re-exports everything here
lazily so the socket-tier processes (async-SSP workers, the fault proxy)
can import ``poseidon_tpu`` without paying the jax import.
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass

import jax.numpy as jnp


@dataclass
class Policy:
    param_dtype: object = jnp.float32
    compute_dtype: object = jnp.float32  # flipped to bfloat16 by perf configs
    accum_dtype: object = jnp.float32
    # Internal activation layout — a GRAPH-level choice, not a per-op one:
    # core/net.py reads this at Net construction (overridable per net via
    # Net(conv_layout=...)) and plans the WHOLE graph in that layout —
    # "NHWC" runs every conv/pool/LRN/elementwise/concat natively
    # channels-last (TPU-preferred) and converts only at genuine
    # boundaries (FC flatten, blob export). The external/prototxt contract
    # stays NCHW: logical shapes, params, grads and checkpoints are always
    # canonical, so snapshots are layout-portable. Ops take explicit
    # layout arguments; nothing reads this field at trace time. (The old
    # per-op transpose shim this replaces lost 1.9x: its boundary pairs
    # did not cancel across pool/LRN/concat seams.)
    conv_layout: str = "NCHW"
    # "auto" (what `train` sets unless --conv_layout names a plan) resolves
    # per-backend at Net construction (resolve_conv_layout: NHWC on the TPU
    # and the GPU, NCHW on the CPU); explicit "nchw"/"nhwc" always win.
    # Space-to-depth stem transform: rewrite few-channel strided convs
    # (AlexNet/GoogLeNet conv1: 3 input channels use 3/128 MXU lanes) as an
    # exact stride-1 conv over s*s-times more channels. Mathematically
    # exact up to float summation order; off by default so golden-value
    # tests compare the direct formulation.
    conv_s2d: bool = False
    # Conv lowering strategy: "" = conv_s2d decides; "direct" | "im2col" |
    # "s2d" forces one strategy net-wide. Net(conv_strategy=...) overrides
    # per net.
    conv_strategy: str = ""


# --bf16 accuracy guardrail (the documented tolerance the LeNet smoke in
# tests/test_kernels.py pins): after BF16_SMOKE_ITERS LeNet steps on
# identical data, the mean of the last 5 bf16 losses must sit within
# BF16_SMOKE_RTOL (relative) + BF16_SMOKE_ATOL (absolute) of the f32 run's.
# Parameters/optimizer state/softmax statistics stay f32 under the bf16
# policy, so the trajectories track closely — drift beyond this band means
# a kernel is accumulating below f32 somewhere it must not.
BF16_SMOKE_ITERS = 30
BF16_SMOKE_RTOL = 0.10
BF16_SMOKE_ATOL = 0.05


def resolve_conv_layout(layout: str, backend: str = None) -> str:
    """Resolve a conv_layout choice ("NCHW" | "NHWC" | "auto") against the
    backend actually running the net. "auto" is this table:

    - **tpu**: NHWC since PR 55. On the v5e the channels-last plan takes
      conv1's weight gradient (K = 3 x 11 x 11 on a 128-wide MXU; 2.12 ms
      of AlexNet's 29 ms step under NCHW, 1.09 under the plan) out of the
      step's largest ops: +3.4% images/s/chip in alexnet.resident, +2.6%
      in alexnet.dp4.resident, +1.2% in googlenet.lmdb, a warm start's
      setup_s level (builder's chip runs, PR 55, the parent's archive
      against the committed files; PERF.md section 6; the driver's pairs
      are PR 55's lines of PERF_LEDGER.jsonl). The LRN and pool kernels
      run the same blocks under both plans (PRs 33, 35), which is what
      turned the 0.53x of the first chip A/B (July 2026, before PRs 1-19).
    - **gpu**: NHWC (tensor-core native conv layout).
    - **cpu** (and anything unknown): NCHW — the Caffe-parity default the
      golden-value suites run under.

    Explicit "NCHW"/"NHWC" pass through untouched (case-insensitive)."""
    lay = (layout or "NCHW").upper()
    if lay != "AUTO":
        return lay
    if backend is None:
        import jax
        backend = jax.default_backend()
    return "NHWC" if backend in ("tpu", "gpu") else "NCHW"


_policy = Policy()


def policy() -> Policy:
    return _policy


def matmul_precision():
    """float32 compute means Caffe-parity numerics: force exact f32 passes.
    bfloat16 compute means MXU-native: let XLA use its fast default."""
    import jax.lax
    if _policy.compute_dtype == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def set_policy(**kwargs) -> None:
    for k, v in kwargs.items():
        if not hasattr(_policy, k):
            raise AttributeError(k)
        setattr(_policy, k, v)


def set_perf_policy(**overrides) -> None:
    """THE bf16 perf config, in one place (``train --bf16`` routes here):
    MXU-native bfloat16 compute plus the space-to-depth
    stem rewrite — conv1's 3 input channels use 3/128 MXU lanes, and the
    rewrite is exact up to float summation order, so it rides every perf
    run by default. Caffe-parity (f32) runs never come through here, so
    golden-value comparisons keep the direct conv1 formulation.

    This IS the documented ``--bf16`` training path: params, optimizer
    state and softmax/online-softmax statistics stay f32; only
    matmul/conv inputs and activations drop to bfloat16 (the MXU
    accumulates bf16 products in f32 internally). Its accuracy guardrail
    is the BF16_SMOKE_* tolerance band above, pinned by the LeNet
    bf16-vs-f32 loss-trajectory smoke in tests/test_kernels.py."""
    cfg = dict(compute_dtype=jnp.bfloat16, conv_s2d=True)
    cfg.update(overrides)
    set_policy(**cfg)


@contextmanager
def policy_scope(**kwargs):
    saved = {k: getattr(_policy, k) for k in kwargs}
    set_policy(**kwargs)
    try:
        yield
    finally:
        set_policy(**saved)
