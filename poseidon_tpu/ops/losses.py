"""Loss and metric ops with the reference's exact normalization conventions.

Every loss is normalized the way the corresponding reference layer normalizes
(``src/caffe/layers/*_loss_layer.cpp``), so loss curves are directly comparable
to PMLS-Caffe logs:

- softmax_loss: -mean over (num * spatial) of log prob[label], probs clamped
  at FLT_MIN                              (softmax_loss_layer.cpp:47-56)
- multinomial_logistic: same but /num only, clamp 1e-20
- euclidean: sum((a-b)^2) / (2*num)
- hinge L1/L2: sum(max(0, 1 +/- score)) / num
- infogain: -sum H[label,j] log(p_j) / num
- sigmoid CE: -sum[x t - log(1+e^x)] / num (stable form)
- contrastive: (y d^2 + (1-y) max(margin - d^2, 0)) / (2*num)
- accuracy: top-k hit rate (a metric, not differentiable; gradients stopped)
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_FLT_MIN = float(np.finfo(np.float32).tiny)


def softmax(x, axis: int = 1):
    return jax.nn.softmax(x, axis=axis)


def softmax_loss(logits, labels):
    """logits (N, C, H, W) or (N, C); labels (N, H, W)/(N,) integer."""
    if logits.ndim == 2:
        logits = logits[:, :, None, None]
    if labels.ndim == 1:
        labels = labels[:, None, None]
    labels = labels.reshape(labels.shape[0], *logits.shape[2:]).astype(jnp.int32)
    logp = jax.nn.log_softmax(logits, axis=1)
    # clamp to log(FLT_MIN) like the reference clamps prob at FLT_MIN
    picked = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    picked = jnp.maximum(picked, jnp.log(_FLT_MIN))
    n, h, w = picked.shape[0], picked.shape[1], picked.shape[2]
    return -jnp.sum(picked) / (n * h * w)


def softmax_loss_last_axis(logits, labels):
    """Classes on the LAST axis: logits (..., C) against integer labels
    (...), the mean over every leading position — the token model's form
    ((batch, sequence, vocabulary) against (batch, sequence)). Same clamp
    as ``softmax_loss``; statistics in f32, and no (…, C) log-probability
    array is formed: the loss is logsumexp minus the picked logit."""
    labels = labels.reshape(logits.shape[:-1]).astype(jnp.int32)
    x = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=-1)
    picked = jnp.take_along_axis(x, labels[..., None], axis=-1)[..., 0]
    picked = jnp.maximum(picked - lse, jnp.log(_FLT_MIN))
    return -jnp.mean(picked)


@jax.custom_vjp
def softmax_nll_last_axis(logits, labels):
    """-log softmax(logits)[label] at every leading position, f32: logits
    (..., C) against integer labels (...), ``softmax_loss_last_axis``
    before its mean and with the same clamp. The backward pass is written
    out: d logits = (softmax - onehot) * g in ONE pass over the logits, in
    their own dtype. Autodiff keeps more f32[..., C] arrays through
    logsumexp's backward (a looped LM's step of 8,192 x 49,152 logits a
    pass compiled for the v5e at 15.0 GB that way, at 12.6 GB this way)."""
    return _softmax_nll_fwd(logits, labels)[0]


def _softmax_nll_fwd(logits, labels):
    labels = labels.reshape(logits.shape[:-1]).astype(jnp.int32)
    x = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(x, axis=-1)
    picked = jnp.take_along_axis(x, labels[..., None], axis=-1)[..., 0]
    nll = -jnp.maximum(picked - lse, jnp.log(_FLT_MIN))
    return nll, (logits, labels, lse, nll)


def _softmax_nll_bwd(res, g):
    logits, labels, lse, nll = res
    g = jnp.where(nll < -jnp.log(_FLT_MIN), g, 0.0)       # the clamp's zero
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    hit = labels[..., None] == jnp.arange(logits.shape[-1], dtype=jnp.int32)
    return ((p - hit) * g[..., None]).astype(logits.dtype), None


softmax_nll_last_axis.defvjp(_softmax_nll_fwd, _softmax_nll_bwd)


def exit_weighted_loss(nlls, gates, entropy_weight):
    """A looped LM's exit-weighted objective (arXiv:2510.25741, stage I)
    over T passes: ``nlls`` are T per-position losses (...), ``gates`` the
    T - 1 exit-gate logits (..., 1) of every pass but the last. With
    lambda_t = sigmoid(gate_t), a position exits at pass t with
    p_t = lambda_t * prod_{j<t}(1 - lambda_j), and at the last pass with
    what is left; the loss is the mean over positions of
    sum_t p_t * nll_t - entropy_weight * H(p). In f32 and in logs, so a
    saturated gate gives 0 * finite and not 0 * inf. Returns the loss and
    the (T, ...) exit distribution."""
    stay = jnp.zeros(nlls[0].shape, jnp.float32)   # sum_{j<t} log(1-lambda_j)
    log_p = []
    for g in gates:
        g = g.astype(jnp.float32).reshape(stay.shape)
        log_p.append(stay + jax.nn.log_sigmoid(g))
        stay = stay + jax.nn.log_sigmoid(-g)
    log_p = jnp.stack(log_p + [stay])
    p = jnp.exp(log_p)
    per = jnp.sum(p * jnp.stack([n.astype(jnp.float32) for n in nlls]),
                  axis=0)
    if entropy_weight:
        per = per + entropy_weight * jnp.sum(p * log_p, axis=0)
    return jnp.mean(per), p


def multinomial_logistic_loss(probs, labels):
    labels = labels.reshape(labels.shape[0]).astype(jnp.int32)
    p = probs.reshape(probs.shape[0], -1)
    picked = jnp.take_along_axis(p, labels[:, None], axis=1)[:, 0]
    return -jnp.mean(jnp.log(jnp.maximum(picked, 1e-20)))


def euclidean_loss(a, b):
    d = a - b
    return jnp.sum(d * d) / (2.0 * a.shape[0])


def hinge_loss(scores, labels, norm: str = "L1"):
    n = scores.shape[0]
    s = scores.reshape(n, -1)
    labels = labels.reshape(n).astype(jnp.int32)
    sign = jnp.ones_like(s).at[jnp.arange(n), labels].set(-1.0)
    margins = jnp.maximum(0.0, 1.0 + sign * s)
    if norm == "L1":
        return jnp.sum(margins) / n
    if norm == "L2":
        return jnp.sum(margins * margins) / n
    raise ValueError(f"unknown hinge norm {norm!r}")


def infogain_loss(probs, labels, H):
    n = probs.shape[0]
    p = probs.reshape(n, -1)
    labels = labels.reshape(n).astype(jnp.int32)
    logp = jnp.log(jnp.maximum(p, 1e-20))
    rows = H[labels]  # (n, dim)
    return -jnp.sum(rows * logp) / n


def sigmoid_cross_entropy_loss(logits, targets):
    n = logits.shape[0]
    x = logits.reshape(n, -1)
    t = targets.reshape(n, -1)
    # -[x*t - log(1 + exp(x))] in the overflow-stable form the reference uses
    # (sigmoid_cross_entropy_loss_layer.cpp)
    loss = jnp.maximum(x, 0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return jnp.sum(loss) / n


def contrastive_loss(a, b, y, margin: float):
    n = a.shape[0]
    d = (a - b).reshape(n, -1)
    dist_sq = jnp.sum(d * d, axis=1)
    y = y.reshape(n)
    per = jnp.where(y > 0, dist_sq, jnp.maximum(margin - dist_sq, 0.0))
    return jnp.sum(per) / (2.0 * n)


def accuracy(scores, labels, top_k: int = 1):
    n = scores.shape[0]
    s = scores.reshape(n, -1)
    labels = labels.reshape(n).astype(jnp.int32)
    s = jax.lax.stop_gradient(s)
    if top_k == 1:
        hit = jnp.argmax(s, axis=1) == labels
    else:
        _, idx = jax.lax.top_k(s, top_k)
        hit = jnp.any(idx == labels[:, None], axis=1)
    return jnp.mean(hit.astype(jnp.float32))


def argmax(scores, top_k: int = 1, out_max_val: bool = False):
    n = scores.shape[0]
    s = scores.reshape(n, -1)
    vals, idx = jax.lax.top_k(s, top_k)
    if out_max_val:
        # (N, 2, top_k, 1): channel 0 = indices, channel 1 = values (argmax_layer.cpp)
        out = jnp.stack([idx.astype(scores.dtype), vals], axis=1)
        return out[:, :, :, None]
    return idx.astype(scores.dtype)[:, None, :, None]
