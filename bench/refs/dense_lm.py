"""Plain reference of a dense decoder LM's training step, in float32.

Pre-norm blocks: RMSNorm with a ``(1 + w)`` scale, q/k RMSNorm (qk_norm),
rotary embedding on each head's two halves, grouped-query causal
attention, a SwiGLU MLP, a final RMSNorm and an untied output head; mean
token cross entropy over the first ``vocab`` columns.  AdamW with
global-norm clipping, linear warm-up and decoupled weight decay.

It takes its weights as a dict of arrays (``embed [Vp, D]``,
``lm_head [D, Vp]``, ``final_norm [D]`` and ``layers`` stacked on a
leading ``[L]`` axis) and imports nothing of the system under test.
Matrix products run at ``precision="highest"``; ``fp8=True`` rounds the
operands of every weight product, forward (e4m3) and backward (e4m3
operands, e5m2 cotangents), to fp8 with a per-tensor scale: the
lower-precision control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _fp8(x, dtype):
    """Round to an fp8 format with a per-tensor absmax scale."""
    top = float(jnp.finfo(dtype).max)
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@jax.custom_vjp
def _mm_fp8(a, b):
    return jnp.matmul(_fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn),
                      precision="highest")


def _mm_fp8_fwd(a, b):
    return _mm_fp8(a, b), (a, b)


def _mm_fp8_bwd(res, g):
    """Backward products in fp8 as well: e4m3 operands, e5m2 cotangent."""
    a, b = res
    a8, b8 = _fp8(a, jnp.float8_e4m3fn), _fp8(b, jnp.float8_e4m3fn)
    g8 = _fp8(g, jnp.float8_e5m2)
    ga = jnp.matmul(g8, b8.T, precision="highest")
    gb = jnp.einsum("...i,...j->ij", a8, g8, precision="highest")
    return ga, gb


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(a, b, fp8):
    if fp8:
        return _mm_fp8(a, b)
    return jnp.matmul(a, b, precision="highest")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freqs = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = np.arange(S, dtype=np.float32)[:, None] * freqs      # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(params, tokens, targets, dims, fp8=False):
    H, KV, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    eps, theta, vocab = dims["eps"], dims["rope_theta"], dims["vocab"]
    B, S = tokens.shape
    x = params["embed"][tokens]
    causal = np.tril(np.ones((S, S), bool))
    for i in range(dims["layers"]):
        a = jax.tree_util.tree_map(lambda t: t[i], params["layers"]["attn"])
        m = jax.tree_util.tree_map(lambda t: t[i], params["layers"]["mlp"])
        h = _rms(x, a["norm"], eps)
        q = _mm(h, a["wq"], fp8).reshape(B, S, H, dh)
        k = _mm(h, a["wk"], fp8).reshape(B, S, KV, dh)
        v = _mm(h, a["wv"], fp8).reshape(B, S, KV, dh)
        q = _rope(_rms(q, a["q_norm"], eps), theta)
        k = _rope(_rms(k, a["k_norm"], eps), theta)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision="highest") / np.sqrt(dh)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")
        x = x + _mm(o.reshape(B, S, H * dh), a["wo"], fp8)
        h = _rms(x, m["norm"], eps)
        x = x + _mm(jax.nn.silu(_mm(h, m["wg"], fp8)) * _mm(h, m["wu"], fp8),
                    m["wd"], fp8)
    x = _rms(x, params["final_norm"], eps)
    logits = _mm(x, params["lm_head"], fp8)[..., :vocab]
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def adamw(opt, params, grads, m, v, step):
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    lr = opt["lr"] * (step + 1) / opt["warmup_steps"]
    t = step + 1.0
    bc1, bc2 = 1 - opt["beta1"] ** t, 1 - opt["beta2"] ** t

    def one(p, g, mi, vi):
        g = g * scale
        mn = opt["beta1"] * mi + (1 - opt["beta1"]) * g
        vn = opt["beta2"] * vi + (1 - opt["beta2"]) * g * g
        u = (mn / bc1) / (jnp.sqrt(vn / bc2) + opt["eps"])
        return p - lr * (u + opt["weight_decay"] * p), mn, vn

    out = jax.tree_util.tree_map(one, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(x))))
                     for x in jax.tree_util.tree_leaves(tree)])


def run(params0, batches, dims, opt, fp8=False) -> dict:
    """Train from ``params0`` through ``batches`` (``(tokens, targets)``
    of the global batch, one per step; warm-up below its end).  Returns
    each step's loss, the per-leaf norms of the first step's gradient of
    the global batch's mean loss (as the optimizer gets it, before its
    clipping) and its global norm, and the per-leaf norms of the
    parameters' change over all the steps."""
    vg = jax.jit(jax.value_and_grad(functools.partial(loss, dims=dims,
                                                      fp8=fp8)))
    step_fn = jax.jit(functools.partial(adamw, opt))
    p = params0
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, first_grad, first_gnorm = [], None, None
    for i, (tok, tgt) in enumerate(batches):
        lval, g = vg(p, tok, tgt)
        p, m, v = step_fn(p, g, m, v, jnp.float32(i))
        losses.append(float(lval))
        if i == 0:
            first_grad = leaf_norms(g)
            first_gnorm = float(np.sqrt(np.sum(first_grad ** 2)))
    change = leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b,
                                               p, params0))
    return {"losses": np.array(losses), "first_grad": first_grad,
            "first_gnorm": first_gnorm, "change": change}
