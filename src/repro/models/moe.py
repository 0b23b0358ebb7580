"""Fine-grained MoE with shared experts (DeepSeekMoE / Kimi-K2 style).

Sort-based capacity dispatch (MaxText-style, no [T, E] one-hots):
tokens' (token, expert) assignments are sorted by expert id; each expert
gathers its first ``capacity`` slots; overflow tokens are dropped (weighted
combine renormalizes).  This keeps peak memory at E*cap*D = T*k*cf*D —
inherent to top-k — and maps onto expert parallelism: expert-major
intermediates are sharded over the "model" axis (an all-to-all at dispatch
and combine, inserted by SPMD from the sharding constraints).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .layers import ninit


def _ep(x, spec):
    """Expert-parallel sharding constraint (REPRO_MOE_EP=1; needs an
    ambient mesh — jax.sharding.set_mesh — else it is a no-op).  §Perf:
    without it GSPMD all-gathers the full token array into every
    expert shard."""
    if os.environ.get("REPRO_MOE_EP") != "1":
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def init_moe_block(root, path, cfg, dtype):
    D, E, Fe = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    Fs = cfg.n_shared_experts * Fe
    p = {
        "router": ninit(root, f"{path}/router", (D, E), 0.02, jnp.float32),
        "wg": ninit(root, f"{path}/wg", (E, D, Fe), 0.02, dtype),
        "wu": ninit(root, f"{path}/wu", (E, D, Fe), 0.02, dtype),
        "wd": ninit(root, f"{path}/wd", (E, Fe, D),
                    0.02 / np.sqrt(2 * cfg.n_layers), dtype),
    }
    if Fs:
        p.update(
            shared_wg=ninit(root, f"{path}/swg", (D, Fs), 0.02, dtype),
            shared_wu=ninit(root, f"{path}/swu", (D, Fs), 0.02, dtype),
            shared_wd=ninit(root, f"{path}/swd", (Fs, D),
                            0.02 / np.sqrt(2 * cfg.n_layers), dtype),
        )
    return p


def moe_forward(cfg, params, x, *, ep_constraint=None):
    """x: [B, S, D] -> [B, S, D] (+ aux load-balance loss).

    ep_constraint: optional fn(array, spec) applying
    with_sharding_constraint for expert-parallel layouts.
    """
    B, S, D = x.shape
    E, k, Fe = cfg.n_experts, cfg.top_k, cfg.d_ff_expert
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ params["router"])        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                        # [T, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.bincount(topi.reshape(-1), length=E).astype(jnp.float32) / (T * k)
    aux = E * jnp.sum(me * ce)

    cap = int(np.ceil(T * k / E * cfg.capacity_factor))
    cap = max(4, int(-(-cap // 4) * 4))

    # --- sort-based dispatch -------------------------------------------
    flat_e = topi.reshape(-1)                                   # [T*k]
    order = jnp.argsort(flat_e)                                 # stable
    sorted_e = flat_e[order]
    sorted_tok = order // k
    sorted_w = topv.reshape(-1)[order]

    starts = jnp.searchsorted(sorted_e, jnp.arange(E))          # [E]
    slot = starts[:, None] + jnp.arange(cap)[None, :]           # [E, cap]
    slot_c = jnp.clip(slot, 0, T * k - 1)
    valid = (sorted_e[slot_c] == jnp.arange(E)[:, None]) & (slot < T * k)
    tok_idx = jnp.where(valid, sorted_tok[slot_c], 0)           # [E, cap]
    w = jnp.where(valid, sorted_w[slot_c], 0.0)                 # [E, cap]

    # experts over "model" (EP); capacity slots optionally over "data"
    # (REPRO_MOE_CAP_SHARD=1 splits expert work 256 ways but makes GSPMD
    # reshard the dispatch gathers — measured trade-off in §Perf).
    cap_axes = ("data",) if os.environ.get("REPRO_MOE_CAP_SHARD") == "1" \
        else (None,)
    spec2 = P("model", *cap_axes)
    spec3 = P("model", *cap_axes, None)
    tok_idx = _ep(tok_idx, spec2)
    w = _ep(w, spec2)
    xe = xt[tok_idx]                                            # [E, cap, D]
    xe = _ep(xe, spec3)
    h = jnp.einsum("ecd,edf->ecf", xe, params["wg"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["wu"])
    h = _ep(jax.nn.silu(h) * u, spec3)
    ye = jnp.einsum("ecf,efd->ecd", h, params["wd"])            # [E, cap, D]
    ye = _ep(ye, spec3)

    # --- weighted combine ------------------------------------------------
    if os.environ.get("REPRO_MOE_COMBINE", "gather") == "scatter":
        # scatter-add back to token space
        yt = jnp.zeros((T, D), ye.dtype)
        yt = yt.at[tok_idx.reshape(-1)].add(
            (ye * w[..., None].astype(ye.dtype)).reshape(-1, D))
    else:
        # gather via the inverse permutation: every (token, j) assignment
        # reads its expert slot: sorted position q -> slot (e, q-starts[e])
        inv = jnp.argsort(order)                                # [T*k]
        e_of = flat_e                                           # [T*k]
        slot_of = inv - starts[e_of]                            # [T*k]
        in_cap = slot_of < cap
        flat_idx = jnp.where(
            in_cap, e_of * cap + jnp.clip(slot_of, 0, cap - 1), 0)
        yg = ye.reshape(E * cap, D)[flat_idx]                   # [T*k, D]
        wg_ = jnp.where(in_cap, topv.reshape(-1), 0.0)
        yt = jnp.sum((yg * wg_[:, None].astype(ye.dtype)).reshape(T, k, D),
                     axis=1)

    if "shared_wg" in params:
        h = jax.nn.silu(xt @ params["shared_wg"]) * (xt @ params["shared_wu"])
        yt = yt + h @ params["shared_wd"]
    return yt.reshape(B, S, D), aux


def moe_forward_dense_ref(cfg, params, x):
    """O(T*E) oracle: every expert on every token, weighted by router
    (with the same top-k mask).  For correctness tests on tiny configs."""
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    logits = xt.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs)
    gates = jax.vmap(lambda g, i, v: g.at[i].set(v))(gates, topi, topv)
    h = jnp.einsum("td,edf->tef", xt, params["wg"])
    u = jnp.einsum("td,edf->tef", xt, params["wu"])
    ye = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, params["wd"])
    yt = jnp.einsum("te,ted->td", gates.astype(ye.dtype), ye)
    if "shared_wg" in params:
        hs = jax.nn.silu(xt @ params["shared_wg"]) * (xt @ params["shared_wu"])
        yt = yt + hs @ params["shared_wd"]
    return yt.reshape(B, S, D)
