"""Transformer layers: GQA attention (qk-norm / QKV-bias / sliding-window
/ RoPE), gated & squared-ReLU MLPs, and sort-based top-k MoE with
optional shared experts and affinity-based expert placement.

All layers follow the ParamDef convention of ``common.py``: ``*_defs``
returns the parameter tree with logical sharding axes; ``*_apply`` is a
pure function over (params, activations).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import (ModelConfig, ParamDef, apply_rope, constrain,
                     current_sharding_ctx, rms_norm, spec_for)
from ..kernels import ops as kops


# ======================================================================
# Attention
# ======================================================================

def attn_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, Q, KV, Dh = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    d = {
        "wq": ParamDef((D, Q), ("embed", "heads"), dtype=cfg.dtype),
        "wk": ParamDef((D, KV), ("embed", "kv_heads"), dtype=cfg.dtype),
        "wv": ParamDef((D, KV), ("embed", "kv_heads"), dtype=cfg.dtype),
        "wo": ParamDef((Q, D), ("heads", "embed"), dtype=cfg.dtype),
    }
    if cfg.qkv_bias:
        d["bq"] = ParamDef((Q,), ("heads",), init="zeros", dtype=cfg.dtype)
        d["bk"] = ParamDef((KV,), ("kv_heads",), init="zeros", dtype=cfg.dtype)
        d["bv"] = ParamDef((KV,), ("kv_heads",), init="zeros", dtype=cfg.dtype)
    if cfg.qk_norm:
        d["q_norm"] = ParamDef((Dh,), (None,), init="ones", dtype=jnp.float32)
        d["k_norm"] = ParamDef((Dh,), (None,), init="ones", dtype=jnp.float32)
    return d


def _project_qkv(cfg: ModelConfig, p, x: jax.Array, positions: jax.Array):
    B, S, _ = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(cfg: ModelConfig, q, k, v, q_offset: int = 0,
          kv_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """q: [B,Sq,H,Dh]; k/v: [B,Skv,Hkv,Dh] -> [B,Sq,H*Dh].

    Pure-XLA attention used in the lowering path; the Pallas kernel is
    selected with cfg.use_flash_kernel (training/prefill, full blocks).
    """
    B, Sq, H, Dh = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    if cfg.use_flash_kernel and Sq == Skv and kv_valid_len is None:
        out = kops.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=True,
                             window=cfg.window)
        return out.transpose(0, 2, 1, 3).reshape(B, Sq, H * Dh)
    g = H // Hkv
    qh = q.reshape(B, Sq, Hkv, g, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(Dh)
    qpos = jnp.arange(Sq) + (Skv - Sq if kv_valid_len is None else 0) + q_offset
    kpos = jnp.arange(Skv)
    mask = kpos[None, :] <= qpos[:, None]
    if cfg.window is not None:
        mask &= kpos[None, :] > qpos[:, None] - cfg.window
    if kv_valid_len is not None:
        mask = mask & (kpos[None, :] < kv_valid_len)
    s = jnp.where(mask[None, None, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", a, v.astype(jnp.float32))
    return out.reshape(B, Sq, H * Dh).astype(q.dtype)


def attn_apply(cfg: ModelConfig, p, x: jax.Array,
               positions: jax.Array) -> jax.Array:
    """Full-sequence (train / prefill)."""
    q, k, v = _project_qkv(cfg, p, x, positions)
    out = _sdpa(cfg, q, k, v)
    return out @ p["wo"]


def attn_decode(cfg: ModelConfig, p, x: jax.Array, cache: Dict[str, jax.Array],
                pos: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token decode with KV cache.

    x: [B, 1, D]; cache: {k,v: [B, Smax, Hkv, Dh]}; pos: scalar int32 --
    the timeline position of this token.  For SWA (mixtral) the cache is
    a rolling buffer of size window and ``pos % window`` is the slot.
    """
    B = x.shape[0]
    Smax = cache["k"].shape[1]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions)
    slot = pos % Smax if cfg.window is not None else pos
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    if cfg.window is not None:
        # rolling buffer: every resident entry is within the window; mask
        # only the unwritten tail during warmup.
        valid = jnp.minimum(pos + 1, Smax)
        out = _sdpa_decode_rolling(cfg, q, ck, cv, valid)
    else:
        out = _sdpa(cfg, q, ck, cv, q_offset=pos, kv_valid_len=pos + 1)
    return out @ p["wo"], {"k": ck, "v": cv}


def _sdpa_decode_rolling(cfg: ModelConfig, q, k, v, valid_len) -> jax.Array:
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qh = q.reshape(B, Sq, Hkv, g, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(Dh)
    kpos = jnp.arange(Skv)
    mask = kpos[None, :] < valid_len
    s = jnp.where(mask[None, None, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", a, v.astype(jnp.float32))
    return out.reshape(B, Sq, H * Dh).astype(q.dtype)


def make_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  stacked_layers: Optional[int] = None,
                  as_shape: bool = False):
    Hkv, Dh = cfg.num_kv_heads, cfg.head_dim
    cap = min(max_len, cfg.window) if cfg.window is not None else max_len
    shape = (batch, cap, Hkv, Dh)
    if stacked_layers is not None:
        shape = (stacked_layers,) + shape
    if as_shape:
        return {"k": jax.ShapeDtypeStruct(shape, cfg.dtype),
                "v": jax.ShapeDtypeStruct(shape, cfg.dtype)}
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def kv_cache_axes(cfg: ModelConfig, stacked: bool = True):
    """Logical axes for the cache (rules map cache_seq -> model when the
    long-context seq-sharding option is on)."""
    axes = ("batch", "cache_seq", "kv_heads", None)
    if stacked:
        axes = ("layers",) + axes
    return {"k": axes, "v": axes}


# ======================================================================
# MLPs
# ======================================================================

def mlp_defs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, ParamDef]:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    if cfg.mlp_act == "silu_glu":
        return {
            "w1": ParamDef((D, F), ("embed", "mlp"), dtype=cfg.dtype),
            "w3": ParamDef((D, F), ("embed", "mlp"), dtype=cfg.dtype),
            "w2": ParamDef((F, D), ("mlp", "embed"), dtype=cfg.dtype),
        }
    # nemotron: squared-ReLU, no gate
    return {
        "w1": ParamDef((D, F), ("embed", "mlp"), dtype=cfg.dtype),
        "w2": ParamDef((F, D), ("mlp", "embed"), dtype=cfg.dtype),
    }


def mlp_apply(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    if cfg.mlp_act == "silu_glu":
        h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    else:
        r = jax.nn.relu(x @ p["w1"])
        h = r * r
    return h @ p["w2"]


# ======================================================================
# MoE (sort-based top-k dispatch with capacity)
# ======================================================================

def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    D, E = cfg.d_model, cfg.num_experts
    F = cfg.effective_moe_ff()
    d = {
        "router": ParamDef((D, E), ("embed", None), dtype=jnp.float32,
                           scale=0.1),
        "w1": ParamDef((E, D, F), ("experts", "embed", "expert_mlp"),
                       dtype=cfg.dtype),
        "w3": ParamDef((E, D, F), ("experts", "embed", "expert_mlp"),
                       dtype=cfg.dtype),
        "w2": ParamDef((E, F, D), ("experts", "expert_mlp", "embed"),
                       dtype=cfg.dtype),
    }
    if cfg.num_shared_experts > 0:
        Fs = F * cfg.num_shared_experts
        d["shared"] = {
            "w1": ParamDef((D, Fs), ("embed", "mlp"), dtype=cfg.dtype),
            "w3": ParamDef((D, Fs), ("embed", "mlp"), dtype=cfg.dtype),
            "w2": ParamDef((Fs, D), ("mlp", "embed"), dtype=cfg.dtype),
        }
    return d


def moe_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(math.ceil(num_tokens * cfg.top_k / max(cfg.num_experts, 1)
                      * cfg.capacity_factor))
    return max(int(np.ceil(c / 8) * 8), 8)  # pad for lane alignment


def _moe_route_group(cfg: ModelConfig, p, xt: jax.Array, C: int,
                     expert_perm: Optional[jax.Array],
                     batched: bool = False):
    """Route one token group xt: [T, D] with capacity C per expert
    (or [B, T, D] when ``batched`` -- the grouped-dispatch path runs the
    same code over a leading batch dim so sharding constraints can name
    the batch axis; pure-vmap would erase them).
    Returns (y like xt, aux scalar)."""
    if batched:
        return _moe_route_batched(cfg, p, xt, C, expert_perm)
    T, D = xt.shape
    E, K = cfg.num_experts, cfg.top_k

    gates = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    if expert_perm is not None:
        gates = gates[:, expert_perm]
    probs = jax.nn.softmax(gates, axis=-1)
    vals, idx = jax.lax.top_k(probs, K)                 # [T, K]
    w = vals / jnp.clip(vals.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss (switch-style)
    me = probs.mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        jnp.ones((T * K,), jnp.float32)) / (T * K)
    aux = (me * ce).sum() * E

    # sort assignments by expert
    e_flat = idx.reshape(-1)                            # [T*K]
    t_flat = jnp.repeat(jnp.arange(T), K)
    w_flat = w.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    e_s, t_s, w_s = e_flat[order], t_flat[order], w_flat[order]
    # position within expert
    start = jnp.searchsorted(e_s, jnp.arange(E), side="left")
    pos = jnp.arange(T * K) - start[e_s]
    keep = pos < C
    slot = jnp.clip(e_s * C + pos, 0, E * C - 1)

    xs = jnp.zeros((E * C, D), cfg.dtype)
    xs = xs.at[slot].add(jnp.where(keep[:, None], xt[t_s], 0).astype(cfg.dtype))
    xe = xs.reshape(E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w1"])) * \
        jnp.einsum("ecd,edf->ecf", xe, p["w3"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["w2"]).reshape(E * C, D)

    back = ye[slot] * (w_s * keep).astype(ye.dtype)[:, None]
    y = jnp.zeros((T, D), ye.dtype).at[t_s].add(back)
    return y, aux


def _moe_route_batched(cfg: ModelConfig, p, x: jax.Array, C: int,
                       expert_perm: Optional[jax.Array]):
    """Grouped dispatch with explicit batch dim + sharding constraints
    (cfg.moe_sharded_ffn): every buffer keeps its 'batch' axis sharded
    over data, expert-FFN intermediates are bf16 and mlp-sharded, so the
    only model-axis collective left is the (bf16, token-sized) combine.
    """
    B, T, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    F = cfg.effective_moe_ff()

    gates = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                       p["router"].astype(jnp.float32))
    if expert_perm is not None:
        gates = gates[..., expert_perm]
    probs = jax.nn.softmax(gates, axis=-1)
    vals, idx = jax.lax.top_k(probs, K)                    # [B,T,K]
    w = vals / jnp.clip(vals.sum(-1, keepdims=True), 1e-9)

    me = probs.mean((0, 1))
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / idx.size
    aux = (me * ce).sum() * E

    e_flat = idx.reshape(B, T * K)
    t_flat = jnp.tile(jnp.repeat(jnp.arange(T), K)[None], (B, 1))
    w_flat = w.reshape(B, T * K)
    order = jnp.argsort(e_flat, axis=-1, stable=True)
    take = lambda a: jnp.take_along_axis(a, order, axis=-1)
    e_s, t_s, w_s = take(e_flat), take(t_flat), take(w_flat)
    start = jax.vmap(lambda es: jnp.searchsorted(es, jnp.arange(E),
                                                 side="left"))(e_s)
    pos = jnp.arange(T * K)[None] - jnp.take_along_axis(start, e_s, axis=-1)
    keep = pos < C
    slot = jnp.clip(e_s * C + pos, 0, E * C - 1)

    brow = jnp.arange(B)[:, None]
    gathered = jnp.take_along_axis(x, t_s[..., None], axis=1)  # [B,T*K,D]
    gathered = jnp.where(keep[..., None], gathered, 0).astype(cfg.dtype)
    xs = jnp.zeros((B, E * C, D), cfg.dtype).at[brow, slot].add(gathered)
    xs = constrain(xs, ("batch", None, None))
    xe = xs.reshape(B, E, C, D)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["w1"])) * \
        jnp.einsum("becd,edf->becf", xe, p["w3"])
    h = constrain(h.astype(cfg.dtype), ("batch", None, None, "expert_mlp"))
    ye = jnp.einsum("becf,efd->becd", h, p["w2"]).astype(cfg.dtype)
    ye = constrain(ye.reshape(B, E * C, D), ("batch", None, None))

    back = jnp.take_along_axis(ye, slot[..., None], axis=1)
    back = back * (w_s * keep).astype(back.dtype)[..., None]
    y = jnp.zeros((B, T, D), back.dtype).at[brow, t_s].add(back)
    return constrain(y, ("batch", None, None)), aux


def _moe_shard_map(cfg: ModelConfig, p, x: jax.Array, C: int,
                   expert_perm: Optional[jax.Array]):
    """Manual-collective MoE (Megatron pattern, §Perf iteration V4).

    Routing is replicated across the model axis (deterministic: identical
    inputs + replicated router), expert matmuls run on the local d_ff
    shard, the slot->token combine happens on the *partial* results, and
    the single model-axis collective is a bf16 psum of the combined
    [B, S, D] output -- instead of the capacity-inflated f32 dispatch
    buffer the jit partitioner reduces.
    """
    ctx = current_sharding_ctx()
    if ctx is None:
        return _moe_route_batched(cfg, p, x, C, expert_perm)
    mesh, rules = ctx
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    tp = "model" if sizes.get("model", 1) > 1 else None
    B = x.shape[0]
    if (tp is None and not dp_axes) or B % max(
            int(np.prod([sizes[a] for a in dp_axes])) if dp_axes else 1, 1):
        return _moe_route_batched(cfg, p, x, C, expert_perm)

    from jax.sharding import PartitionSpec as P

    batch_spec = P(dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes
                                                     else None), None, None)
    w_specs = {
        "router": P(None, None),
        "w1": P(None, None, tp), "w3": P(None, None, tp),
        "w2": P(None, tp, None),
    }

    from .common import no_constraints

    def local_fn(x_loc, router, w1, w3, w2):
        pl = {"router": router, "w1": w1, "w3": w3, "w2": w2}
        with no_constraints():
            y_partial, aux = _moe_route_batched(cfg, pl, x_loc, C,
                                                expert_perm)
        # combine happened on partials; ONE bf16 psum of token-sized y
        y = jax.lax.psum(y_partial, tp) if tp is not None else y_partial
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return y, aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(batch_spec, w_specs["router"], w_specs["w1"],
                  w_specs["w3"], w_specs["w2"]),
        out_specs=(batch_spec, P()), check_vma=False)
    return fn(x, p["router"], p["w1"], p["w3"], p["w2"])


def moe_apply(cfg: ModelConfig, p, x: jax.Array,
              expert_perm: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (y, aux_loss).

    Sort-based dispatch: route top-k, stable-sort the (token, expert)
    assignments by expert, truncate to capacity, run the per-expert FFN
    as one batched einsum, and scatter-add back.

    cfg.moe_grouped_dispatch: route each sequence independently (vmap
    over batch, per-row capacity) so the data-axis sharding of the batch
    survives the argsort/scatter -- the flat path forces an all-gather of
    every token on multi-device meshes (measured in §Perf).

    ``expert_perm``: optional expert relabeling from affinity placement
    (paper Def. 13 / Algorithm 2 over token co-activation; experts that
    fire together get adjacent ids => same shard under contiguous expert
    sharding).
    """
    B, S, D = x.shape
    if cfg.moe_shard_map and S > 1:
        C = moe_capacity(cfg, S)
        y, aux = _moe_shard_map(cfg, p, x, C, expert_perm)
    elif cfg.moe_sharded_ffn and S > 1:
        C = moe_capacity(cfg, S)
        y, aux = _moe_route_batched(cfg, p, x, C, expert_perm)
    elif cfg.moe_grouped_dispatch and S > 1:  # decode (S=1) stays flat
        C = moe_capacity(cfg, S)
        y, aux = jax.vmap(
            lambda row: _moe_route_group(cfg, p, row, C, expert_perm))(x)
        aux = aux.mean()
        y = y.reshape(B, S, D)
    else:
        T = B * S
        C = moe_capacity(cfg, T)
        y, aux = _moe_route_group(cfg, p, x.reshape(T, D), C, expert_perm)
        y = y.reshape(B, S, D)

    if cfg.num_shared_experts > 0:
        y = y + mlp_apply(cfg, p["shared"], x)
    return y.astype(x.dtype), aux
