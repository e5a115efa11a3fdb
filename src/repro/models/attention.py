"""Attention: GQA (RoPE, qk-norm, sliding window, bias) and MLA (DeepSeek-V2).

Long sequences use :func:`blocked_attention` — an online-softmax scan that
streams KV blocks through the compute unit, the direct jnp analogue of the
paper's systolic operand streaming (and the oracle for the
``kernels/flash_attention`` Pallas kernel). When ``cfg.systolic_mode`` is a
link mode (sw/xqueue/qlr) and the mesh/shapes admit it, the KV stream is
realized as actual queue traffic: ``core/ring_attention`` keeps each query
shard resident and hops K/V blocks around the 'model' ring. Decode paths
operate on fixed-size caches: dense for full attention, ring-buffer for
sliding-window.

MLA decode uses the absorbed formulation (q projected into the latent space,
attention performed against the compressed cache) so per-token FLOPs scale
with the latent rank, not the expanded KV width.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import (
    Param,
    adtype,
    apply_rope,
    param,
    pdtype,
    rms_norm_simple,
    shard,
)

_NEG_INF = -1e30
# Sequences at or above this length use the blocked (streaming) path.
BLOCKED_ATTN_THRESHOLD = 2048
KV_BLOCK = 512


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig, d_model: int | None = None):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq": param(ks[0], (d, cfg.num_heads, hd), ("w_embed", "heads", "head_dim"), pdtype(cfg)),
        "wk": param(ks[1], (d, cfg.num_kv_heads, hd), ("w_embed", "kv_heads", "head_dim"), pdtype(cfg)),
        "wv": param(ks[2], (d, cfg.num_kv_heads, hd), ("w_embed", "kv_heads", "head_dim"), pdtype(cfg)),
        "wo": param(ks[3], (cfg.num_heads, hd, d), ("heads", "head_dim", "w_embed"), pdtype(cfg)),
    }
    if cfg.use_attn_bias:
        p["bq"] = param(ks[4], (cfg.num_heads, hd), ("heads", "head_dim"), pdtype(cfg), init="zeros")
        p["bk"] = param(ks[5], (cfg.num_kv_heads, hd), ("kv_heads", "head_dim"), pdtype(cfg), init="zeros")
        p["bv"] = param(ks[6], (cfg.num_kv_heads, hd), ("kv_heads", "head_dim"), pdtype(cfg), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = param(ks[7], (hd,), ("head_dim",), pdtype(cfg), init="ones")
        p["k_norm"] = param(ks[7], (hd,), ("head_dim",), pdtype(cfg), init="ones")
    return p


def _systolic_attn_ctx(cfg: ModelConfig):
    """Mesh context when the paper's ring projections are enabled (or the
    autotuner may enable them via a cached plan)."""
    if cfg.systolic_mode == "baseline" and not cfg.autotune:
        return None
    from repro.models.common import current_ctx
    return current_ctx()


def _tuned(cfg: ModelConfig, op: str, shape):
    """Config.autotune gate: rewrite the systolic fields from a cached
    measured plan for (op, shape) — cache-only, defaults stand on miss."""
    if not cfg.autotune:
        return cfg
    from repro.models.common import current_ctx
    ctx = current_ctx()
    if ctx is None:
        return cfg
    from repro.autotune.api import tuned_cfg
    return tuned_cfg(cfg, op, shape, ctx.mesh)


def _sched(cfg: ModelConfig, mesh, *, cycle_only: bool = False):
    """cfg.systolic_topology -> schedule over the 'model' axis (None keeps
    the callee's default +1 ring)."""
    if cfg.systolic_topology in ("", "ring"):
        return None
    from repro.core import topology as topo_lib
    return topo_lib.resolve_safe(cfg.systolic_topology, "model",
                                 mesh.shape["model"], cycle_only=cycle_only)


def _qkv(params, x, cfg: ModelConfig, positions):
    dt = adtype(cfg)
    x = x.astype(dt)
    ctx = _systolic_attn_ctx(cfg)
    done = False
    if ctx is not None and cfg.systolic_mode != "baseline" and x.ndim == 3:
        from repro.core import collective_matmul as cm
        if cm.attn_applicable(x, cfg.num_heads, cfg.num_kv_heads,
                              cfg.resolved_head_dim, ctx.mesh):
            # one systolic x-stream feeds the three projection sinks
            q, k, v = cm.systolic_qkv(
                x, params["wq"].astype(dt), params["wk"].astype(dt),
                params["wv"].astype(dt), ctx.mesh, cfg.systolic_mode,
                use_kernel=cfg.use_kernel, topo=_sched(cfg, ctx.mesh),
                block=cfg.kernel_block)
            done = True
    if not done:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.use_attn_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if cfg.qk_norm:
        q = rms_norm_simple(q, params["q_norm"])
        k = rms_norm_simple(k, params["k_norm"])
    if cfg.use_rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def _expand_kv(k, num_heads: int):
    """[B,S,Kv,hd] -> [B,S,H,hd] by repeating KV heads (keeps the 'heads'
    dim contiguous so head sharding over 'model' survives the einsums)."""
    kvh = k.shape[2]
    if kvh == num_heads:
        return k
    return jnp.repeat(k, num_heads // kvh, axis=2)


def plain_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_positions=None, k_positions=None):
    """Materialized-scores attention (short sequences / decode).

    q: [B,Sq,H,hd], k/v: [B,Skv,Kv,hd]. Positions default to aligned ranges.
    """
    b, sq, h, hd = q.shape
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bshk,bthk->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale       # [B,H,Sq,Skv]
    scores = shard(scores, "batch", "heads", None, None)
    if q_positions is None:
        q_positions = jnp.arange(sq)
    if k_positions is None:
        k_positions = jnp.arange(k.shape[1])
    qp = q_positions.reshape((-1, sq)) if q_positions.ndim == 1 else q_positions
    kp = k_positions
    # masks on [Sq, Skv] (broadcast over batch when positions are per-batch)
    dq = qp[..., :, None]
    dk = kp[..., None, :] if kp.ndim > 1 else kp[None, :]
    mask = dk <= dq if causal else jnp.ones_like(dk <= dq)
    if window:
        mask = jnp.logical_and(mask, dq - dk < window)
    while mask.ndim < scores.ndim:
        mask = mask[:, None]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = shard(probs, "batch", "heads", None, None)
    out = jnp.einsum("bhst,bthk->bshk", probs, v.astype(jnp.float32))
    return out


def blocked_attention(q, k, v, *, causal: bool, window: int = 0,
                      kv_block: int = KV_BLOCK):
    """Online-softmax attention streaming KV blocks (flash-style).

    The KV stream is the systolic-queue analogue: each scan step pops one
    KV block, updates the running (max, normalizer, accumulator) — identical
    math to the Pallas flash kernel, kept in pure jnp as its oracle. The
    per-block update is shared with core/ring_attention, where the same
    stream rides actual queue links; KV blocks stay unexpanded (GQA) until
    each update consumes them.
    """
    from repro.core.ring_attention import _block_update
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    kvh = k.shape[2]
    if skv % kv_block:
        pad = kv_block - skv % kv_block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = k.shape[1] // kv_block
    q32 = q.astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    kb = k.reshape(b, nblk, kv_block, kvh, hd)
    vb = v.reshape(b, nblk, kv_block, kvh, hd)
    q_pos = jnp.arange(sq)

    def step(carry, inputs):
        kblk, vblk, blk_idx = inputs
        k_pos = blk_idx * kv_block + jnp.arange(kv_block)
        carry = _block_update(
            carry, q32, kblk, vblk, q_pos, k_pos, causal=causal,
            window=window, scale=scale, num_heads=h, k_len=skv,
            score_hint=lambda s: shard(s, "batch", "heads", None, None))
        return carry, None

    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]             # [B,H,Sq,hd]
    return out.transpose(0, 2, 1, 3)                          # [B,Sq,H,hd]


def gqa_forward(params, x, cfg: ModelConfig, positions=None, return_kv=False):
    """Full-sequence causal attention (train / prefill). x: [B,S,D]."""
    b, s, _ = x.shape
    cfg = _tuned(cfg, "attention", x.shape)
    if positions is None:
        positions = jnp.arange(s)[None, :].astype(jnp.int32)
    q, k, v = _qkv(params, x, cfg, positions)
    out = None
    used_ring = False
    ctx = _systolic_attn_ctx(cfg)
    if ctx is not None and cfg.systolic_mode != "baseline":
        from repro.core import ring_attention as ra
        if ra.ring_attn_applicable(q, k, ctx.mesh):
            # the paper's streamed-operand schedule on the attention core:
            # q shards stay resident, K/V blocks ride the 'model' ring
            # (or the tuned 2-D grid schedule)
            out = ra.systolic_ring_attention(
                q, k, v, ctx.mesh, cfg.systolic_mode, causal=True,
                window=cfg.sliding_window, use_kernel=cfg.use_kernel,
                topo=_sched(cfg, ctx.mesh))
            used_ring = True
    if out is None:
        if s >= BLOCKED_ATTN_THRESHOLD:
            out = blocked_attention(q, k, v, causal=True,
                                    window=cfg.sliding_window)
        else:
            out = plain_attention(q, k, v, causal=True,
                                  window=cfg.sliding_window)
    out = shard(out.astype(adtype(cfg)), "batch", "seq", "heads", "head_dim")
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape)) if ctx else {}
    # after ring attention the output is already sequence-sharded and the
    # out-projection is local to each shard (wo is the resident multicast
    # operand) — the head-sharded RS ring would only add a reshard
    if (not used_ring and ctx is not None
            and cfg.systolic_mode != "baseline"
            and cfg.num_heads % max(sizes.get("model", 1), 1) == 0
            and sizes.get("model", 0) > 1 and s % sizes["model"] == 0):
        from repro.core import collective_matmul as cm
        # reduce-scatter ring: head-shard partials travel to seq owners
        y = cm.systolic_out_proj(out, params["wo"].astype(adtype(cfg)),
                                 ctx.mesh, cfg.systolic_mode,
                                 use_kernel=cfg.use_kernel,
                                 topo=_sched(cfg, ctx.mesh),
                                 block=cfg.kernel_block)
    else:
        y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(adtype(cfg)))
        # reduce-scatter (not all-reduce) into the sequence-parallel layout
        y = shard(y, "batch", "seq_sp" if cfg.sequence_parallel else "seq",
                  "embed")
    if return_kv:
        return y, (k, v)
    return y


# ----------------------------- decode cache -------------------------------


def init_gqa_cache(cfg: ModelConfig, batch: int, seq_len: int):
    """Cache ShapeDtype layout. Sliding window uses a ring buffer."""
    hd = cfg.resolved_head_dim
    s_cache = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    shape = (batch, s_cache, cfg.num_kv_heads, hd)
    return {
        "k": jnp.zeros(shape, adtype(cfg)),
        "v": jnp.zeros(shape, adtype(cfg)),
        # per-row positions: rows decode at independent offsets
        # (continuous batching in serve/engine.py)
        "pos": jnp.zeros((batch,), jnp.int32),
    }


GQA_CACHE_AXES = {
    "k": ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
    "v": ("cache_batch", "cache_seq", "kv_heads", "head_dim"),
    "pos": ("cache_batch",),
}


def _layer(leaf, layer):
    """One layer of a decode-cache leaf: the leaf itself when ``layer`` is
    None, else slice ``layer`` of a stacked [L, ...] leaf (read only, so
    the slice fuses into its consumer instead of being copied out)."""
    return leaf if layer is None else leaf[layer]


def _write_rows(leaf, layer, rows, slots, new):
    """Write one new entry per row into a decode-cache leaf at (row, slot),
    in place; a slot past the end drops the write (inactive rows).
    ``layer`` indexes a stacked [L, ...] leaf; None means one layer's."""
    idx = (rows, slots) if layer is None else (layer, rows, slots)
    return leaf.at[idx].set(new, mode="drop")


def _write_pos(leaf, layer, new_pos):
    return new_pos if layer is None else leaf.at[layer].set(new_pos)


def gqa_decode(params, x, cache, cfg: ModelConfig, active=None, layer=None):
    """One-token decode. x: [B,1,D]; per-row positions; rows with
    active=False neither write the cache nor advance (continuous batching).

    ``layer`` None: ``cache`` is one layer's. Otherwise ``cache`` is the
    stacked [L, ...] cache of a layer scan: this step's K/V rows are written
    into it at (layer, row, slot) and attention reads layer ``layer`` where
    it lies, so no layer's cache is copied out or back.

    When ``cfg.systolic_mode`` is a link mode and the mesh/shapes admit it
    (``ring_decode_applicable``), the attention core runs the decode dual
    of the ring schedule: the cache shards stay resident along the 'model'
    ring and each row's query streams around them with carried
    online-softmax state. Returns (y [B,1,D], new cache)."""
    pos = _layer(cache["pos"], layer)                        # [B]
    b = x.shape[0]
    cfg = _tuned(cfg, "decode", x.shape)
    q, k, v = _qkv(params, x, cfg, pos[:, None].astype(jnp.int32))
    s_cache = cache["k"].shape[-3]
    write_idx = jnp.mod(pos, s_cache) if cfg.sliding_window else \
        jnp.minimum(pos, s_cache - 1)
    if active is not None:
        write_idx = jnp.where(active, write_idx, s_cache)    # OOB -> dropped
    rows = jnp.arange(b)
    k_store = _write_rows(cache["k"], layer, rows, write_idx, k[:, 0])
    v_store = _write_rows(cache["v"], layer, rows, write_idx, v[:, 0])
    k_all = shard(_layer(k_store, layer),
                  "cache_batch", "cache_seq", "kv_heads", "head_dim")
    v_all = shard(_layer(v_store, layer),
                  "cache_batch", "cache_seq", "kv_heads", "head_dim")

    out = None
    ctx = _systolic_attn_ctx(cfg)
    if ctx is not None and cfg.systolic_mode != "baseline" \
            and not cfg.sliding_window:
        from repro.core import ring_attention as ra
        if ra.ring_decode_applicable(q, k_all, ctx.mesh):
            out = ra.systolic_ring_decode(
                q, k_all, v_all, pos, ctx.mesh, cfg.systolic_mode,
                use_kernel=cfg.use_kernel,
                topo=_sched(cfg, ctx.mesh, cycle_only=True))
    if out is None:
        slot = jnp.arange(s_cache)
        pos_c = pos[:, None]                                 # [B,1]
        if cfg.sliding_window:
            # ring buffer: entry age = pos - stored position; all valid
            # once full
            wrap = jnp.mod(pos_c, s_cache)
            stored_pos = jnp.where(slot[None] <= wrap,
                                   pos_c - (wrap - slot[None]),
                                   pos_c - (wrap + s_cache - slot[None]))
            valid = jnp.logical_and(stored_pos >= 0,
                                    pos_c - stored_pos < cfg.sliding_window)
        else:
            valid = slot[None] <= pos_c                      # [B, S]

        h, hd = q.shape[2], q.shape[3]
        ke = _expand_kv(k_all, h)
        ve = _expand_kv(v_all, h)
        ke = shard(ke, "cache_batch", "cache_seq", "heads", "head_dim")
        ve = shard(ve, "cache_batch", "cache_seq", "heads", "head_dim")
        scale = 1.0 / math.sqrt(hd)
        scores = jnp.einsum("bshk,bthk->bhst", q.astype(jnp.float32),
                            ke.astype(jnp.float32)) * scale  # [B,H,1,S]
        scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhst,bthk->bshk", probs, ve.astype(jnp.float32))
    out = out.astype(adtype(cfg))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(adtype(cfg)))
    new_pos = pos + 1 if active is None else pos + active.astype(jnp.int32)
    new_cache = {"k": k_store, "v": v_store,
                 "pos": _write_pos(cache["pos"], layer, new_pos)}
    return shard(y, "batch", None, "embed"), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    d = cfg.d_model
    r = cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    h = cfg.num_heads
    ks = jax.random.split(key, 6)
    return {
        "wq": param(ks[0], (d, h, dn + dr), ("w_embed", "heads", "head_dim"), pdtype(cfg)),
        "w_dkv": param(ks[1], (d, r + dr), ("w_embed", None), pdtype(cfg)),
        "kv_norm": param(ks[2], (r,), (None,), pdtype(cfg), init="ones"),
        "w_uk": param(ks[3], (r, h, dn), (None, "heads", "head_dim"), pdtype(cfg)),
        "w_uv": param(ks[4], (r, h, dv), (None, "heads", "head_dim"), pdtype(cfg)),
        "wo": param(ks[5], (h, dv, d), ("heads", "head_dim", "w_embed"), pdtype(cfg)),
    }


def _mla_latent(params, x, cfg: ModelConfig, positions):
    """x -> (normalized latent c [B,S,r], roped shared key k_rope [B,S,dr])."""
    dt = adtype(cfg)
    r = cfg.kv_lora_rank
    ckv = jnp.einsum("bsd,dr->bsr", x.astype(dt), params["w_dkv"].astype(dt))
    c, k_rope = ckv[..., :r], ckv[..., r:]
    c = rms_norm_simple(c, params["kv_norm"])
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c, k_rope


def _mla_queries(params, x, cfg: ModelConfig, positions):
    dt = adtype(cfg)
    dn = cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x.astype(dt), params["wq"].astype(dt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_forward(params, x, cfg: ModelConfig, positions=None):
    """Full-sequence MLA (train / prefill), expanded formulation."""
    b, s, _ = x.shape
    dt = adtype(cfg)
    if positions is None:
        positions = jnp.arange(s)[None, :].astype(jnp.int32)
    q_nope, q_rope = _mla_queries(params, x, cfg, positions)
    c, k_rope = _mla_latent(params, x, cfg, positions)
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)

    if s >= BLOCKED_ATTN_THRESHOLD:
        out = _mla_blocked(params, q_nope, q_rope, c, k_rope, cfg, scale)
    else:
        k_nope = jnp.einsum("bsr,rhk->bshk", c, params["w_uk"].astype(dt))
        v = jnp.einsum("bsr,rhk->bshk", c, params["w_uv"].astype(dt))
        scores = (
            jnp.einsum("bshk,bthk->bhst", q_nope.astype(jnp.float32),
                       k_nope.astype(jnp.float32))
            + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                         k_rope.astype(jnp.float32))
        ) * scale
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhst,bthk->bshk", probs, v.astype(jnp.float32))

    out = out.astype(dt)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))
    return shard(y, "batch", "seq_sp" if cfg.sequence_parallel else "seq",
                 "embed")


def _mla_blocked(params, q_nope, q_rope, c, k_rope, cfg: ModelConfig, scale,
                 kv_block: int = KV_BLOCK):
    """Streaming MLA prefill: expand K/V from latent one block at a time."""
    dt = adtype(cfg)
    b, s, h, dn = q_nope.shape
    dv = cfg.v_head_dim
    nblk = (s + kv_block - 1) // kv_block
    pad = nblk * kv_block - s
    c_p = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    kr_p = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    cb = c_p.reshape(b, nblk, kv_block, -1).swapaxes(0, 1)
    krb = kr_p.reshape(b, nblk, kv_block, -1).swapaxes(0, 1)
    q_pos = jnp.arange(s)
    qn32 = q_nope.astype(jnp.float32)
    qr32 = q_rope.astype(jnp.float32)

    def step(carry, inputs):
        m, l, acc = carry
        cblk, krblk, blk = inputs
        k_pos = blk * kv_block + jnp.arange(kv_block)
        k_nope = jnp.einsum("btr,rhk->bthk", cblk.astype(dt), params["w_uk"].astype(dt))
        vblk = jnp.einsum("btr,rhk->bthk", cblk.astype(dt), params["w_uv"].astype(dt))
        sc = (jnp.einsum("bshk,bthk->bhst", qn32, k_nope.astype(jnp.float32))
              + jnp.einsum("bshk,btk->bhst", qr32, krblk.astype(jnp.float32))) * scale
        mask = jnp.logical_and(k_pos[None, :] <= q_pos[:, None], k_pos[None, :] < s)
        sc = jnp.where(mask[None, None], sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhst,bthk->bshk", p, vblk.astype(jnp.float32)).transpose(0, 2, 1, 3)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    acc0 = jnp.zeros((b, h, s, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (cb, krb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]             # [B,H,S,dv]
    return out.transpose(0, 2, 1, 3)                          # [B,S,H,dv]


def init_mla_cache(cfg: ModelConfig, batch: int, seq_len: int):
    return {
        "c": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), adtype(cfg)),
        "k_rope": jnp.zeros((batch, seq_len, cfg.qk_rope_head_dim), adtype(cfg)),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


MLA_CACHE_AXES = {
    "c": ("cache_batch", "cache_seq", None),
    "k_rope": ("cache_batch", "cache_seq", None),
    "pos": ("cache_batch",),
}


def mla_decode(params, x, cache, cfg: ModelConfig, active=None, layer=None):
    """Absorbed-matrix MLA decode: attention in the latent space.
    ``cache`` and ``layer`` as in :func:`gqa_decode`."""
    dt = adtype(cfg)
    pos = _layer(cache["pos"], layer)                         # [B]
    b = x.shape[0]
    s_cache = cache["c"].shape[-2]
    positions = pos[:, None].astype(jnp.int32)
    q_nope, q_rope = _mla_queries(params, x, cfg, positions)   # [B,1,H,*]
    c_new, kr_new = _mla_latent(params, x, cfg, positions)     # [B,1,r],[B,1,dr]
    write_idx = jnp.minimum(pos, s_cache - 1)
    if active is not None:
        write_idx = jnp.where(active, write_idx, s_cache)
    rows = jnp.arange(b)
    c_store = _write_rows(cache["c"], layer, rows, write_idx, c_new[:, 0])
    kr_store = _write_rows(cache["k_rope"], layer, rows, write_idx,
                           kr_new[:, 0])
    c_all = shard(_layer(c_store, layer), "cache_batch", "cache_seq", None)
    kr_all = shard(_layer(kr_store, layer), "cache_batch", "cache_seq", None)

    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    # absorb: q_lat[b,h,r] = q_nope . W_uk
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope.astype(jnp.float32),
                       params["w_uk"].astype(jnp.float32))
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, c_all.astype(jnp.float32))
              + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                           kr_all.astype(jnp.float32))) * scale
    valid = jnp.arange(c_all.shape[1])[None] <= pos[:, None]  # [B, S]
    scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bhst,btr->bshr", probs, c_all.astype(jnp.float32))
    out = jnp.einsum("bshr,rhk->bshk", ctx_lat, params["w_uv"].astype(jnp.float32))
    y = jnp.einsum("bshk,hkd->bsd", out.astype(dt), params["wo"].astype(dt))
    new_pos = pos + 1 if active is None else pos + active.astype(jnp.int32)
    new_cache = {"c": c_store, "k_rope": kr_store,
                 "pos": _write_pos(cache["pos"], layer, new_pos)}
    return shard(y, "batch", None, "embed"), new_cache


# ---------------------------------------------------------------------------
# Cross-attention (Whisper decoder)
# ---------------------------------------------------------------------------


def init_cross_attention(key, cfg: ModelConfig):
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 5)
    return {
        "wq": param(ks[0], (d, cfg.num_heads, hd), ("w_embed", "heads", "head_dim"), pdtype(cfg)),
        "wk": param(ks[1], (d, cfg.num_kv_heads, hd), ("w_embed", "kv_heads", "head_dim"), pdtype(cfg)),
        "wv": param(ks[2], (d, cfg.num_kv_heads, hd), ("w_embed", "kv_heads", "head_dim"), pdtype(cfg)),
        "wo": param(ks[3], (cfg.num_heads, hd, d), ("heads", "head_dim", "w_embed"), pdtype(cfg)),
        "bq": param(ks[4], (cfg.num_heads, hd), ("heads", "head_dim"), pdtype(cfg), init="zeros"),
    }


def cross_kv(params, memory, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output [B,T,D]."""
    dt = adtype(cfg)
    k = jnp.einsum("btd,dhk->bthk", memory.astype(dt), params["wk"].astype(dt))
    v = jnp.einsum("btd,dhk->bthk", memory.astype(dt), params["wv"].astype(dt))
    return k, v


def cross_attend(params, x, k, v, cfg: ModelConfig):
    """x: [B,S,D] queries against precomputed memory K/V (non-causal)."""
    dt = adtype(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x.astype(dt), params["wq"].astype(dt))
    q = q + params["bq"].astype(dt)
    out = plain_attention(q, k, v, causal=False)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(dt), params["wo"].astype(dt))
    return y
