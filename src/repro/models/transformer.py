"""Decoder-only transformer LM: granite / qwen3 / olmo backbones, the
Mixtral & DeepSeek MoE variants, and the InternVL2 VLM fusion.

Layers are scanned (stacked params, O(1) HLO in depth) with remat applied to
the block body per ``cfg.remat``. MoE models scan the homogeneous MoE stack
and run the ``first_k_dense`` leading layers explicitly (DeepSeek-V2).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.obs import linkstats
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.common import (
    adtype,
    shard_residual,
    apply_mlp,
    apply_norm,
    embed,
    init_embedding,
    init_lm_head,
    init_mlp,
    init_norm,
    lm_logits,
    lm_loss_chunked,
    param,
    pdtype,
    shard,
    stack_init,
)


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "selective":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, moe_layer: bool = False):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {
        "norm1": init_norm(ks[0], cfg),
        "norm2": init_norm(ks[1], cfg),
    }
    if cfg.attention_type == "mla":
        p["attn"] = attn.init_mla(ks[2], cfg)
    else:
        p["attn"] = attn.init_gqa(ks[2], cfg)
    if moe_layer:
        p["moe"] = moe_lib.init_moe(ks[3], cfg)
    else:
        d_ff = cfg.d_ff_dense if (cfg.family == "moe" and cfg.d_ff_dense) else cfg.d_ff
        p["mlp"] = init_mlp(ks[3], cfg, d_ff=d_ff)
    return p


def _maybe_systolic_mlp(lp_mlp, h, cfg: ModelConfig):
    """Route the FFN through the paper's ring schedules when enabled.

    cfg.systolic_mode in {sw, xqueue, qlr} + an active mesh context + shapes
    that divide -> systolic sequence-parallel SwiGLU (AG-ring in, RS-ring
    out); otherwise the baseline einsum path. The same switch routes the
    attention core through core/ring_attention (wired inside
    attention.gqa_forward, gated by ring_attn_applicable) so a systolic
    block streams *both* its FFN and its K/V operands over queue links.
    """
    from repro.models.common import current_ctx
    ctx = current_ctx()
    if (cfg.systolic_mode != "baseline" and cfg.mlp_kind == "swiglu"
            and ctx is not None):
        from repro.core import collective_matmul as cm
        if cm.ffn_applicable(h, lp_mlp["w_gate"].shape[-1], ctx.mesh):
            dt = adtype(cfg)
            return cm.systolic_ffn(
                h.astype(dt), lp_mlp["w_gate"].astype(dt),
                lp_mlp["w_up"].astype(dt), lp_mlp["w_down"].astype(dt),
                mesh=ctx.mesh, mode=cfg.systolic_mode,
                use_kernel=cfg.use_kernel)
    return apply_mlp(lp_mlp, h, cfg)


def block_forward(lp, x, cfg: ModelConfig, moe_layer: bool = False):
    """Returns (x, aux_loss)."""
    h = apply_norm(lp["norm1"], x, cfg)
    if cfg.attention_type == "mla":
        a = attn.mla_forward(lp["attn"], h, cfg)
    else:
        a = attn.gqa_forward(lp["attn"], h, cfg)
    x = x + a
    h = apply_norm(lp["norm2"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if moe_layer:
        y, aux = moe_lib.apply_moe(lp["moe"], h, cfg)
    else:
        y = _maybe_systolic_mlp(lp["mlp"], h, cfg)
    return shard_residual(x + y, cfg), aux


def block_prefill(lp, x, cfg: ModelConfig, moe_layer: bool = False):
    """block_forward variant that also returns the post-rope K/V of the
    attention sublayer, for seeding a decode cache (multi-token prefill).
    Routes through ring_attention when cfg.systolic_mode is a link mode."""
    h = apply_norm(lp["norm1"], x, cfg)
    a, (k, v) = attn.gqa_forward(lp["attn"], h, cfg, return_kv=True)
    x = x + a
    h = apply_norm(lp["norm2"], x, cfg)
    if moe_layer:
        y, _ = moe_lib.apply_moe(lp["moe"], h, cfg)
    else:
        y = _maybe_systolic_mlp(lp["mlp"], h, cfg)
    return shard_residual(x + y, cfg), (k, v)


def block_decode(lp, x, cache, layer, cfg: ModelConfig,
                 moe_layer: bool = False, active=None):
    """One decoder block on the stacked cache: the attention sublayer
    writes this step's entries of layer ``layer`` in place."""
    h = apply_norm(lp["norm1"], x, cfg)
    decode = attn.mla_decode if cfg.attention_type == "mla" else attn.gqa_decode
    a, cache = decode(lp["attn"], h, cache, cfg, active=active, layer=layer)
    x = x + a
    h = apply_norm(lp["norm2"], x, cfg)
    if moe_layer:
        y, _ = moe_lib.apply_moe(lp["moe"], h, cfg)
    else:
        y = apply_mlp(lp["mlp"], h, cfg)
    return x + y, cache


def _scan_decode(layer_fn, x, params, cache):
    """``layer_fn(lp, x, cache, i) -> (x, cache)`` over the stacked layers.
    The whole stacked cache rides in the loop carry; layer ``i`` writes
    only its new rows into it, at index ``i``, and reads its K/V where they
    lie, so no layer's cache is sliced out or written back. (A scan with
    the cache as xs/ys would stack a second cache, which at serving sizes
    does not fit next to the first.)"""
    n = jax.tree_util.tree_leaves(cache)[0].shape[0]

    def body(carry, inp):
        x, cache = carry
        lp, i = inp
        return layer_fn(lp, x, cache, i), None

    (x, cache), _ = linkstats.scan(body, (x, cache), (params, jnp.arange(n)))
    return x, cache


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class TransformerLM:
    """granite / qwen3 / olmo / mixtral / deepseek / internvl backbone."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.n_scanned = cfg.num_layers - cfg.first_k_dense
        self.moe = cfg.family == "moe"

    # ------------------------------------------------------------- params
    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        p: dict[str, Any] = {
            "embed": init_embedding(ks[0], cfg),
            "final_norm": init_norm(ks[1], cfg),
            "head": init_lm_head(ks[2], cfg),
            "layers": stack_init(
                lambda k: init_block(k, cfg, moe_layer=self.moe), ks[3],
                self.n_scanned),
        }
        if cfg.first_k_dense:
            p["dense_layers"] = stack_init(
                lambda k: init_block(k, cfg, moe_layer=False), ks[4],
                cfg.first_k_dense)
        if cfg.family == "vlm":
            kp = jax.random.split(ks[5], 3)
            p["projector"] = {
                "w1": param(kp[0], (cfg.vit_dim, cfg.d_model), (None, "w_embed"),
                            pdtype(cfg)),
                "w2": param(kp[1], (cfg.d_model, cfg.d_model), ("w_embed", None),
                            pdtype(cfg)),
                "norm": init_norm(kp[2], cfg, d=cfg.vit_dim),
            }
        return p

    # ------------------------------------------------------------- forward
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        x = embed(params["embed"], batch["tokens"], cfg)
        if cfg.family == "vlm" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(adtype(cfg))
            pe = apply_norm(params["projector"]["norm"], pe, cfg)
            pe = jnp.einsum("bpv,vd->bpd", pe,
                            params["projector"]["w1"].astype(adtype(cfg)))
            pe = jax.nn.gelu(pe)
            pe = jnp.einsum("bpd,de->bpe", pe,
                            params["projector"]["w2"].astype(adtype(cfg)))
            # image tokens occupy the sequence prefix (stub fusion)
            np_ = min(pe.shape[1], x.shape[1])
            x = jax.lax.dynamic_update_slice_in_dim(x, pe[:, :np_], 0, axis=1)
            x = shard(x, "batch", "seq", "embed")
        return x

    def hidden_states(self, params, batch):
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        aux_total = jnp.zeros((), jnp.float32)

        if cfg.first_k_dense:
            def dense_body(x, lp):
                y, aux = block_forward(lp, x, cfg, moe_layer=False)
                return y, aux
            dense_body = _remat(dense_body, cfg)
            x, auxs = jax.lax.scan(dense_body, x, params["dense_layers"])
            aux_total = aux_total + jnp.sum(auxs)

        def body(x, lp):
            y, aux = block_forward(lp, x, cfg, moe_layer=self.moe)
            return y, aux
        body = _remat(body, cfg)
        x, auxs = jax.lax.scan(body, x, params["layers"])
        aux_total = aux_total + jnp.sum(auxs)
        x = apply_norm(params["final_norm"], x, cfg)
        return x, aux_total

    def loss(self, params, batch):
        x, aux = self.hidden_states(params, batch)
        mask = batch.get("mask")
        ce = lm_loss_chunked(params.get("head", {}), params["embed"], x,
                             batch["targets"], self.cfg, mask=mask)
        return ce + aux, {"ce": ce, "aux": aux}

    def prefill(self, params, batch):
        """Forward pass returning last-position logits (inference prefill)."""
        x, _ = self.hidden_states(params, batch)
        logits = lm_logits(params.get("head", {}), params["embed"],
                           x[:, -1:], self.cfg)
        return logits[:, 0]

    # ------------------------------------------------------------- decode
    def init_cache(self, batch: int, seq_len: int):
        cfg = self.cfg
        if cfg.attention_type == "mla":
            one = lambda: attn.init_mla_cache(cfg, batch, seq_len)
        else:
            one = lambda: attn.init_gqa_cache(cfg, batch, seq_len)
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[one() for _ in range(self.n_scanned)])
        cache = {"layers": stacked}
        if cfg.first_k_dense:
            cache["dense_layers"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[one() for _ in range(cfg.first_k_dense)])
        return cache

    def cache_axes(self):
        cfg = self.cfg
        axes = (attn.MLA_CACHE_AXES if cfg.attention_type == "mla"
                else attn.GQA_CACHE_AXES)
        padded = {k: (None,) + tuple(v) for k, v in axes.items()}
        out = {"layers": dict(padded)}
        if cfg.first_k_dense:
            out["dense_layers"] = dict(padded)
        return out

    def prefill_into_cache(self, params, cache, tokens, row, length):
        """Batched prefill of one slot: run the full-sequence forward over
        ``tokens`` [C] and write the post-rope K/V of positions [0, C) into
        cache row ``row``, setting its position to ``length``.

        ``length`` <= C masks nothing in the forward (pad positions past it
        are computed but their cache slots are never read before the decode
        loop overwrites them: slot validity is ``slot <= pos``). In systolic
        modes the forward's attention core is the existing ring_attention
        schedule, so prefill streams K/V blocks over the same links the
        decode hop uses. The forward runs at the cache's full slot-batch
        width (every row sees the same tokens; only ``row`` is written) so
        the systolic paths' batch sharding stays applicable — the redundant
        rows are the price of a fixed-shape jitted prefill. GQA-family
        caches only (no MLA / sliding window).

        Returns (logits [V] at position length-1, new cache).
        """
        cfg = self.cfg
        assert cfg.attention_type == "gqa" and not cfg.sliding_window
        c = tokens.shape[0]
        b = cache["layers"]["pos"].shape[1]
        x = embed(params["embed"],
                  jnp.broadcast_to(tokens[None], (b, c)), cfg)  # [B,C,D]

        def write(cache_leafs, kv):
            k, v = kv                                         # [L,B,C,Kv,hd]
            new = dict(cache_leafs)
            new["k"] = cache_leafs["k"].at[:, row, :c].set(k[:, 0])
            new["v"] = cache_leafs["v"].at[:, row, :c].set(v[:, 0])
            new["pos"] = jnp.where(
                jnp.arange(cache_leafs["pos"].shape[1])[None] == row,
                length.astype(cache_leafs["pos"].dtype), cache_leafs["pos"])
            return new

        new_cache = dict(cache)
        if cfg.first_k_dense:
            def dbody(x, lp):
                y, kv = block_prefill(lp, x, cfg, moe_layer=False)
                return y, kv
            x, kvs = linkstats.scan(dbody, x, params["dense_layers"])
            new_cache["dense_layers"] = write(cache["dense_layers"], kvs)

        def body(x, lp):
            y, kv = block_prefill(lp, x, cfg, moe_layer=self.moe)
            return y, kv
        x, kvs = linkstats.scan(body, x, params["layers"])
        new_cache["layers"] = write(cache["layers"], kvs)

        x = apply_norm(params["final_norm"], x, cfg)
        logits = lm_logits(params.get("head", {}), params["embed"], x, cfg)
        last = jax.lax.dynamic_index_in_dim(logits[0], length - 1, 0,
                                            keepdims=False)
        return last, new_cache

    def decode_step(self, params, cache, tokens, active=None):
        """tokens: [B,1] -> (logits [B,V], new cache). ``active`` [B] masks
        rows that should not consume a step (continuous batching)."""
        cfg = self.cfg
        x = embed(params["embed"], tokens, cfg)
        new_cache = dict(cache)

        if cfg.first_k_dense:
            x, new_cache["dense_layers"] = _scan_decode(
                lambda lp, x, c, i: block_decode(lp, x, c, i, cfg,
                                                 moe_layer=False,
                                                 active=active),
                x, params["dense_layers"], cache["dense_layers"])
        x, new_cache["layers"] = _scan_decode(
            lambda lp, x, c, i: block_decode(lp, x, c, i, cfg,
                                             moe_layer=self.moe,
                                             active=active),
            x, params["layers"], cache["layers"])

        x = apply_norm(params["final_norm"], x, cfg)
        logits = lm_logits(params.get("head", {}), params["embed"], x, cfg)
        return logits[:, 0], new_cache
