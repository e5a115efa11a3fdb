"""The decode step writes each token's cache entries into the stacked
per-layer cache in place. Held here, bit for bit, against the form it
replaced: every layer's cache sliced out of the stack, decoded with the
per-layer attention, and written back whole."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import attention as attn
from repro.models import build_model, moe as moe_lib, split_tree
from repro.models.common import apply_mlp, apply_norm, embed, lm_logits

B, S = 4, 32
# rows that sit out a step keep their cache and position untouched
ACTIVE = [[True, False, True, True], [False, True, True, False],
          [True, True, False, True], [True, True, True, True],
          [False, False, True, False]]


def _decode_by_slices(model, params, cache, tokens, active):
    """The decode step as each layer's slice, read out and written back."""
    cfg = model.cfg
    decode = attn.mla_decode if cfg.attention_type == "mla" \
        else attn.gqa_decode

    def layers(stack, caches, moe_layer, x):
        def body(carry, inp):
            x, caches = carry
            lp, i = inp
            c = jax.tree_util.tree_map(lambda a: a[i], caches)
            h = apply_norm(lp["norm1"], x, cfg)
            a, c = decode(lp["attn"], h, c, cfg, active=active)
            x = x + a
            h = apply_norm(lp["norm2"], x, cfg)
            y = (moe_lib.apply_moe(lp["moe"], h, cfg)[0] if moe_layer
                 else apply_mlp(lp["mlp"], h, cfg))
            caches = jax.tree_util.tree_map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, i, 0),
                caches, c)
            return (x + y, caches), None

        n = caches["pos"].shape[0]
        (x, caches), _ = jax.lax.scan(body, (x, caches),
                                      (stack, jnp.arange(n)))
        return x, caches

    x = embed(params["embed"], tokens, cfg)
    new = dict(cache)
    if cfg.first_k_dense:
        x, new["dense_layers"] = layers(params["dense_layers"],
                                        cache["dense_layers"], False, x)
    x, new["layers"] = layers(params["layers"], cache["layers"], model.moe, x)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params.get("head", {}), params["embed"], x, cfg)
    return logits[:, 0], new


def _filled_cache(model, key):
    """A cache with random entries and rows at different positions."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(model.init_cache(B, S))
    pos = jax.random.randint(key, (B,), 0, S - len(ACTIVE))

    def fill(path, leaf, k):
        if path[-1].key == "pos":
            return jnp.broadcast_to(pos, leaf.shape).astype(leaf.dtype)
        return jax.random.normal(k, leaf.shape, jnp.float32).astype(leaf.dtype)
    keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
    return jax.tree_util.tree_unflatten(
        tree, [fill(p, l, k) for (p, l), k in zip(leaves, keys)])


def _row(cache, r):
    """Row ``r`` of every leaf ([L, B, ...] -> [L, ...])."""
    return jax.tree_util.tree_map(lambda a: np.asarray(a[:, r]), cache)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v2-lite-16b"])
def test_decode_step_matches_per_layer_slices(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    cache = _filled_cache(model, jax.random.PRNGKey(1))
    step = jax.jit(model.decode_step)
    ref = jax.jit(lambda p, c, t, a: _decode_by_slices(model, p, c, t, a))
    ref_cache = cache
    for t, mask in enumerate(ACTIVE):
        tokens = jax.random.randint(jax.random.PRNGKey(10 + t), (B, 1), 0,
                                    cfg.vocab_size)
        active = jnp.asarray(mask)
        logits, new = step(params, cache, tokens, active)
        ref_logits, ref_cache = ref(params, ref_cache, tokens, active)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                       np.asarray(b)),
            new, ref_cache)
        for r in range(B):
            if not mask[r]:
                jax.tree_util.tree_map(np.testing.assert_array_equal,
                                       _row(new, r), _row(cache, r))
        cache = new
