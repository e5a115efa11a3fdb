"""The dense transformer family: pre-norm decoder, RMSNorm or
non-parametric LayerNorm, optional RMS qk-norm, rotary embeddings with the
rotate-half layout, grouped-query attention, SwiGLU, tied embeddings.
A configuration file with no ``"family"`` key is of this family.

A family is one file ``bench/families/<family>.py``, chosen by the
configuration file's ``"family"`` key (``bench/plugins.py``). It defines,
each taking the configuration file's dict ``conf``:

* ``model_config(conf)``: the program's ``ModelConfig``. It is the one
  function that imports the program, and does so lazily.
* ``layout(conf)``: ``{path: (shape, std or "norm")}`` of every weight,
  and ``skeleton(conf)``: the program's parameter tree with empty dicts
  where the leaves go. ``weights.py`` fills it in sorted-path order, and
  a leaf's rank there is its ``fold_in`` index.
* ``hidden(conf, w, tokens, fp8=False)`` and ``head_matrix(conf, w)``: the
  plain float32 reference, importing nothing of the program: the final-
  normed hidden states ``[n, L, d]`` of tokens ``[n, L]``, and the
  ``[V, d]`` rows whose dot with one is the logit. ``fp8=True`` is the
  control: both operands of every product rounded to float8 e4m3
  (``reference.mm``).
* ``decode_step(conf, rows, positions)``, ``prefill(conf, n)`` and
  ``train_step(conf, batch, seq, master_weights)``: the (flops, bytes) a
  call needs, as ``work.py`` defines them.
* ``smoke(conf)``: the configuration cut to a size the CPU runs in
  seconds, for the tests.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench import reference
from bench.reference import Q_BLOCK, mm


# ------------------------------------------------------- program config
def model_config(conf: dict):
    """The program's ``ModelConfig`` (Hugging Face key names)."""
    from repro.configs.base import ModelConfig
    norm = conf["norm"]
    eps = conf.get("rms_norm_eps", conf.get("assumed", {}).get(
        "layer_norm_eps", 1e-5))
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or
        conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        norm_type=norm, norm_eps=float(eps), qk_norm=conf["qk_norm"],
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"], mlp_kind="swiglu",
        use_attn_bias=conf["attention_bias"],
        dtype=conf["serve_dtype"], param_dtype=conf["serve_dtype"])


# -------------------------------------------------------------- weights
def layout(conf: dict) -> dict:
    """{path: (shape, std or 'norm')} in the program's parameter tree."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    n, v = conf["num_hidden_layers"], conf["vocab_size"]
    out = {("embed", "table"): ((v, d), 2.0 / math.sqrt(d)),
           ("layers", "attn", "wq"): ((n, d, h, hd), 1 / math.sqrt(d)),
           ("layers", "attn", "wk"): ((n, d, kv, hd), 1 / math.sqrt(d)),
           ("layers", "attn", "wv"): ((n, d, kv, hd), 1 / math.sqrt(d)),
           ("layers", "attn", "wo"): ((n, h, hd, d), 1 / math.sqrt(h * hd)),
           ("layers", "mlp", "w_gate"): ((n, d, f), 1 / math.sqrt(d)),
           ("layers", "mlp", "w_up"): ((n, d, f), 1 / math.sqrt(d)),
           ("layers", "mlp", "w_down"): ((n, f, d), 1 / math.sqrt(f))}
    if conf["qk_norm"]:
        out[("layers", "attn", "q_norm")] = ((n, hd), "norm")
        out[("layers", "attn", "k_norm")] = ((n, hd), "norm")
    if conf["norm"] == "rmsnorm":
        out[("layers", "norm1", "scale")] = ((n, d), "norm")
        out[("layers", "norm2", "scale")] = ((n, d), "norm")
        out[("final_norm", "scale")] = ((d,), "norm")
    if not conf["tie_word_embeddings"]:
        out[("head", "w")] = ((d, v), 1 / math.sqrt(d))
    return out


def skeleton(conf: dict) -> dict:
    return {"embed": {}, "final_norm": {}, "head": {},
            "layers": {"attn": {}, "mlp": {}, "norm1": {}, "norm2": {}}}


# ------------------------------------------------------------ reference
def norm(x, scale, kind: str, eps: float):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * scale.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)      # nonparametric


def rope(x, pos, theta: float):
    """Rotate-half rotary embedding. x [n, L, heads, hd], pos [L]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]        # [L, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, fp8: bool):
    """Causal softmax attention over query blocks. q [n,L,H,hd], k/v
    [n,L,Kv,hd] -> [n,L,H,hd]."""
    n, L, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = min(Q_BLOCK, L)
    if L % qb:
        raise ValueError(f"{L} positions are not a whole number of "
                         f"{qb}-query blocks")
    qs = q.reshape(n, L // qb, qb, H, hd).swapaxes(0, 1)

    @jax.checkpoint
    def block(args):
        qi, i = args
        s = mm("nqhd,nkhd->nhqk", qi, k, fp8, -1, -1) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        mask = jnp.arange(L)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("nhqk,nkhd->nqhd", p, v, fp8, -1, 1)

    out = jax.lax.map(block, (qs, jnp.arange(L // qb)))
    return out.swapaxes(0, 1).reshape(n, L, H, hd)


def layer(conf: dict, fp8: bool, x, lp):
    kind, eps = conf["norm"], _eps(conf)
    pos = jnp.arange(x.shape[1])
    h = norm(x, lp.get("norm1", {}).get("scale"), kind, eps)
    a = lp["attn"]
    q = mm("nld,dhk->nlhk", h, a["wq"], fp8)
    k = mm("nld,dhk->nlhk", h, a["wk"], fp8)
    v = mm("nld,dhk->nlhk", h, a["wv"], fp8)
    if conf["qk_norm"]:
        q = norm(q, a["q_norm"], "rmsnorm", _eps(conf))
        k = norm(k, a["k_norm"], "rmsnorm", _eps(conf))
    q = rope(q, pos, float(conf["rope_theta"]))
    k = rope(k, pos, float(conf["rope_theta"]))
    o = attention(q, k, v, fp8)
    x = x + mm("nlhk,hkd->nld", o, a["wo"], fp8, (-2, -1))
    h = norm(x, lp.get("norm2", {}).get("scale"), kind, eps)
    m = lp["mlp"]
    g = mm("nld,df->nlf", h, m["w_gate"], fp8)
    u = mm("nld,df->nlf", h, m["w_up"], fp8)
    return x + mm("nlf,fd->nld", jax.nn.silu(g) * u, m["w_down"], fp8)


def _eps(conf: dict) -> float:
    return float(conf.get("rms_norm_eps",
                          conf.get("assumed", {}).get("layer_norm_eps", 1e-5)))


def hidden(conf: dict, w, tokens, fp8: bool = False):
    """Final-normed hidden states [n, L, d] for tokens [n, L]."""
    x = jnp.take(w["embed"]["table"].astype(reference.LOOKUP_DTYPE), tokens,
                 axis=0).astype(jnp.float32)
    body = jax.checkpoint(partial(layer, conf, fp8))
    x, _ = jax.lax.scan(lambda x, lp: (body(x, lp), None), x, w["layers"])
    return norm(x, w["final_norm"].get("scale"), conf["norm"], _eps(conf))


def head_matrix(conf: dict, w):
    """[V, d] rows whose dot with a hidden state is the logit."""
    if conf["tie_word_embeddings"]:
        return w["embed"]["table"]
    return w["head"]["w"].T


# ----------------------------------------------------------------- work
def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    return dict(d=d, f=conf["intermediate_size"], h=h, kv=kv, hd=hd,
                L=conf["num_hidden_layers"], V=conf["vocab_size"])


def layer_matmul_params(conf: dict) -> int:
    """Weights one token multiplies by in one layer."""
    x = dims(conf)
    return x["d"] * (x["h"] + 2 * x["kv"]) * x["hd"] + \
        x["h"] * x["hd"] * x["d"] + 3 * x["d"] * x["f"]


def matmul_params(conf: dict) -> int:
    """Weights one token multiplies by: every layer and the LM head."""
    x = dims(conf)
    return x["L"] * layer_matmul_params(conf) + x["V"] * x["d"]


def weight_bytes(conf: dict, itemsize: int = 2) -> int:
    """Every stored weight: layers, norms, embedding (and head if untied)."""
    x = dims(conf)
    norms = 0
    if conf["norm"] == "rmsnorm":
        norms += (2 * x["L"] + 1) * x["d"]
    if conf["qk_norm"]:
        norms += 2 * x["L"] * x["hd"]
    emb = x["V"] * x["d"] * (1 if conf["tie_word_embeddings"] else 2)
    return itemsize * (x["L"] * layer_matmul_params(conf) + norms + emb)


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    x = dims(conf)
    return 2 * x["L"] * x["kv"] * x["hd"] * itemsize


def attn_flops(conf: dict, q_tokens: int, k_tokens: int) -> float:
    """Scores and weighted sum for ``q_tokens`` queries over ``k_tokens``
    keys each, in every layer."""
    x = dims(conf)
    return 4.0 * x["L"] * x["h"] * x["hd"] * q_tokens * k_tokens


def decode_step(conf: dict, rows: int, positions: int) -> tuple[float, float]:
    x = dims(conf)
    flops = 2.0 * matmul_params(conf) * rows
    flops += 4.0 * x["L"] * x["h"] * x["hd"] * (positions + rows)
    nbytes = weight_bytes(conf) + kv_bytes_per_token(conf) * (positions + rows)
    return flops, float(nbytes)


def prefill(conf: dict, n: int) -> tuple[float, float]:
    x = dims(conf)
    flops = 2.0 * x["L"] * layer_matmul_params(conf) * n
    flops += attn_flops(conf, 1, 1) * n * (n + 1) / 2
    flops += 2.0 * x["V"] * x["d"]
    nbytes = weight_bytes(conf) + kv_bytes_per_token(conf) * n
    return flops, float(nbytes)


def train_step(conf: dict, batch: int, seq: int,
               master_weights: bool = True) -> tuple[float, float]:
    flops = 6.0 * matmul_params(conf) * batch * seq
    flops += 3 * attn_flops(conf, 1, 1) * batch * seq * (seq + 1) / 2
    n_params = weight_bytes(conf) // 2
    per_param = 2 + 2 + 4 + 8 + 8 + (8 if master_weights else 0)
    return flops, float(n_params * per_param)


# ---------------------------------------------------------------- smoke
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "head_dim": 16, "vocab_size": 512}


def smoke(conf: dict) -> dict:
    kv = 2 if conf["num_key_value_heads"] < conf["num_attention_heads"] else 4
    return {**conf, **SMALL, "num_key_value_heads": kv}
