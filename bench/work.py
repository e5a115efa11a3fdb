"""The work a step needs, counted from the configuration and the step's
real inputs, whatever implements it.

* weights are read once per call;
* a decode step reads the K/V of the valid positions of its active rows
  once and writes each active row's new K/V once;
* a prefill computes the prompt's own tokens once (not a padded chunk, not
  one copy per cache row) and the logits of its last position;
* a train step is 6 x (matmul parameters) x tokens plus causal attention
  forward and backward (3 x forward), with no recomputation.

Matrix products count 2 operations per multiply-add; element-wise work
is not counted.
"""
from __future__ import annotations


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
    """(flops, bytes) of one decode step over ``rows`` active rows whose
    caches held ``positions`` valid positions in all before the step."""
    x = dims(conf)
    flops = 2.0 * matmul_params(conf) * rows
    flops += 4.0 * x["L"] * x["h"] * x["hd"] * (positions + rows)
    nbytes = weight_bytes(conf) + kv_bytes_per_token(conf) * (positions + rows)
    return flops, float(nbytes)


def prefill(conf: dict, n: int) -> tuple[float, float]:
    """(flops, bytes) of block-prefilling an ``n``-token prompt: its K/V
    written, the logits of its last position."""
    x = dims(conf)
    flops = 2.0 * x["L"] * layer_matmul_params(conf) * n
    flops += attn_flops(conf, 1, 1) * n * (n + 1) / 2
    flops += 2.0 * x["V"] * x["d"]
    nbytes = weight_bytes(conf) + kv_bytes_per_token(conf) * n
    return flops, float(nbytes)


def train_step(conf: dict, batch: int, seq: int,
               master_weights: bool = True) -> tuple[float, float]:
    """(flops, bytes) of one AdamW train step on ``batch`` x ``seq``
    tokens: forward and backward, and the optimizer reading and writing
    its state once (bf16 weights, fp32 gradients, moments, master)."""
    flops = 6.0 * matmul_params(conf) * batch * seq
    flops += 3 * attn_flops(conf, 1, 1) * batch * seq * (seq + 1) / 2
    n_params = weight_bytes(conf) // 2
    per_param = 2 + 2 + 4 + 8 + 8 + (8 if master_weights else 0)
    return flops, float(n_params * per_param)
