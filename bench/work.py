"""The work a step needs, counted from the configuration and the step's
real inputs, whatever implements it. Each family (``families/<family>.py``)
counts its own, by these rules:

* weights are read once per call;
* a decode step reads the K/V of the valid positions of its active rows
  once and writes each active row's new K/V once;
* a prefill computes the prompt's own tokens once (not a padded chunk, not
  one copy per cache row) and the logits of its last position;
* a train step is 6 x (matmul parameters) x tokens plus causal attention
  forward and backward (3 x forward), with no recomputation.

Matrix products count 2 operations per multiply-add; element-wise work
is not counted. The work is the whole call's, however many chips share
it: a weight replicated on every chip is counted once.
"""
from __future__ import annotations

from bench import plugins


def decode_step(conf: dict, rows: int, positions: int) -> tuple[float, float]:
    """(flops, bytes) of one decode step over ``rows`` active rows whose
    caches held ``positions`` valid positions in all before the step."""
    return plugins.family(conf).decode_step(conf, rows, positions)


def prefill(conf: dict, n: int) -> tuple[float, float]:
    """(flops, bytes) of block-prefilling an ``n``-token prompt: its K/V
    written, the logits of its last position."""
    return plugins.family(conf).prefill(conf, n)


def train_step(conf: dict, batch: int, seq: int,
               master_weights: bool = True) -> tuple[float, float]:
    """(flops, bytes) of one AdamW train step on ``batch`` x ``seq``
    tokens: forward and backward, and the optimizer reading and writing
    its state once (bf16 weights, fp32 gradients, moments, master)."""
    return plugins.family(conf).train_step(conf, batch, seq, master_weights)
