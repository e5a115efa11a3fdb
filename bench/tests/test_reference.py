"""The float32 reference agrees with the program's own forward pass
(``TransformerLM.prefill``) at smoke size, for both configurations, and its
training step moves the loss the way the program's does."""
import copy
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import reference, weights  # noqa: E402
from bench.spec import model_config  # noqa: E402
from bench.tests.smoke import smoke_cell  # noqa: E402


@pytest.mark.parametrize("workload", ["qwen3-0.6b.chat",
                                      "olmo-1b.batch-decode"])
def test_reference_matches_program_forward(workload):
    from repro.models import build_model
    conf = copy.deepcopy(smoke_cell(workload).config)
    conf["serve_dtype"] = "float32"
    model = build_model(model_config(conf))
    w = weights.make_weights(conf, 7, jnp.float32)
    toks = np.random.default_rng(0).integers(0, conf["vocab_size"], (2, 48))
    with jax.default_matmul_precision("highest"):
        got = model.prefill(w, {"tokens": jnp.asarray(toks)})
    h = reference.hidden(conf, w, jnp.asarray(toks))
    ref = reference.mm("nd,vd->nv", h[:, -1], reference.head_matrix(conf, w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_served_gaps_zero_for_reference_greedy_tokens():
    conf = smoke_cell("qwen3-0.6b.chat").config
    w = weights.make_weights(conf, 11)
    prompt = np.arange(5, 17, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):                       # greedy decode by the reference
        h = reference.hidden(conf, w, jnp.asarray([seq]))
        lg = reference.mm("d,vd->v", h[0, -1], reference.head_matrix(conf, w))
        seq.append(int(jnp.argmax(lg)))
    out = reference.served_gaps(conf, w, [np.asarray(seq)], [len(prompt)],
                                control=True)
    assert out["tokens"] == 6 and out["max_gap"] == 0.0
    bad = np.asarray(seq)
    bad[-1] = (bad[-1] + 1) % conf["vocab_size"]
    assert reference.served_gaps(conf, w, [bad], [len(prompt)])["max_gap"] > 0
    # lengths past one query block pad to whole blocks
    long = np.arange(700, dtype=np.int32) % conf["vocab_size"]
    assert reference.served_gaps(conf, w, [long, bad], [690, len(prompt)],
                                 control=True)["tokens"] == 10 + 6


def test_reference_train_step_lowers_loss():
    cell = smoke_cell("qwen3-0.6b.pretrain-4k")
    from bench import training, traffic
    conf = copy.deepcopy(cell.config)
    conf["train"] = dict(conf["train"], warmup_steps=1, learning_rate=1e-2)
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               weights.make_weights(conf, 3))
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    batch = traffic.train_batch(cell.traffic, 3, 0, conf["vocab_size"])
    losses = []
    for t in range(1, 4):
        w, m, v, loss, g = reference.adamw_step(conf, w, m, v, t, batch)
        losses.append(float(loss))
    assert losses[2] < losses[0]
    assert training.leaf_norms(g).min() > 0
