"""Model families and serving backends are plug-ins found by file
(``bench/plugins.py``). The dense family gives the numbers that the
harness gave before families were files: the figures below were computed
by that code and are written in here. A new family or backend is found
with no other file edited, and one that has no file fails, naming it,
before any chip work."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import reference, run, spec, weights, work  # noqa: E402
from bench.tests.smoke import smoke_cell  # noqa: E402

CELLS = ["qwen3-0.6b.chat", "olmo-1b.batch-decode"]

WEIGHTS_SHA256 = {
    "qwen3-0.6b.chat":
        "1680a25443e88d85e71e72f5b31fbece162c4e4d1319a54da7b4f54da7ced739",
    "olmo-1b.batch-decode":
        "5a75b9e2f937655a143a0519256541f7e229fc76f28c4fe27b12c1b28601c18d",
}
# (flops, bytes) of decode_step(32 rows, 32,017 positions), prefill(255)
# and train_step(2 x 4096) at the published widths
WORK = {
    "qwen3-0.6b.chat": {
        "decode_step": (45494272000.0, 4867735552.0),
        "prefill": (232402976768.0, 1221345280.0),
        "train_step": (40841515106304.0, 19073597440.0)},
    "olmo-1b.batch-decode": {
        "decode_step": (79513649152.0, 6554255360.0),
        "prefill": (552092565504.0, 2386952192.0),
        "train_step": (64439004954624.0, 37656461312.0)},
}
GAPS = {
    "qwen3-0.6b.chat": dict(
        tokens=34, max_gap=9.172348976135254, mean_gap=5.752681255340576,
        off_argmax=34, control_max_gap=0.43924522399902344,
        control_off_argmax=3),
    "olmo-1b.batch-decode": dict(
        tokens=34, max_gap=11.538400650024414, mean_gap=5.456145763397217,
        off_argmax=34, control_max_gap=0.40100574493408203,
        control_off_argmax=7),
}
MODEL_CONFIG = {
    "qwen3-0.6b.chat": dict(
        name="qwen3-0.6b", family="dense", num_layers=28, d_model=1024,
        num_heads=16, num_kv_heads=8, head_dim=128, d_ff=3072,
        vocab_size=151936, norm_type="rmsnorm", norm_eps=1e-06,
        qk_norm=True, rope_theta=1000000.0, tie_embeddings=True,
        mlp_kind="swiglu", use_attn_bias=False, dtype="bfloat16",
        param_dtype="bfloat16"),
    "olmo-1b.batch-decode": dict(
        name="olmo-1b", family="dense", num_layers=16, d_model=2048,
        num_heads=16, num_kv_heads=16, head_dim=128, d_ff=8192,
        vocab_size=50304, norm_type="nonparam_ln", norm_eps=1e-05,
        qk_norm=False, rope_theta=10000.0, tie_embeddings=True,
        mlp_kind="swiglu", use_attn_bias=False, dtype="bfloat16",
        param_dtype="bfloat16"),
}


@pytest.mark.parametrize("name", CELLS)
def test_dense_weights_as_before(name):
    import jax
    w = weights.make_weights(smoke_cell(name).config, 2147483999)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[name]


@pytest.mark.parametrize("name", CELLS)
def test_dense_work_as_before(name):
    conf = spec.load_cell(name).config
    assert work.decode_step(conf, 32, 32_017) == WORK[name]["decode_step"]
    assert work.prefill(conf, 255) == WORK[name]["prefill"]
    assert work.train_step(conf, 2, 4096) == WORK[name]["train_step"]


@pytest.mark.parametrize("name", CELLS)
def test_dense_served_gaps_as_before(name):
    conf = smoke_cell(name).config
    v = conf["vocab_size"]
    seqs = [((np.arange(40) * 37 + 11) % v).astype(np.int32),
            ((np.arange(23) * 101 + 5) % v).astype(np.int32)]
    got = reference.served_gaps(conf, weights.make_weights(conf, 11), seqs,
                                [20, 9], control=True)
    assert got == pytest.approx(GAPS[name], rel=1e-5)


@pytest.mark.parametrize("name", CELLS)
def test_dense_model_config_as_before(name):
    from repro.configs.base import ModelConfig
    got = spec.model_config(spec.load_cell(name).config)
    assert got == ModelConfig(**MODEL_CONFIG[name])


# ------------------------------------------------------ found by file
STUB_FAMILY = '''
_dense_model_config = model_config


def model_config(conf):
    import sys
    print("[stub family] model_config", file=sys.stderr)
    return _dense_model_config(conf)
'''

STUB_BACKEND = '''"""A backend that is the dense one under another name."""
import sys


def build(cfg, scfg, params, conf, devices):
    from repro.serve.sharded_cache import DecodeBackend
    print(f"[stub backend] on {len(devices)} device(s)", file=sys.stderr)
    return DecodeBackend(cfg, scfg, params)
'''

DRIVE = '''
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from bench import run
from bench.tests.smoke import smoke_cell
name = "qwen3-stub.chat"
sys.exit(run.main(["--workload", name, "--seed", "2147483999", "--seconds",
                   "2", "--trace", "0"], cell=smoke_cell(name),
                  require_chip=False))
'''


def test_stub_family_and_backend_found_by_file(tmp_path):
    """In a copy of ``bench/``, a family and a backend added as files, a
    configuration naming them and a cell held out of BENCHMARK.json run
    a whole smoke run, correct, with no file of the copy edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = tmp_path / "bench"
    (bench / "families" / "stub.py").write_text(
        (ROOT / "bench" / "families" / "dense.py").read_text()
        + STUB_FAMILY)
    (bench / "backends" / "stub.py").write_text(STUB_BACKEND)
    conf = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    conf.update(name="qwen3-stub", family="stub")
    conf["serve"] = dict(conf["serve"], backend="stub")
    (bench / "configs" / "qwen3-stub.json").write_text(json.dumps(conf))
    (bench / "cells" / "qwen3-stub.chat.json").write_text(json.dumps({
        "held_out": {"workload": {"name": "qwen3-stub.chat",
                                  "config": "qwen3-stub", "traffic": "chat",
                                  "chips": 1}},
        "limits": {"max_logit_gap": 0.5}}))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", DRIVE, str(tmp_path), str(ROOT / "src")],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "[stub family] model_config" in proc.stderr
    assert "[stub backend] on 1 device(s)" in proc.stderr
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("kind", ["family", "backend"])
def test_unknown_plugin_fails_before_chip_work(kind, monkeypatch, capsys):
    import jax
    cell = smoke_cell("qwen3-0.6b.chat")
    if kind == "family":
        cell.config["family"] = "nonexistent"
        missing = "bench/families/nonexistent.py"
    else:
        cell.config["serve"] = dict(cell.config["serve"],
                                    backend="nonexistent")
        missing = "bench/backends/nonexistent.py"

    def no_devices(*a, **k):
        raise AssertionError("looked for a chip")
    monkeypatch.setattr(jax, "devices", no_devices)
    rc = run.main(["--workload", cell.name, "--seed", "1", "--seconds", "1"],
                  cell=cell, require_chip=False)
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert missing in out.err and "is missing" in out.err
