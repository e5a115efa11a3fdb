"""A whole run at smoke size, past the harness's look for a chip, with the
timed path broken underneath: ``correct`` has to come out false for each
fault the cell can have, and true with nothing broken."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from bench import training  # noqa: E402
from bench.tests.control import half_batch_step  # noqa: E402
from bench.tests.smoke import smoke_cell  # noqa: E402


def result(name, capsys, seconds="2"):
    rc = run.main(["--workload", name, "--seed", "2147483999", "--seconds",
                   seconds, "--trace", "0"], cell=smoke_cell(name),
                  require_chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ------------------------------------------------------------- serving
def cache_unchanged(monkeypatch):
    from repro.serve.sharded_cache import DecodeBackend
    step = DecodeBackend.step

    def broken(self, tokens, active):
        old = self.snapshot_cache()
        logits = step(self, tokens, active)
        self.cache = old
        return logits
    monkeypatch.setattr(DecodeBackend, "step", broken)


def half_rows_left_out(monkeypatch):
    import numpy as np
    from repro.serve.sharded_cache import DecodeBackend
    step = DecodeBackend.step

    def broken(self, tokens, active):
        active = np.array(active)
        active[len(active) // 2:] = False
        return step(self, tokens, active)
    monkeypatch.setattr(DecodeBackend, "step", broken)


def token_altered(monkeypatch):
    import numpy as np
    from repro.serve import engine
    sample = engine.sample

    def broken(logits, key, *a):
        tok = np.array(sample(logits, key, *a))
        tok[0] = (tok[0] + 1) % logits.shape[-1]
        return tok
    monkeypatch.setattr(engine, "sample", broken)


@pytest.mark.parametrize("name", ["qwen3-0.6b.chat", "olmo-1b.batch-decode"])
@pytest.mark.parametrize("fault", [None, cache_unchanged, half_rows_left_out,
                                   token_altered])
def test_serving_fault(name, fault, monkeypatch, capsys):
    if fault is not None:
        fault(monkeypatch)
    r = result(name, capsys)
    assert r["correct"] is (fault is None), r["checks"]


# ------------------------------------------------------------ training
def state_unchanged(cfg, tcfg, mesh):
    step = training.program_step(cfg, tcfg, mesh)

    def broken(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return broken


@pytest.mark.parametrize("factory", [None, state_unchanged, half_batch_step])
def test_training_fault(factory, monkeypatch, capsys):
    if factory is not None:
        monkeypatch.setattr(training, "make_step", factory)
    r = result("qwen3-0.6b.pretrain-4k", capsys)
    assert r["correct"] is (factory is None), r["checks"]
