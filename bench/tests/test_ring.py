"""The ring cell at smoke size on four fake CPU devices. JAX fixes its
device count when it starts, so the runs go in a subprocess with
``XLA_FLAGS`` set, as ``tests/multidev`` does. A whole run through
``run.main``, past the look for a chip, comes out correct with nothing
broken and not correct for each fault the cell can have, the exchange
between chips left out among them; the fp8 control fails the cell's
limit and the program's reading does not."""
import contextlib
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "qwen3-0.6b-ring4.reasoning-6k"
FAULTS = ["none", "cache_unchanged", "half_rows_left_out",
          "exchange_left_out", "token_altered"]


def exchange_left_out(monkeypatch):
    """Every hop of the ring returns what it was given: no chip sees
    another's queries or softmax state."""
    from repro.core import queues
    monkeypatch.setattr(queues, "_raw_hop", lambda topo, x, mode: x)


def main() -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run
    from bench.tests import test_faults
    from bench.tests.control import fails, readings
    from bench.tests.smoke import smoke_cell
    plant = {"none": None, "exchange_left_out": exchange_left_out,
             **{f: getattr(test_faults, f) for f in FAULTS[1:]
                if f != "exchange_left_out"}}
    out = {}
    for fault in FAULTS:
        buf = io.StringIO()
        with pytest.MonkeyPatch.context() as mp, \
                contextlib.redirect_stdout(buf):
            if plant[fault]:
                plant[fault](mp)
            rc = run.main(["--workload", CELL, "--seed", "2147483999",
                           "--seconds", "2", "--trace", "0"],
                          cell=smoke_cell(CELL), require_chip=False)
        r = json.loads(buf.getvalue().strip().splitlines()[-1])
        out[fault] = {"rc": rc, "correct": r["correct"],
                      "devices": r["device"]["count"], "checks": r["checks"]}
    cell = smoke_cell(CELL)
    r = readings(cell, 5, 3.0)
    out["control"] = {"program": r["program"], "control": r["control"],
                      "program_fails": fails(cell.limits, r["program"]),
                      "control_fails": fails(cell.limits, r["control"])}
    print(json.dumps(out), flush=True)


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=1200)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    return json.loads(lines[-1])


@pytest.mark.parametrize("fault", FAULTS)
def test_ring_fault(fault, runs):
    r = runs[fault]
    assert r["rc"] == 0 and r["devices"] == 4
    assert r["correct"] is (fault == "none"), r["checks"]


def test_ring_control_fails(runs):
    r = runs["control"]
    assert not r["program_fails"] and r["control_fails"], r


if __name__ == "__main__":
    main()
