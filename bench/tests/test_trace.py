"""The trace reduction on a trace recorded on one TPU v5 lite: three decode
ticks of qwen3-0.6b (32 slots x 2048), a slot reset, a block prefill and a
fourth tick, inside a host span ``bench_window`` with ``tick`` spans."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import tracing  # noqa: E402

TRACE = str(Path(__file__).parent / "data" / "decode.xplane.pb.gz")


@pytest.fixture(scope="module")
def red():
    return tracing.reduce(TRACE, host_spans=("tick",))


def test_programs_by_stable_name(red):
    p = red["programs"]
    assert p["decode_step"]["count"] == 4
    assert p["prefill_into_cache"]["count"] == 1
    assert p["zero_row"]["count"] == 1
    # module durations read off the trace by hand
    assert p["decode_step"]["device_s"] == pytest.approx(0.620855, rel=1e-4)
    assert p["prefill_into_cache"]["device_s"] == pytest.approx(0.070707,
                                                                rel=1e-3)


def test_window_busy_and_idle(red):
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(0.712558, rel=1e-4)
    modules = sum(p["device_s"] for p in red["programs"].values())
    # ops run inside modules; busy is their union, a little under the sum
    # of module spans
    assert 0.95 * modules < red["busy_s"] <= modules + 1e-6
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert set(idle) <= {"tick", "other"}


def test_top_ops_are_self_time_by_program(red):
    names = [n for n, _ in red["device_ops"]]
    assert all("/" in n for n in names)
    # the while loop holds the layer body: its self time is small, and the
    # fp32 K/V widening of the decode step leads
    assert names[0].startswith("decode_step/broadcast")
    total = sum(t for _, t in red["device_ops"])
    assert total <= red["busy_s"] + 1e-9
    assert red["collective_exposed_s"] == 0


def test_union_and_self_time_helpers():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    ev = [("loop", 0, 10), ("a", 1, 3), ("b", 4, 9), ("c", 5, 6)]
    assert tracing.self_times(ev) == {"loop": 3, "a": 2, "b": 4, "c": 1}
    assert tracing.program_name("jit_decode_step(123)") == "decode_step"
