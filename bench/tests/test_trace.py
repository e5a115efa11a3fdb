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


@pytest.mark.parametrize("text,name,coll", [
    ("%collective-permute-start.1 = (bf16[8]{0}, bf16[8]{0}, u32[], u32[]) "
     "collective-permute-start(bf16[8]{0} %x), source_target_pairs={{0,1}}",
     "collective-permute-start.1", True),
    ("%cp = bf16[8]{0:T(512)} collective-permute(bf16[8]{0} %x)", "cp", True),
    ("%all-gather-fusion.2 = bf16[8]{0} fusion(bf16[2]{0} %p), kind=kLoop, "
     "calls=%fused_computation.2", "all-gather-fusion.2", True),
    # a fusion that takes a permuted operand is compute, not a collective
    ("%fusion.3 = bf16[8]{0:T(512)} fusion(bf16[8]{0} "
     "%collective-permute-done.1), kind=kLoop, calls=%fused_computation.3",
     "fusion.3", False),
    ("%and_reduce_fusion = pred[]{:T(512)} fusion(s32[1]{0:T(128)} %bitcast),"
     " kind=kLoop, calls=%fused_computation.5", "and_reduce_fusion", False),
])
def test_op_name_and_collective_class(text, name, coll):
    assert tracing.op_info(text) == (name, coll)


def test_shortest_open_span_matches_a_scan():
    import random
    rng = random.Random(7)
    spans = []
    for k in range(60):
        s = rng.randrange(0, 1000)
        spans.append((f"s{k % 7}", s, s + rng.randrange(1, 200)))
    points = sorted(rng.uniform(-10, 1300) for _ in range(400))

    def scan(t):
        open_ = [sp for sp in spans if sp[1] <= t < sp[2]]
        return min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ \
            else None
    assert tracing.shortest_open(spans, points) == [scan(t) for t in points]


def test_one_read_feeds_both_reductions():
    """A trace read once gives what reading it from its path gives, the
    program's spans with it."""
    from bench import spans
    tr = tracing.read(TRACE)
    assert tracing.reduce(tr, ("tick",)) == tracing.reduce(TRACE, ("tick",))
    assert tracing.reduce(tr)["program_spans"] == spans.reduce(TRACE)
