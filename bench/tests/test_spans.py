"""The reduction of the program's own spans (``bench/spans.py``) and the
per-layer numbers read from them, on hand-made spans and on a window
recorded on one TPU v5 lite: qwen3-0.6b (32 slots x 2048), two requests
decoding, then one admitted with a prompt of 296 tokens (256 block
prefilled, 40 streamed) and three ticks, inside ``bench_window``."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import spans  # noqa: E402

TRACE = str(Path(__file__).parent / "data" / "spans.xplane.pb.gz")


def test_innermost_open_span():
    sp = [(0, 10, "serve.tick"), (1, 3, "serve.plan"),
          (4, 9, "serve.device_wait"), (12, 14, "serve.admit")]
    pts = [0.5, 2, 3.5, 5, 9.5, 11, 13, 15]
    assert spans.innermost(sp, pts) == [
        "serve.tick", "serve.plan", "serve.tick", "serve.device_wait",
        "serve.tick", None, "serve.admit", None]


def synthetic():
    tick = {"active": 4, "sampling": 3, "prompt_rows": 1, "compiles": 0}
    return {"chips": 1, "window_s": 1.0, "spans": [
        ["serve.admit", 0.00, 0.02, {}],
        ["serve.admit_request", 0.00, 0.02,
         {"rid": 7, "queued_s": 0.5, "compiles": 2}],
        ["serve.tick", 0.02, 0.12, dict(tick, tick=1)],
        ["serve.device_wait", 0.05, 0.11, {}],
        ["serve.tick", 0.12, 0.20, dict(tick, tick=2, prompt_rows=3)],
        ["serve.device_wait", 0.14, 0.19, {}],
    ], "idle": {"serve.tick": 0.004, "serve.device_wait": 0.002,
                "serve.admit": 0.010, "none": 0.5}}


def test_numbers_from_hand_made_spans():
    red = synthetic()
    # (0.10 - 0.06 + 0.08 - 0.05) / 2
    assert spans.tick_host_ms(red) == pytest.approx(35.0)
    assert spans.tick_idle_ms(red) == pytest.approx(3.0)
    assert spans.admit_wait_p95_s(red) == pytest.approx(0.5)
    assert spans.decode_prompt_row_share(red) == pytest.approx(50.0)
    assert spans.window_compiles(red) == 2


@pytest.mark.parametrize("red", [None, {"spans": [], "idle": {}}])
def test_nothing_to_read_gives_none(red):
    """A program without the spans (or no trace) reports no number."""
    assert all(f(red) is None for f in spans.METRICS.values())


@pytest.fixture(scope="module")
def recorded():
    return spans.reduce(TRACE)


def test_recorded_window_spans(recorded):
    names = [s[0] for s in recorded["spans"]]
    assert recorded["chips"] == 1
    assert recorded["window_s"] == pytest.approx(0.546393, rel=1e-5)
    assert names[:4] == ["serve.admit", "serve.admit_request",
                         "serve.slot_reset", "serve.prefill.dispatch"]
    assert names.count("serve.tick") == 3 and len(names) == 24
    req = recorded["spans"][1][3]
    assert (req["rid"], req["slot"], req["prefill_tokens"]) == (3, 2, 256)
    ticks = [s[3] for s in recorded["spans"] if s[0] == "serve.tick"]
    assert [t["tick"] for t in ticks] == [5, 6, 7]
    assert all((t["active"], t["sampling"], t["prompt_rows"]) == (3, 2, 1)
               for t in ticks)


def test_recorded_window_idle_by_span(recorded):
    """Idle read by hand: 9.243 ms in the window, of which the gaps after
    each tick's sampler programs (7.940 ms) fall in ``serve.device_wait``
    and the wait for the block prefill to start (1.303 ms) in
    ``serve.prefill.dispatch``."""
    idle = recorded["idle"]
    assert sum(idle.values()) == pytest.approx(9.2428e-3, rel=1e-4)
    assert idle["serve.device_wait"] == pytest.approx(7.9400e-3, rel=1e-4)
    assert idle["serve.prefill.dispatch"] == pytest.approx(1.3028e-3,
                                                           rel=1e-4)


def test_recorded_window_numbers(recorded):
    # ticks of 227.476, 158.120 and 157.926 ms, of which the host waited
    # 224.004, 154.708 and 154.523 ms in serve.device_wait
    assert spans.tick_host_ms(recorded) == pytest.approx(3.429, rel=1e-3)
    # idle inside the three ticks, 2.602 + 2.692 + 2.633 ms by hand; a gap
    # across a tick's edge is put down whole to the span open at its middle
    assert spans.tick_idle_ms(recorded) == pytest.approx(2.642, rel=5e-3)
    assert spans.admit_wait_p95_s(recorded) == pytest.approx(4.4211e-5,
                                                             rel=1e-4)
    assert spans.decode_prompt_row_share(recorded) == pytest.approx(100 / 3)
    assert spans.window_compiles(recorded) == 0


@pytest.mark.parametrize("name", sorted(spans.METRICS))
def test_readers_take_the_spans_from_the_trace_reduction(name, recorded):
    """Each reader ``metrics/<name>.py`` reads, from ``tracing.reduce``'s
    ``program_spans``, the number ``bench/spans.py`` gives."""
    from bench import tracing
    from bench.layer import Context, read
    red = tracing.reduce(TRACE)
    assert red["program_spans"] == recorded
    ctx = Context({}, {}, "TPU v5 lite", 1, red)
    assert read(name, ctx) == spans.METRICS[name](recorded)
    assert read(name, Context({}, {}, "TPU v5 lite", 1, None)) is None
