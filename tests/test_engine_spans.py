"""The serving engine's spans on the profiler's clock (obs/trace.py): the
phases of each tick in order, admissions by request id with their queue
time, the prompt rows the scheduler fed, backend compiles, the off path
that computes no metadata, and the names of the backend's programs."""
import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import ServeConfig, get_smoke_config
from repro.models import build_model, split_tree
from repro.obs import trace
from repro.serve.engine import ServeEngine
from repro.serve.health import HealthConfig

TICK_PHASES = ["serve.plan", "serve.step.dispatch", "serve.sample.dispatch",
               "serve.device_wait", "serve.commit"]
# the guard waits for the step before it judges it, then samples
GUARDED_PHASES = ["serve.plan", "serve.step.dispatch", "serve.device_wait",
                  "serve.sample.dispatch", "serve.device_wait",
                  "serve.commit"]
PROMPTS = [np.arange(1, 20), np.array([7, 2]), np.arange(3, 14),
           np.array([11]), np.arange(5, 30), np.arange(2, 6)]
SCFG = ServeConfig(max_batch=3, max_seq_len=64, prefill_chunk=8,
                   temperature=0.0)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    return cfg, params


def drive(eng, recompile_at=None):
    """Serve PROMPTS to the end; returns the out tokens and, per tick, the
    rows the scheduler will feed from prompts (counted before the tick)."""
    reqs = [eng.sched.submit(p.astype(np.int32), 5) for p in PROMPTS]
    prompt_rows, tick = [], 0
    while eng.sched.busy:
        eng._admit()
        prompt_rows.append(sum(r is not None and left > 0 for r, left in
                               zip(eng.sched.slot_req,
                                   eng.sched.slot_prompt_left)))
        tick += 1
        if tick == recompile_at:
            jax.clear_caches()              # the next step compiles again
        eng.step()
    return [r.out_tokens for r in reqs], prompt_rows


def read_spans(directory):
    """(name, start_ns, end_ns, stats) of the serve.* host spans, in
    order of start."""
    path = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def children(spans, parent, names):
    return [s[0] for s in spans if s[0] in names
            and parent[1] <= s[1] and s[2] <= parent[2] and s is not parent]


@pytest.fixture(scope="module", params=["plain", "guarded"])
def traced(request, qwen, tmp_path_factory):
    cfg, params = qwen
    health = HealthConfig() if request.param == "guarded" else None
    eng = ServeEngine(cfg, SCFG, params, health=health)
    d = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    with jax.profiler.trace(d):
        out, prompt_rows = drive(eng, recompile_at=6)
    return request.param, out, prompt_rows, read_spans(d)


def test_every_tick_holds_its_phases_in_order(traced):
    kind, _, prompt_rows, spans = traced
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(ticks) == len(prompt_rows)
    want = GUARDED_PHASES if kind == "guarded" else TICK_PHASES
    for t in ticks:
        assert children(spans, t, set(TICK_PHASES)) == want
    assert [t[3]["tick"] for t in ticks] == list(range(1, len(ticks) + 1))


def test_prompt_rows_are_the_rows_plan_fed_from_prompts(traced):
    _, _, prompt_rows, spans = traced
    meta = [s[3] for s in spans if s[0] == "serve.tick"]
    assert [m["prompt_rows"] for m in meta] == prompt_rows
    assert sum(prompt_rows) > 0                 # some prompts streamed
    for m in meta:
        assert 0 <= m["prompt_rows"] <= m["active"] <= SCFG.max_batch
        assert m["sampling"] <= m["active"]


def test_admissions_carry_rid_and_queue_time(traced):
    _, _, _, spans = traced
    admits = [s for s in spans if s[0] == "serve.admit"]
    reqs = [s for s in spans if s[0] == "serve.admit_request"]
    assert sorted(s[3]["rid"] for s in reqs) == list(range(len(PROMPTS)))
    for r in reqs:
        m = r[3]
        assert m["queued_s"] >= 0
        assert 0 <= m["slot"] < SCFG.max_batch
        assert m["prefill_tokens"] == min(len(PROMPTS[m["rid"]]) - 1,
                                          SCFG.prefill_chunk)
        assert any(a[1] <= r[1] and r[2] <= a[2] for a in admits)
        want = ["serve.slot_reset"] + (["serve.prefill.dispatch"]
                                       if m["prefill_tokens"] else [])
        assert children(spans, r, {"serve.slot_reset",
                                   "serve.prefill.dispatch"}) == want
    # requests wait in the queue while the three slots are busy
    assert max(r[3]["queued_s"] for r in reqs) > 0


def test_compiles_name_the_tick_that_compiled(traced):
    _, _, _, spans = traced
    compiles = [s[3]["compiles"] for s in spans if s[0] == "serve.tick"]
    assert compiles[0] > 0                      # the first step compiles
    assert compiles[5] > 0                      # caches cleared before it
    assert compiles[1:5] == [0] * 4 and not any(compiles[6:])
    first = [s for s in spans if s[0] == "serve.admit_request"][0]
    assert first[3]["compiles"] > 0             # slot reset and prefill


def test_greedy_tokens_match_with_the_profiler_off(traced, qwen):
    kind, out, _, _ = traced
    cfg, params = qwen
    health = HealthConfig() if kind == "guarded" else None
    ref, _ = drive(ServeEngine(cfg, SCFG, params, health=health))
    assert out == ref


def test_no_metadata_without_a_profiler(qwen, monkeypatch, tmp_path):
    """Counting stubs for the annotation and the compile counter: with no
    profiler attached, spans are made but no metadata is computed."""
    counts = {"spans": 0, "meta": 0, "compiles": 0}

    class Counting(TraceAnnotation):
        def __init__(self, name, **kw):
            counts["spans"] += 1
            counts["meta"] += len(kw)
            super().__init__(name, **kw)

        def set_metadata(self, **kw):
            counts["meta"] += 1
            super().set_metadata(**kw)

    real = trace.compiles

    def counting_compiles():
        counts["compiles"] += 1
        return real()

    monkeypatch.setattr(trace, "TraceAnnotation", Counting)
    monkeypatch.setattr(trace, "compiles", counting_compiles)
    cfg, params = qwen
    drive(ServeEngine(cfg, SCFG, params))
    assert counts["spans"] > 0
    assert counts["meta"] == 0 and counts["compiles"] == 0
    with jax.profiler.trace(str(tmp_path)):     # the stubs do count
        drive(ServeEngine(cfg, SCFG, params))
    assert counts["meta"] > 0 and counts["compiles"] > 0


def test_instant_is_a_zero_work_span_with_metadata(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        trace.instant("serve.rollback", tick=3, detail="probe")
    [(name, start, end, meta)] = read_spans(str(tmp_path))
    assert name == "serve.rollback"
    assert meta == {"tick": 3, "detail": "probe"}


@pytest.mark.parametrize("program", ["decode_step", "prefill_into_cache",
                                     "zero_row"])
def test_backend_programs_have_stable_names(qwen, program):
    """The device trace finds the backend's programs by these names."""
    cfg, params = qwen
    eng = ServeEngine(cfg, SCFG, params)
    b = eng.backend
    args = {
        "decode_step": (b._step, (b.params, b.cache,
                                  jnp.zeros((SCFG.max_batch, 1), jnp.int32),
                                  jnp.ones(SCFG.max_batch, bool))),
        "prefill_into_cache": (b._prefill, (b.params, b.cache,
                                            jnp.zeros(8, jnp.int32),
                                            jnp.int32(0), jnp.int32(3))),
        "zero_row": (b._zero, (b.cache, 0)),
    }
    fn, a = args[program]
    text = fn.lower(*a).as_text()
    assert text.splitlines()[0].startswith(f"module @jit_{program} ")
