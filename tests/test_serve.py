"""Serving engine tests: continuous batching equals sequential decode,
request lifecycle (EOS / failure / eviction), sampler edge cases, and the
health monitor's single-device behaviors (non-finite eviction with exact
rollback, ladder exhaustion)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ServeConfig, get_smoke_config
from repro.models import build_model, split_tree
from repro.serve.engine import ServeEngine, TicksExhaustedError
from repro.serve.health import FatalFaultError, HealthConfig
from repro.serve.sample import sample
from repro.serve.scheduler import Scheduler


@pytest.fixture(scope="module")
def qwen():
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    return cfg, model, params


def sequential_greedy(model, params, prompt, n_new, max_seq=64):
    cache = model.init_cache(1, max_seq)
    step = jax.jit(model.decode_step)
    logits = None
    for t in prompt:
        logits, cache = step(params, cache, jnp.asarray([[t]], jnp.int32))
    out = []
    for _ in range(n_new):
        nxt = int(jnp.argmax(logits, -1)[0])
        out.append(nxt)
        logits, cache = step(params, cache, jnp.asarray([[nxt]], jnp.int32))
    return out


def test_engine_matches_sequential(qwen):
    cfg, model, params = qwen
    eng = ServeEngine(cfg, ServeConfig(max_batch=4, max_seq_len=64), params)
    prompts = [np.array([5, 9, 13]), np.array([7, 2]),
               np.array([1, 2, 3, 4, 5]), np.array([11]), np.array([3, 3])]
    for p in prompts:
        eng.submit(p, max_new_tokens=4)
    reqs = list(eng.pending)
    ticks = eng.run()
    assert ticks < 40
    for p, req in zip(prompts, reqs):
        assert req.done
        assert req.out_tokens == sequential_greedy(model, params, list(p), 4)


def test_engine_more_requests_than_slots(qwen):
    cfg, model, params = qwen
    eng = ServeEngine(cfg, ServeConfig(max_batch=2, max_seq_len=64), params)
    for i in range(5):
        eng.submit(np.array([i + 1, i + 2]), max_new_tokens=3)
    reqs = list(eng.pending)
    eng.run()
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == 3 for r in reqs)


def test_sampler_greedy_and_topk():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [3.0, 0.0, -1.0]])
    toks = sample(logits, jax.random.PRNGKey(0), temperature=0.0)
    assert toks.tolist() == [1, 0]
    toks = sample(logits, jax.random.PRNGKey(0), temperature=1.0, top_k=1)
    assert toks.tolist() == [1, 0]  # top-1 == greedy regardless of temp


# ---------------------------------------------------------------------------
# Churn: staggered submits, slot reuse, budgets
# ---------------------------------------------------------------------------


def test_staggered_mid_run_submits(qwen):
    """Requests submitted while the engine is mid-run decode exactly like
    requests submitted up front (continuous batching admits into whatever
    slot frees up; the active mask keeps other rows' caches frozen)."""
    cfg, model, params = qwen
    eng = ServeEngine(cfg, ServeConfig(max_batch=2, max_seq_len=64), params)
    first = [np.array([5, 9, 13]), np.array([7, 2])]
    for p in first:
        eng.submit(p, max_new_tokens=5)
    reqs = list(eng.pending)
    # run a few ticks, then drip new requests in while slots are busy
    for _ in range(3):
        eng._admit()
        eng.step()
    late = [np.array([1, 2, 3, 4]), np.array([11]), np.array([3, 3])]
    for i, p in enumerate(late):
        eng.submit(p, max_new_tokens=4)
        eng._admit()
        eng.step()
    reqs += list(eng.pending) + [r for s in eng.sched.slot_req
                                 if s is not None and s not in reqs]
    eng.run()
    prompts = first + late
    budgets = [5, 5, 4, 4, 4]
    by_rid = sorted({id(r): r for r in reqs}.values(), key=lambda r: r.rid)
    assert len(by_rid) == 5 and all(r.done for r in by_rid)
    for p, n, req in zip(prompts, budgets, by_rid):
        assert req.out_tokens == sequential_greedy(model, params, list(p), n)


def test_slot_reuse_is_bit_identical_to_fresh(qwen):
    """A freed slot's cache must be zeroed so its next occupant decodes
    bit-identically to a fresh engine (no KV bleed-through)."""
    cfg, model, params = qwen
    scfg = ServeConfig(max_batch=1, max_seq_len=64)
    eng = ServeEngine(cfg, scfg, params)
    eng.submit(np.array([9, 8, 7, 6]), max_new_tokens=6)   # dirties slot 0
    eng.submit(np.array([4, 2]), max_new_tokens=4)         # reuses slot 0
    reqs = list(eng.pending)
    eng.run()

    fresh = ServeEngine(cfg, scfg, params)
    fresh.submit(np.array([4, 2]), max_new_tokens=4)
    ref = fresh.pending[0]
    fresh.run()
    assert reqs[1].out_tokens == ref.out_tokens

    # and the zeroing itself is bitwise: with max_batch=1 every request
    # used slot 0, so freeing it must restore the exact fresh cache
    eng.backend.free_slot(0)
    a = jax.tree_util.tree_leaves(eng.backend.cache)
    b = jax.tree_util.tree_leaves(fresh.backend._init_cache())
    assert len(a) == len(b)
    for la, lb in zip(a, b):
        assert la.shape == lb.shape
        assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_empty_prompt_seeds_bos(qwen):
    """An empty prompt used to crash step() (IndexError on out_tokens[-1]);
    it must now be seeded with the BOS token and decode like prompt=[bos]."""
    cfg, model, params = qwen
    eng = ServeEngine(cfg, ServeConfig(max_batch=2, max_seq_len=64,
                                       bos_token=3), params)
    eng.submit(np.array([], np.int32), max_new_tokens=4)
    req = eng.pending[0]
    eng.run()
    assert req.done
    assert req.out_tokens == sequential_greedy(model, params, [3], 4)


def test_sequence_budget_truncates_and_rejects(qwen):
    cfg, model, params = qwen
    eng = ServeEngine(cfg, ServeConfig(max_batch=2, max_seq_len=16), params)
    # prompt 10 + max_new 20 > 16: truncated to 6 new tokens
    eng.submit(np.arange(1, 11, dtype=np.int32), max_new_tokens=20)
    req = eng.pending[0]
    assert req.truncated and req.max_new_tokens == 6
    eng.run()
    assert req.done and len(req.out_tokens) == 6
    # a prompt that fills the whole budget leaves no room to generate
    with pytest.raises(ValueError):
        eng.submit(np.arange(16, dtype=np.int32), max_new_tokens=1)
    with pytest.raises(ValueError):
        eng.submit(np.arange(99, dtype=np.int32), max_new_tokens=1)


# ---------------------------------------------------------------------------
# Request lifecycle: max_ticks failure, EOS, prefill accounting errors
# ---------------------------------------------------------------------------


def test_run_exhausting_max_ticks_fails_leftovers(qwen):
    """A stuck run must not silently drop in-flight work: every leftover
    request (running *and* still pending) is terminally failed and
    TicksExhaustedError carries them."""
    cfg, model, params = qwen
    eng = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq_len=64), params)
    eng.submit(np.array([5, 9, 13]), max_new_tokens=5)   # needs ~8 ticks
    eng.submit(np.array([7, 2]), max_new_tokens=3)       # never admitted
    reqs = list(eng.pending)
    with pytest.raises(TicksExhaustedError) as exc:
        eng.run(max_ticks=2)
    assert sorted(r.rid for r in exc.value.failed) == [r.rid for r in reqs]
    for r in reqs:
        assert r.status == "failed" and not r.done
        assert "max_ticks=2" in r.finish_reason
    assert not eng.sched.busy                            # nothing lingers


def test_eos_token_retires_slot(qwen):
    """With ServeConfig.eos_token set, a slot retires the tick it samples
    that token (finish_reason 'eos'), keeping the EOS in its output."""
    cfg, model, params = qwen
    prompt = np.array([5, 9, 13])

    ref_eng = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq_len=64),
                          params)
    ref_eng.submit(prompt, max_new_tokens=6)
    ref = ref_eng.pending[0]
    ref_eng.run()
    assert ref.finish_reason == "length"
    eos = ref.out_tokens[2]                 # a token the model will emit
    cut = ref.out_tokens.index(eos)         # first time it appears

    eng = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq_len=64,
                                       eos_token=eos), params)
    eng.submit(prompt, max_new_tokens=6)
    req = eng.pending[0]
    eng.run()
    assert req.done and req.status == "done"
    assert req.finish_reason == "eos"
    assert req.out_tokens == ref.out_tokens[:cut + 1]


def test_note_prefilled_rejects_bad_accounting():
    sched = Scheduler(max_batch=2, max_seq_len=32)
    sched.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    sched.admit()
    with pytest.raises(ValueError, match="empty slot"):
        sched.note_prefilled(1, 2)
    with pytest.raises(ValueError, match="positive token count"):
        sched.note_prefilled(0, 0)
    with pytest.raises(ValueError, match="whole remaining prompt"):
        sched.note_prefilled(0, 5)          # must leave >= 1 to stream
    sched.note_prefilled(0, 4)              # legal: one token left
    assert sched.slot_prompt_left[0] == 1


def test_scheduler_evict_and_snapshot_roundtrip():
    sched = Scheduler(max_batch=2, max_seq_len=32)
    a = sched.submit(np.array([1, 2], np.int32), max_new_tokens=3)
    b = sched.submit(np.array([3], np.int32), max_new_tokens=3)
    sched.admit()
    snap = sched.snapshot()
    sched.plan()                            # mutates prompt_left
    evicted = sched.evict(0, reason="poisoned")
    assert evicted is a and a.status == "error" and not a.done
    assert a.finish_reason == "poisoned"
    with pytest.raises(ValueError, match="empty slot"):
        sched.evict(0)
    sched.restore(snap)                     # rollback resurrects the tick
    assert sched.slot_req[0] is a
    assert sched.slot_prompt_left[0] == 2 and sched.slot_prompt_left[1] == 1
    assert b.status == "running"


# ---------------------------------------------------------------------------
# Sampler edge cases (the contract in serve/sample.py's docstring)
# ---------------------------------------------------------------------------


def test_sampler_nan_logits_defined_behavior():
    logits = jnp.asarray([[1.0, jnp.nan, 3.0, 2.0],
                          [jnp.nan, jnp.nan, jnp.nan, jnp.nan]])
    toks = sample(logits, jax.random.PRNGKey(0), temperature=0.0)
    assert toks.tolist() == [2, 0]          # best finite; all-NaN -> 0
    toks = sample(logits, jax.random.PRNGKey(1), temperature=1.0)
    assert int(toks[1]) == 0                # stochastic path too
    assert int(toks[0]) != 1                # NaN index never sampled


def test_sampler_topk_geq_vocab_is_noop():
    logits = jnp.asarray([[0.5, -1.0, 2.0]])
    for k in (3, 7):
        a = sample(logits, jax.random.PRNGKey(2), temperature=1.0, top_k=k)
        b = sample(logits, jax.random.PRNGKey(2), temperature=1.0, top_k=0)
        assert a.tolist() == b.tolist()


def test_sampler_topk_ties_at_cutoff_stay_sampleable():
    logits = jnp.asarray([[0.0, 5.0, 5.0, 1.0]])
    seen = {int(sample(logits, jax.random.PRNGKey(s), temperature=1.0,
                       top_k=1)[0]) for s in range(40)}
    assert seen == {1, 2}                   # both tied maxima, nothing else


# ---------------------------------------------------------------------------
# Health monitor on a single device (ring cases: tests/multidev)
# ---------------------------------------------------------------------------


def test_monitor_evicts_nonfinite_rows_with_exact_rollback(qwen):
    """A NaN logit row indicts only that request: it is evicted (status
    'error', committed tokens kept), the step's cache writes are rolled
    back, and the surviving request's tokens are bitwise those of an
    undisturbed run."""
    cfg, model, params = qwen
    scfg = ServeConfig(max_batch=2, max_seq_len=64)
    eng = ServeEngine(cfg, scfg, params, health=HealthConfig())
    eng.submit(np.array([5, 9, 13]), max_new_tokens=4)
    eng.submit(np.array([7, 2]), max_new_tokens=4)
    victim, survivor = list(eng.pending)

    for _ in range(3):                      # victim has committed a token
        eng._admit()
        eng.step()
    assert len(victim.out_tokens) == 1

    orig = eng.backend.step
    fired = []

    def poisoned(tokens, active):
        logits = orig(tokens, active)
        if not fired:
            fired.append(True)
            logits = logits.at[0, :].set(jnp.nan)
        return logits

    eng.backend.step = poisoned
    eng.run()
    assert victim.status == "error" and not victim.done
    assert victim.finish_reason == "non-finite logits"
    assert len(victim.out_tokens) == 1      # keeps what was committed
    assert [e.kind for e in eng.monitor.events] == ["nonfinite"]
    assert survivor.done
    assert survivor.out_tokens == sequential_greedy(model, params, [7, 2], 4)


def test_monitor_ladder_exhaustion_is_fatal(qwen):
    """A dense backend is the last ladder rung: a persistent 'link' fault
    there cannot be degraded away and must fail all requests loudly."""
    cfg, model, params = qwen
    eng = ServeEngine(cfg, ServeConfig(max_batch=1, max_seq_len=64), params,
                      health=HealthConfig(max_retries=2))
    eng.backend.link_health = lambda: {"tag_errors": 1}
    eng.submit(np.array([5, 9]), max_new_tokens=3)
    req = eng.pending[0]
    with pytest.raises(FatalFaultError) as exc:
        eng.run()
    assert req.status == "failed" and not req.done
    assert exc.value.failed == [req]
    assert not eng.sched.busy


def test_dense_block_prefill_matches_streaming(qwen):
    """prefill_chunk > 0 block-prefills each prompt's head through one
    full-sequence forward; greedy outputs must match chunk-less streaming
    and the tick count must drop."""
    cfg, model, params = qwen
    prompts = [np.array([5, 9, 13, 2, 8, 1, 7]), np.array([7, 2]),
               np.array([1, 2, 3, 4, 5, 6, 7, 8, 9]), np.array([11])]

    def run(scfg):
        eng = ServeEngine(cfg, scfg, params)
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        reqs = list(eng.pending)
        ticks = eng.run()
        return [r.out_tokens for r in reqs], ticks

    ref, t_stream = run(ServeConfig(max_batch=4, max_seq_len=64))
    out, t_block = run(ServeConfig(max_batch=4, max_seq_len=64,
                                   prefill_chunk=8))
    assert out == ref
    assert t_block < t_stream


@pytest.mark.parametrize("prefill_chunk", [0, 8])
def test_backend_donates_cache_and_snapshot_survives(qwen, prefill_chunk):
    """The step (and block prefill) update the cache in place: the array a
    caller kept of the old cache is deleted, while ``snapshot_cache`` gives
    a copy that outlives the step and ``adopt_cache`` takes it back."""
    cfg, model, params = qwen
    scfg = ServeConfig(max_batch=2, max_seq_len=32, temperature=0.0,
                       prefill_chunk=prefill_chunk)
    eng = ServeEngine(cfg, scfg, params)
    eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
    eng._admit()
    snap = eng.backend.snapshot_cache()
    old = eng.backend.cache
    tokens, active, _ = eng.sched.plan()
    eng.backend.step(tokens, active)
    assert all(l.is_deleted() for l in jax.tree_util.tree_leaves(old))
    assert not any(l.is_deleted() for l in jax.tree_util.tree_leaves(snap))
    after = jax.tree_util.tree_map(np.asarray, eng.backend.cache)
    eng.backend.adopt_cache(snap)
    eng.backend.step(tokens, active)            # replay the same tick
    for a, b in zip(jax.tree_util.tree_leaves(after),
                    jax.tree_util.tree_leaves(eng.backend.cache)):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert not any(l.is_deleted() for l in jax.tree_util.tree_leaves(snap))
