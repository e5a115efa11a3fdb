#!/usr/bin/env python3
"""Smoke run of the system on a TPU, in one process.

Drives the main paths through the entry points a user calls, at
qwen3-0.6b's published widths (bf16, random weights from ``--seed``), and
checks what comes out. It is a smoke run, not a benchmark: the times it
prints describe this one run and are no measurement of speed.

  python chip_smoke.py              one chip: serve, train, kernels
  python chip_smoke.py --chips 4    a 2x2 host: ring serving only

Default phases (one chip):

* serve   — ``ServeEngine`` + ``DecodeBackend`` at the default
            ``ServeConfig`` (32 slots x 2048 positions) with 256-token block
            prefill: 8 seeded requests of 16-600 prompt tokens, 32 new
            tokens each. Every tick's logits are finite, every token is in
            the vocabulary, and one request's last decode logits agree with
            a full-sequence forward over the same tokens.
* train   — 3 steps of ``repro.launch.train.main`` at batch 4 x 256; the
            checkpoint goes to a temporary directory that is removed.
* kernels — ``tile_matmul`` and ``flash_hop`` at qwen3-0.6b widths, and
            the SSD, conv2d and FFT kernels, against jnp references, with
            ``tpu_custom_call`` in each compiled text.

``--chips 4`` runs ``RingShardedBackend(mode="qlr")`` on a 1x4 mesh, with
jnp hops and with kernel hops, each in lockstep with ``DecodeBackend`` on
one device over the same 8 requests: the greedy tokens agree except at
certified near-ties (the rule of tests/multidev/check_ring_decode.py).

The script refuses to run where JAX finds no TPU, and exits non-zero,
printing no result, when any phase fails. Its last line of standard output
is one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-0.6b"
N_REQUESTS = 8
MAX_NEW = 32
PROMPT_LENS = (16, 600)
# Agreement limits in bf16, as a share of the reference's largest |logit|
# (or |output|): decode vs full-sequence forward, and each kernel vs jnp.
LOGIT_TOL = 0.05
KERNEL_TOL = 2e-2
# Ring vs dense greedy tokens may differ only where the dense top-2 logit
# gap is below this (sharded reductions reorder bf16 sums): a certified tie.
TIE_GAP = 0.1


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"ok: {what}")


# ---------------------------------------------------------------- helpers
def init_params(cfg, seed: int):
    import jax
    from repro.models import build_model, split_tree
    model = build_model(cfg)
    return jax.jit(lambda k: split_tree(model.init(k))[0])(
        jax.random.PRNGKey(seed))


def make_prompts(vocab: int, seed: int) -> list:
    """N_REQUESTS prompts whose lengths span PROMPT_LENS (both ends
    included), drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LENS
    lens = [lo, hi] + list(rng.integers(lo, hi + 1, size=N_REQUESTS - 2))
    return [rng.integers(0, vocab, size=int(n)).astype(np.int32)
            for n in lens]


def peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use'] / 2 ** 30:.3f} GiB"


def _watched_backend(cfg, scfg, params):
    """A ``DecodeBackend`` that checks each tick's logits for finiteness
    (on device, one flag per tick) and keeps the latest logits row of a
    watched request."""
    import jax.numpy as jnp
    from repro.serve.sharded_cache import DecodeBackend

    class Watched(DecodeBackend):
        sched = None
        watch = None
        watched_row = None

        def __init__(self, *a):
            super().__init__(*a)
            self.finite = []

        def step(self, tokens, active):
            logits = super().step(tokens, active)
            self.finite.append(jnp.all(jnp.isfinite(logits)))
            for slot, req in enumerate(self.sched.slot_req):
                if req is not None and req is self.watch:
                    self.watched_row = logits[slot]
            return logits

    return Watched(cfg, scfg, params)


# ---------------------------------------------------------------- phases
def phase_serve(cfg, scfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    from repro.serve.engine import ServeEngine

    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    backend = _watched_backend(cfg, scfg, params)
    engine = ServeEngine(cfg, scfg, params, backend=backend)
    backend.sched = engine.sched
    jax.block_until_ready(engine.cache)
    log(f"serve set-up (weights + cache) {time.perf_counter() - t0:.3f} s")

    # warm-up: one short request touches every program the run uses (slot
    # reset, block prefill, decode step); its wall time is mostly compiling
    t0 = time.perf_counter()
    engine.submit(make_prompts(cfg.vocab_size, seed + 1)[0][:20],
                  max_new_tokens=2)
    engine.run()
    log(f"serve warm-up (compile) {time.perf_counter() - t0:.3f} s")

    prompts = make_prompts(cfg.vocab_size, seed)
    for p in prompts:
        engine.submit(p, max_new_tokens=MAX_NEW)
    reqs = list(engine.pending)
    backend.watch = max(reqs, key=lambda r: len(r.prompt))
    backend.finite = []
    t0 = time.perf_counter()
    ticks = engine.run()
    wall = time.perf_counter() - t0
    n_tok = sum(len(r.out_tokens) for r in reqs)
    log(f"smoke run, not a benchmark: served {len(reqs)} requests "
        f"(prompts {min(len(p) for p in prompts)}-"
        f"{max(len(p) for p in prompts)} tokens), {n_tok} tokens in "
        f"{ticks} ticks, wall {wall:.3f} s")
    log(f"peak device memory after serving: {peak_gib(jax.devices()[0])}")

    check(all(r.status == "done" and len(r.out_tokens) == MAX_NEW
              for r in reqs), f"all {len(reqs)} requests done with "
          f"{MAX_NEW} tokens each")
    check(bool(jnp.all(jnp.stack(backend.finite))),
          f"logits finite at all {len(backend.finite)} ticks")
    toks = np.concatenate([np.asarray(r.out_tokens) for r in reqs])
    check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
          f"every token in [0, {cfg.vocab_size})")

    # the watched request's last decode logits vs one forward over the
    # tokens the decode path consumed (prompt + all but the last sample)
    req = backend.watch
    seq = np.concatenate([req.prompt, np.asarray(req.out_tokens[:-1],
                                                 np.int32)])
    model = build_model(cfg)
    ref = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(seq)[None]})
    ref = np.asarray(ref[0], np.float32)
    got = np.asarray(backend.watched_row, np.float32)
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    log(f"decode vs full forward over {len(seq)} tokens: max |diff| "
        f"{err:.6g}, max |logit| {scale:.6g}, argmax {int(got.argmax())} vs "
        f"{int(ref.argmax())}")
    check(err <= LOGIT_TOL * scale,
          f"decode logits within {LOGIT_TOL} x max|logit| of the forward")


def phase_train(arch: str, seed: int, extra: tuple = ()) -> None:
    from repro.launch import train

    tmp = tempfile.mkdtemp(prefix="chip_smoke_train_")
    try:
        log_path = os.path.join(tmp, "metrics.jsonl")
        t0 = time.perf_counter()
        train.main(["--arch", arch, "--steps", "3", "--batch", "4",
                    "--seq", "256", "--ckpt-dir", os.path.join(tmp, "ckpt"),
                    "--log", log_path, "--train-set", f"seed={seed}",
                    *extra])
        log(f"train: 3 steps (first includes compile) + checkpoint "
            f"{time.perf_counter() - t0:.3f} s")
        with open(log_path) as f:
            recs = [json.loads(line) for line in f]
    finally:
        shutil.rmtree(tmp)
    losses = [r["loss"] for r in recs]
    log(f"train losses by logged step: "
        f"{[(r['step'], r['loss']) for r in recs]}")
    check(bool(recs) and all(np.isfinite(losses)), "train loss finite")


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def _kernel_in(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def phase_kernels(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_hop
    from repro.kernels.systolic_matmul.ops import tile_matmul

    hi = jax.lax.Precision.HIGHEST
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    d, f = cfg.d_model, cfg.d_ff
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    bf = jnp.bfloat16

    # tile_matmul: FFN up-projection, and the down-projection folding into
    # a carried fp32 accumulator
    x = jax.random.normal(ks[0], (2048, d), jnp.float32).astype(bf)
    w = (jax.random.normal(ks[1], (d, f), jnp.float32) / d ** 0.5).astype(bf)
    w2 = (jax.random.normal(ks[2], (f, d), jnp.float32) / f ** 0.5).astype(bf)
    acc = jax.random.normal(ks[3], (2048, d), jnp.float32)
    check(_kernel_in(tile_matmul, x, w),
          "tile_matmul compiled text holds tpu_custom_call")
    up = jax.jit(tile_matmul)(x, w)
    err = _rel_err(up, jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                               precision=hi))
    log(f"tile_matmul [2048,{d}]@[{d},{f}] bf16: rel err {err:.3g}")
    check(err <= KERNEL_TOL, f"tile_matmul within {KERNEL_TOL} of jnp")
    check(_kernel_in(tile_matmul, up, w2, acc),
          "tile_matmul (carried acc) compiled text holds tpu_custom_call")
    down = jax.jit(tile_matmul)(up, w2, acc)
    err = _rel_err(down, acc + jnp.dot(up.astype(jnp.float32),
                                       w2.astype(jnp.float32), precision=hi))
    log(f"tile_matmul acc [2048,{f}]@[{f},{d}] + fp32 acc: rel err {err:.3g}")
    check(err <= KERNEL_TOL, f"tile_matmul (carried acc) within "
          f"{KERNEL_TOL} of jnp")

    def zero_state(b, sq):
        return (jnp.full((b, h, sq), -1e30, jnp.float32),
                jnp.zeros((b, h, sq), jnp.float32),
                jnp.zeros((b, h, sq, hd), jnp.float32))

    def attend(q, k, v, valid):
        """Plain masked softmax attention, fp32. valid: [B, Sq, T]."""
        k = jnp.repeat(k.astype(jnp.float32), h // kvh, axis=2)
        v = jnp.repeat(v.astype(jnp.float32), h // kvh, axis=2)
        s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32), k,
                       precision=hi) / hd ** 0.5
        s = jnp.where(valid[:, None], s, -jnp.inf)
        return jnp.einsum("bhqt,bthd->bqhd", jax.nn.softmax(s, axis=-1), v,
                          precision=hi)

    def normalize(state):
        m, l, a = state
        return (a / l[..., None]).transpose(0, 2, 1, 3)

    # flash_hop, decode shape: 32 rows, one query each, over a 512-slot
    # block with per-row fill levels (the ring decode hop)
    b, t = 32, 512
    q = jax.random.normal(ks[4], (b, 1, h, hd), jnp.float32)
    k = jax.random.normal(ks[5], (b, t, kvh, hd), jnp.float32).astype(bf)
    v = jax.random.normal(ks[6], (b, t, kvh, hd), jnp.float32).astype(bf)
    pos = jax.random.randint(ks[7], (b,), 0, t)

    def dec(q, k, v, pos):
        return flash_hop(q, k, v, zero_state(b, 1), k_len=pos + 1,
                         causal=False)
    check(_kernel_in(dec, q, k, v, pos),
          "flash_hop (decode) compiled text holds tpu_custom_call")
    out = normalize(jax.jit(dec)(q, k, v, pos))
    ref = attend(q, k, v, (jnp.arange(t)[None, :] <= pos[:, None])[:, None])
    err = _rel_err(out, ref)
    log(f"flash_hop decode q[{b},1,{h},{hd}] kv[{b},{t},{kvh},{hd}]: "
        f"rel err {err:.3g}")
    check(err <= KERNEL_TOL, f"flash_hop (decode) within {KERNEL_TOL} of jnp")

    # flash_hop, prefill shape: a 256-query shard folding two 256-key
    # blocks (one hop each) under a causal mask, state carried between
    sq = 256
    qp = jax.random.normal(ks[0], (1, sq, h, hd), jnp.float32).astype(bf)
    kp = jax.random.normal(ks[1], (1, 2 * sq, kvh, hd), jnp.float32).astype(bf)
    vp = jax.random.normal(ks[2], (1, 2 * sq, kvh, hd), jnp.float32).astype(bf)

    def two_hops(q, k, v):
        st = zero_state(1, sq)
        for i in range(2):
            st = flash_hop(q, k[:, i * sq:(i + 1) * sq],
                           v[:, i * sq:(i + 1) * sq], st, q_offset=sq,
                           k_offset=i * sq, causal=True)
        return st
    check(_kernel_in(two_hops, qp, kp, vp),
          "flash_hop (prefill) compiled text holds tpu_custom_call")
    out = normalize(jax.jit(two_hops)(qp, kp, vp))
    causal = (jnp.arange(2 * sq)[None, :] <= sq + jnp.arange(sq)[:, None])
    ref = attend(qp, kp, vp, causal[None])
    err = _rel_err(out, ref)
    log(f"flash_hop prefill q[1,{sq},{h},{hd}] 2 hops x {sq} keys: "
        f"rel err {err:.3g}")
    check(err <= KERNEL_TOL, f"flash_hop (prefill) within {KERNEL_TOL} "
          f"of jnp")

    # the SSD, conv2d and FFT kernels through their ops wrappers, against
    # the references their tests use (fp32, full-precision matmuls)
    from repro.kernels.conv2d.ops import conv2d
    from repro.kernels.conv2d.ref import conv2d_ref
    from repro.kernels.fft.ops import fft256
    from repro.kernels.fft.ref import fft_ref
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_sequential_ref

    s_len, nh, p, n = 512, 8, 64, 128          # mamba2 head/state widths
    xs = jax.random.normal(ks[0], (1, s_len, nh, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s_len, nh)))
    a = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    bs = jax.random.normal(ks[3], (1, s_len, 1, n), jnp.float32) * 0.3
    cs = jax.random.normal(ks[4], (1, s_len, 1, n), jnp.float32) * 0.3
    dd = jnp.ones((nh,), jnp.float32)
    img = jax.random.normal(ks[5], (512, 512), jnp.float32)
    k33 = jax.random.normal(ks[6], (3, 3), jnp.float32)
    sig = (jax.random.normal(ks[7], (256, 256))
           + 1j * jax.random.normal(ks[0], (256, 256))).astype(jnp.complex64)
    cases = [
        ("ssd", lambda *xs_: ssd(*xs_, chunk=256), ssd_sequential_ref,
         (xs, dt, a, bs, cs, dd)),
        ("conv2d", conv2d, conv2d_ref, (img, k33)),
        ("fft256", fft256, fft_ref, (sig,)),
    ]
    for name, fn, ref_fn, args in cases:
        check(_kernel_in(fn, *args),
              f"{name} compiled text holds tpu_custom_call")
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(*args)
        got = jax.jit(fn)(*args)
        if jnp.iscomplexobj(ref):
            got = jnp.stack([got.real, got.imag])
            ref = jnp.stack([ref.real, ref.imag])
        err = _rel_err(got, ref)
        log(f"{name}: rel err {err:.3g}")
        check(err <= KERNEL_TOL, f"{name} within {KERNEL_TOL} of jnp")


def _lockstep(dense, ring, prompts) -> tuple[int, int, float]:
    """Drive both engines through one schedule, committing the dense greedy
    token to both; returns (certified ties, mismatches, max |logit diff|
    over sampled rows)."""
    for p in prompts:
        dense.submit(p, max_new_tokens=MAX_NEW)
        ring.submit(p, max_new_tokens=MAX_NEW)
    ties = bad = 0
    max_diff = 0.0
    while dense.sched.busy:
        dense._admit()
        ring._admit()
        td, ad, sd = dense.sched.plan()
        tr, ar, sr = ring.sched.plan()
        if not ((td == tr).all() and (ad == ar).all() and (sd == sr).all()):
            raise SmokeFailure("ring and dense schedulers diverged")
        ld = np.asarray(dense.backend.step(td, ad), np.float32)
        lr = np.asarray(ring.backend.step(tr, ar), np.float32)
        if sd.any():
            max_diff = max(max_diff, float(np.abs(ld[sd] - lr[sd]).max()))
        nd, nr = ld.argmax(-1), lr.argmax(-1)
        for row in np.where(sd & (nd != nr))[0]:
            gap = ld[row].max() - np.partition(ld[row], -2)[-2]
            if gap < TIE_GAP:
                ties += 1
            else:
                bad += 1
        dense.sched.commit(sd, nd)
        ring.sched.commit(sr, nd)
    return ties, bad, max_diff


def phase_ring(cfg, scfg, seed: int, n_chips: int) -> None:
    import gc

    import jax
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ServeEngine
    from repro.serve.sharded_cache import RingShardedBackend

    mesh = make_mesh((1, n_chips), ("data", "model"))
    params = init_params(cfg, seed)
    prompts = make_prompts(cfg.vocab_size, seed)
    for use_kernel in (False, True):
        kcfg = replace(cfg, use_kernel=use_kernel)
        t0 = time.perf_counter()
        dense = ServeEngine(cfg, scfg, params)
        ring = ServeEngine(kcfg, scfg, params, backend=RingShardedBackend(
            kcfg, scfg, params, mesh, mode="qlr"))
        ties, bad, diff = _lockstep(dense, ring, prompts)
        hops = "kernel" if use_kernel else "jnp"
        log(f"smoke run, not a benchmark: {ring.backend.name} ({hops} hops) "
            f"on 1x{n_chips} vs dense on {jax.devices()[0]}: "
            f"{N_REQUESTS} requests x {MAX_NEW} tokens in "
            f"{time.perf_counter() - t0:.3f} s (compile included); "
            f"max |logit diff| {diff:.6g}, {ties} certified ties")
        check(bad == 0, f"ring qlr ({hops} hops) greedy tokens match dense "
              f"(mismatches only at top-2 gaps < {TIE_GAP})")
        del dense, ring
        gc.collect()
    log(f"peak device memory (device 0): {peak_gib(jax.devices()[0])}")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve, train and kernels on one chip; "
                         "4: ring serving on a 2x2 host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.configs import ServeConfig, get_config
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    cfg = get_config(ARCH)
    scfg = ServeConfig(prefill_chunk=256)
    log(f"{ARCH} at published widths ({cfg.dtype}), ServeConfig "
        f"max_batch={scfg.max_batch} max_seq_len={scfg.max_seq_len} "
        f"prefill_chunk={scfg.prefill_chunk}")

    if args.chips == 1:
        phases = [("serve", lambda: phase_serve(cfg, scfg, args.seed)),
                  ("train", lambda: phase_train(ARCH, args.seed)),
                  ("kernels", lambda: phase_kernels(cfg, args.seed))]
    else:
        phases = [("ring", lambda: phase_ring(cfg, scfg, args.seed,
                                              args.chips))]
    for name, run in phases:
        log(f"phase {name}")
        t0 = time.perf_counter()
        try:
            run()
        except Exception as e:                  # report, then fail the run
            import traceback
            traceback.print_exc()
            print(f"chip_smoke: phase {name} failed: {e!r}", file=sys.stderr)
            return 1
        log(f"phase {name} passed in {time.perf_counter() - t0:.3f} s")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
