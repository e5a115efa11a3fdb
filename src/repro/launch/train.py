"""Training driver.

Local/e2e:   PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
                 --smoke --steps 30 --batch 8 --seq 128
Cluster:     the same entry point under launch/cluster/*.sh with
             jax.distributed auto-initialization (see --multihost).

Features: config overrides (--set k=v), deterministic data pipeline,
async atomic checkpoints + auto-resume, elastic mesh restore, preemption
hook (SIGTERM), straggler watchdog, metrics JSONL.

Observability (DESIGN.md §8): --metrics-out FILE.json snapshots the run's
obs registry (steps/tokens counters, loss/lr gauges, step-time histogram)
as JSON plus a FILE.prom Prometheus twin; --trace-out DIR records a
profiler trace of the run there: the train.* spans (data, step,
checkpoint, straggler; repro.obs.trace) on the device's timeline.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time
from dataclasses import replace
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import (
    TrainConfig,
    apply_overrides,
    config_summary,
    get_config,
    get_smoke_config,
)
from repro.data.pipeline import DataLoader, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.sharding.partitioning import shardings_from_axes
from repro.train import step as step_lib
from repro.train.checkpoint import CheckpointManager, install_preemption_hook
from repro.train.metrics import MetricLogger, StepTimer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="",
                    help="mesh as 'dxm' (e.g. 2x4); default all devices on 'data'")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    help="model config overrides key=value")
    ap.add_argument("--train-set", action="append", default=[],
                    dest="train_overrides")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--multihost", action="store_true",
                    help="jax.distributed.initialize() from env")
    ap.add_argument("--log", default="")
    ap.add_argument("--metrics-out", default="",
                    help="write metrics snapshot JSON here (+ .prom twin)")
    ap.add_argument("--trace-out", default="",
                    help="record a profiler trace of the run in this "
                         "directory")
    args = ap.parse_args(argv)

    if args.multihost:
        jax.distributed.initialize()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = apply_overrides(cfg, args.overrides)
    tcfg = TrainConfig(total_steps=args.steps,
                       checkpoint_dir=args.ckpt_dir or f"/tmp/repro_ckpt_{args.arch}")
    tcfg = apply_overrides(tcfg, args.train_overrides)
    print(config_summary(cfg))

    n_dev = len(jax.devices())
    if args.mesh:
        d, m = (int(v) for v in args.mesh.split("x"))
    else:
        d, m = n_dev, 1
    mesh = make_mesh((d, m), ("data", "model"))

    # the state is donated: a step writes the new state into the old one's
    # buffers instead of holding both (at full width two do not fit a chip)
    train_step = jax.jit(step_lib.make_train_step(cfg, tcfg, mesh),
                         donate_argnums=0)
    state_sds, state_axes = step_lib.state_shapes(cfg, tcfg, mesh)

    ckpt = CheckpointManager(tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints,
                             async_save=tcfg.async_checkpoint)
    start_step = 0
    loader = DataLoader(SyntheticLM(cfg.vocab_size, seed=tcfg.seed),
                        global_batch=args.batch, seq_len=args.seq,
                        host_id=jax.process_index(),
                        host_count=jax.process_count())

    latest = ckpt.latest_step() if args.resume else None
    if latest is not None:
        state = ckpt.restore(latest, state_sds)
        meta = ckpt.restore_meta(latest)
        loader.load_state_dict(meta.get("data_state", {"step": 0}))
        start_step = latest
        print(f"resumed from step {latest}")
    else:
        state = step_lib.init_state(cfg, tcfg, jax.random.PRNGKey(tcfg.seed))
        state = jax.device_put(
            state, shardings_from_axes(state, state_axes, mesh))

    def emergency_save():
        step = int(np.asarray(state["opt"]["step"]))
        print(f"[preempt] checkpointing at step {step}")
        ckpt.save(step, state, extra={"data_state": loader.state_dict()})
        ckpt.wait()

    install_preemption_hook(emergency_save)

    logger = MetricLogger(args.log or None)
    timer = StepTimer(deadline_s=tcfg.straggler_deadline_s)
    tokens_per_step = args.batch * args.seq

    from repro.obs import metrics as obs_metrics
    from repro.obs import trace
    registry = obs_metrics.Registry()
    step_hist = registry.histogram("repro_train_step_seconds",
                                   "train step wall time")

    profile = (jax.profiler.trace(args.trace_out) if args.trace_out
               else contextlib.nullcontext())
    with profile:
        for step_i in range(start_step, args.steps):
            with trace.span("train.data"):
                batch = next(loader)
                batch = jax.device_put(
                    {k: jnp.asarray(v) for k, v in batch.items()})
            timer.start()
            with trace.span("train.step") as sp:
                if trace.enabled():
                    sp.set_metadata(step=step_i)
                state, metrics = train_step(state, batch)
                metrics = jax.tree_util.tree_map(np.asarray, metrics)
            dt, slow = timer.stop()
            step_hist.observe(dt)
            registry.counter("repro_train_steps_total",
                             "train steps run").inc()
            registry.counter("repro_train_tokens_total",
                             "tokens consumed").inc(tokens_per_step)
            registry.gauge("repro_train_loss", "last logged loss").set(
                float(metrics["loss"]))
            registry.gauge("repro_train_lr", "last learning rate").set(
                float(metrics["lr"]))
            registry.gauge("repro_train_tokens_per_second",
                           "tokens / step wall time").set(
                tokens_per_step / max(dt, 1e-9))
            if slow:
                trace.instant("train.straggler", step=step_i, seconds=dt)
                registry.counter("repro_train_stragglers_total",
                                 "steps past the watchdog deadline").inc()
                print(f"[watchdog] step {step_i} took {dt:.2f}s "
                      f"(deadline {tcfg.straggler_deadline_s}s)")
            if step_i % tcfg.log_every == 0 or step_i == args.steps - 1:
                logger.log(step_i, loss=float(metrics["loss"]),
                           grad_norm=float(metrics["grad_norm"]),
                           lr=float(metrics["lr"]),
                           tok_per_s=tokens_per_step / max(dt, 1e-9),
                           step_s=dt)
            if tcfg.checkpoint_every and \
                    (step_i + 1) % tcfg.checkpoint_every == 0:
                with trace.span("train.checkpoint"):
                    ckpt.save(step_i + 1, state,
                              extra={"data_state": loader.state_dict()})
        with trace.span("train.checkpoint"):
            ckpt.save(args.steps, state,
                      extra={"data_state": loader.state_dict()})
            ckpt.wait()
    loader.close()
    logger.close()
    if args.metrics_out:
        registry.dump_json(args.metrics_out)
        prom = args.metrics_out.rsplit(".", 1)[0] + ".prom"
        registry.dump_prometheus(prom)
        print(f"wrote {args.metrics_out}\nwrote {prom}")
    if args.trace_out:
        print(f"wrote a profiler trace under {args.trace_out}")
    print(f"done: {args.steps} steps; watchdog {timer.summary()}")
    return state


if __name__ == "__main__":
    enable_compile_cache()
    main()
