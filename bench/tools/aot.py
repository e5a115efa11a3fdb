#!/usr/bin/env python3
"""Compile a cell's programs at full size for a described TPU v5e, with no
chip attached, and print each one's memory analysis (bytes per device).

  JAX_PLATFORMS=cpu python3 bench/tools/aot.py <workload> [...]

Serving cells: the decode step, block prefill and the reference's forward
over the checked sequences; training cells: the train step and the
reference's AdamW step. A compile that passes is not a chip run.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import reference, spec, weights  # noqa: E402

GiB = 2 ** 30


def on(dev, tree):
    sh = SingleDeviceSharding(dev)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree)


def report(name, compiled) -> dict:
    """Print a program's memory analysis; returns its bytes."""
    m = compiled.memory_analysis()
    out = {"argument": m.argument_size_in_bytes,
           "output": m.output_size_in_bytes, "temp": m.temp_size_in_bytes,
           "alias": m.alias_size_in_bytes}
    total = out["argument"] + out["output"] + out["temp"] - out["alias"]
    print(f"{name}: args {out['argument'] / GiB:.3f} GiB, out "
          f"{out['output'] / GiB:.3f}, temp {out['temp'] / GiB:.3f}, alias "
          f"{out['alias'] / GiB:.3f}: {total / GiB:.3f} GiB in all",
          flush=True)
    return out


def serve_programs(cell, dev) -> dict:
    """{program: bytes} of a serving cell's programs on one device."""
    from repro.models import build_model
    conf = cell.config
    cfg, scfg = spec.model_config(conf), spec.serve_config(conf)
    model = build_model(cfg)
    p = on(dev, weights.weight_shapes(conf))
    cache = on(dev, jax.eval_shape(lambda: model.init_cache(
        scfg.max_batch, scfg.max_seq_len)))
    i32 = jnp.int32
    tok = on(dev, jax.ShapeDtypeStruct((scfg.max_batch, 1), i32))
    act = on(dev, jax.ShapeDtypeStruct((scfg.max_batch,), jnp.bool_))
    out = {"decode_step": report("decode_step", jax.jit(
        model.decode_step, donate_argnums=1).lower(
        p, cache, tok, act).compile())}
    chunk = on(dev, jax.ShapeDtypeStruct((scfg.prefill_chunk,), i32))
    s = on(dev, jax.ShapeDtypeStruct((), i32))
    out["prefill_into_cache"] = report("prefill_into_cache", jax.jit(
        model.prefill_into_cache, donate_argnums=1).lower(
        p, cache, chunk, s, s).compile())
    # the reference over the checked requests: at most this many rows of
    # the longest sequence the cell can serve
    rows = 16
    L = scfg.max_seq_len
    toks = on(dev, jax.ShapeDtypeStruct((rows, L), i32))
    out["reference_hidden"] = report(
        f"reference hidden [{rows} x {L}]", jax.jit(
            lambda w, t: reference.hidden(conf, w, t)).lower(
            p, toks).compile())
    return out


def train_programs(cell, dev):
    from repro.launch.mesh import make_mesh
    from repro.train import optimizer as opt
    from repro.train import step as step_lib
    conf, mix = cell.config, cell.traffic
    cfg, tcfg = spec.model_config(conf), spec.train_config(conf)
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    p = weights.weight_shapes(conf)
    state = on(dev, jax.eval_shape(
        lambda p: {"params": p, "opt": opt.init_opt_state(p, tcfg)}, p))
    i32 = jnp.int32
    b = on(dev, {k: jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]), i32)
                 for k in ("tokens", "targets")})
    report("train_step", jax.jit(step_lib.make_train_step(cfg, tcfg, mesh),
                                 donate_argnums=0).lower(state, b).compile())
    w32 = on(dev, weights.weight_shapes(conf, jnp.float32))
    t = on(dev, jax.ShapeDtypeStruct((), jnp.float32))
    report("reference adamw_step", jax.jit(
        lambda w, m, v, t, b: reference.adamw_step(conf, w, m, v, t, b),
        donate_argnums=(0, 1, 2)).lower(w32, w32, w32, t, b).compile())


def main(names):
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]
    for name in names:
        cell = spec.load_cell(name)
        print(f"== {name} on one described {dev.device_kind}", flush=True)
        if cell.traffic["kind"] == "train":
            train_programs(cell, dev)
        else:
            serve_programs(cell, dev)


if __name__ == "__main__":
    main(sys.argv[1:])
