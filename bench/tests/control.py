#!/usr/bin/env python3
"""Readings that the limits of ``cells/<workload>.json`` are set from, and
the control that has to fail them.

  python3 bench/tests/control.py --workload <cell> --seconds <s> --seeds 1 2 3

On the chip, at the cell's own size, in one process: for each seed, the
number the program's run gives (the lower reading) and the number the
control gives, the reference computed in float8 e4m3 and put in the
program's place (the upper reading). Serving cells run the cell's window
at its own load and read, over the same sampled finished requests, the
widest gap below the float32 reference's best logit of the served tokens
and of the tokens the control would put first. Training cells compare the
program's checked steps, and the control's, with the float32 reference;
with ``--faults`` also the program with half of each batch left out, and
with ``--witness`` the reference with a bfloat16 embedding lookup (a look
at the cause of a gap, never a limit); ``--rows`` and ``--seq-len`` take
that look at another size than the cell's.

``test_control_fails`` runs the same at smoke size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serve_readings(cell, seed: int, seconds: float) -> dict:
    import numpy as np
    from bench import reference, weights
    from bench import serving as sd
    engine, win = sd.setup(cell, seed)
    win.run(seconds)
    picked = sd.sample_finished(win, seed, cell.traffic["check_tokens"])
    sd.free(engine)
    del engine, win
    seqs = [np.concatenate([r.prompt, np.asarray(r.handle.out_tokens,
                                                 np.int32)]) for r in picked]
    out = reference.served_gaps(
        cell.config, weights.make_weights(cell.config, seed), seqs,
        [len(r.prompt) for r in picked], control=True)
    return {"program": {"max_logit_gap": out["max_gap"]},
            "control": {"max_logit_gap": out["control_max_gap"]},
            "detail": out}


def half_batch_step(cfg, tcfg, mesh):
    """The program's step with the second half of each batch left out and
    the mean taken over the rest (a fault the check has to catch)."""
    from bench.training import program_step
    step = program_step(cfg, tcfg, mesh)

    def broken(state, batch):
        half = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:half] for k, v in batch.items()})
    return broken


def _ratios(names, a, b) -> dict:
    return {n: float(x / y) for n, x, y in zip(names, a, b)}


def train_readings(cell, seed: int, faults: bool,
                   witness: bool = False) -> dict:
    """Training: the program's checked steps against the reference, and
    the fp8 control's; with ``faults`` the program with half of each batch
    left out; with ``witness`` the reference with its embedding rows
    looked up in bfloat16 (so that the lookup's gradient is a bfloat16
    scatter-add, as the program's is), compared both with the reference
    and with the program. Per-leaf ratios of gradient and change norms,
    program over reference, come with each."""
    import jax.numpy as jnp
    from bench import reference
    from bench import training as td
    out = {}
    runs = [("program", td.program_step)]
    if faults:
        runs.append(("half_batch", half_batch_step))
    progs = {}
    for name, factory in runs:
        tr = td.Trainer(cell, seed, factory)
        progs[name] = tr.checked_steps()
        tr.free()
        del tr
    names = progs["program"]["names"]
    ref = td.reference_readings(cell, seed)
    ref["names"] = names
    for name, prog in progs.items():
        out[name] = td.compare(prog, ref)
    ctl = td.reference_readings(cell, seed, fp8=True)
    ctl["names"] = names
    out["control"] = td.compare(ctl, ref)
    out["losses"] = {"ref": ref["losses"], "control": ctl["losses"],
                     **{k: v["losses"] for k, v in progs.items()}}
    p = progs["program"]
    out["grad_ratio"] = _ratios(names, p["grad"], ref["grad"])
    out["update_ratio"] = _ratios(names, p["update"], ref["update"])
    if witness:
        reference.LOOKUP_DTYPE = jnp.bfloat16
        try:
            wit = td.reference_readings(cell, seed)
        finally:
            reference.LOOKUP_DTYPE = jnp.float32
        wit["names"] = names
        out["witness_vs_ref"] = td.compare(wit, ref)
        out["program_vs_witness"] = td.compare(p, wit)
        out["witness_grad_ratio"] = _ratios(names, wit["grad"], ref["grad"])
        out["losses"]["witness"] = wit["losses"]
    return out


def readings(cell, seed: int, seconds: float, faults: bool = False,
             witness: bool = False) -> dict:
    if cell.traffic["kind"] == "train":
        return train_readings(cell, seed, faults, witness)
    return serve_readings(cell, seed, seconds)


def fails(limits: dict, numbers: dict) -> bool:
    """Whether a reading fails one of the limits (as ``correct`` judges)."""
    return any(not (numbers[k] <= v) for k, v in limits.items()
               if k in numbers)


def test_control_fails():
    import jax
    from bench.tests.smoke import smoke_cell
    if jax.devices()[0].platform == "tpu":
        return
    for name in ("qwen3-0.6b.chat", "olmo-1b.batch-decode",
                 "qwen3-0.6b.pretrain-4k"):
        cell = smoke_cell(name)
        r = readings(cell, 5, 3.0)
        assert not fails(cell.limits, r["program"]), (name, r)
        assert fails(cell.limits, r["control"]), (name, r)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--witness", action="store_true",
                    help="training: also the reference with a bfloat16 "
                    "embedding lookup")
    ap.add_argument("--rows", type=int, default=None,
                    help="training: rows of a step, for a look at another "
                    "size (not the cell's)")
    ap.add_argument("--seq-len", type=int, default=None)
    args = ap.parse_args()
    import jax
    from bench import run, spec
    if jax.devices()[0].platform != "tpu":
        sys.exit("control: needs a TPU")
    run.enable_cache()
    cell = spec.load_cell(args.workload)
    if args.rows:
        cell.traffic["batch"] = args.rows
    if args.seq_len:
        cell.traffic["seq_len"] = args.seq_len
    for seed in args.seeds:
        r = readings(cell, seed, args.seconds, args.faults, args.witness)
        print(json.dumps({"seed": seed, **r}, default=str), flush=True)


if __name__ == "__main__":
    main()
