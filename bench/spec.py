"""Finds a cell's files by name and turns them into the program's configs.

A workload names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); its limits for ``correct`` are in
``cells/<workload>.json``, and each per-layer metric is read by
``metrics/<metric>.py``; a configuration's model family and serving
backend are files too (``plugins.py``). Nothing here knows a cell by
name.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    workload: dict        # the BENCHMARK.json entry
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    limits: dict          # cells/<workload>.json: number -> limit
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: Path = ROOT / "BENCHMARK.json") -> Cell:
    """A workload of BENCHMARK.json, or one held out of it: a cell whose
    ``cells/<name>.json`` carries its entry under ``held_out`` runs for
    the control and the tests, and reports no metric."""
    spec = _json(bench_json)
    found = [w for w in spec["workloads"] if w["name"] == name]
    own = BENCH / "cells" / f"{name}.json"
    if found:
        w = found[0]
        conf = ROOT / [c for c in spec["configs"]
                       if c["name"] == w["config"]][0]["file"]
    elif own.exists() and "held_out" in _json(own):
        w = _json(own)["held_out"]["workload"]
        conf = BENCH / "configs" / f"{w['config']}.json"
    else:
        raise KeyError(f"no workload {name!r} in {bench_json.name}; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    return Cell(
        name=name, workload=w,
        config=_json(conf),
        traffic=_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=_json(own)["limits"],
        end_to_end=[m for m in spec["end_to_end"]
                    if found and _applies(m, name)],
        per_layer=[m for m in spec["per_layer"]
                   if found and _applies(m, name)])


# ------------------------------------------------ configs for the program
def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, by its
    family (``families/<family>.py``)."""
    from bench import plugins
    return plugins.family(conf).model_config(conf)


def serve_config(conf: dict):
    from repro.configs.base import ServeConfig
    s = conf["serve"]
    return ServeConfig(max_batch=s["max_batch"], max_seq_len=s["max_seq_len"],
                       prefill_chunk=s["prefill_chunk"], temperature=0.0)


def train_config(conf: dict):
    from repro.configs.base import TrainConfig
    t = conf["train"]
    return TrainConfig(
        learning_rate=t["learning_rate"], weight_decay=t["weight_decay"],
        beta1=t["beta1"], beta2=t["beta2"], eps=t["eps"],
        grad_clip=t["grad_clip"], warmup_steps=t["warmup_steps"],
        total_steps=t["total_steps"], schedule=t["schedule"],
        use_master_weights=t["master_weights"], checkpoint_every=0)
