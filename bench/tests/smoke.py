"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds: the same
files, with the model cut by its family's ``smoke`` and slots, lengths and
load scaled down. For tests only; no number from them is a measurement."""
from __future__ import annotations

import copy

from bench import plugins, spec


def smoke_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    conf = plugins.family(cell.config).smoke(copy.deepcopy(cell.config))
    conf["serve"] = dict(conf["serve"], max_batch=4, max_seq_len=256,
                         prefill_chunk=32)
    mix = copy.deepcopy(cell.traffic)
    if mix["kind"] == "train":
        mix.update(batch=2, seq_len=64)
    else:
        for key, hi in (("prompt_tokens", 80), ("output_tokens", 24)):
            d = mix[key]
            mix[key] = {"median": max(4, d["median"] // 8),
                        "sigma": d["sigma"], "min": 2, "max": hi}
        mix.update(pool=64, check_tokens=400)
        if mix["kind"] == "open_loop":
            mix.update(rate_rps=20.0, warm_s=1.0)
        else:
            mix["clients"] = 4
    cell.config, cell.traffic = conf, mix
    return cell
