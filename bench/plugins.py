"""Plug-ins found by file, by the name a configuration or BENCHMARK.json
gives: a configuration's model family (``families/<family>.py``, its
``"family"`` key, ``"dense"`` where it has none), its serving backend
(``backends/<backend>.py``, its ``serve.backend``) and each per-layer
metric's reader (``metrics/<metric>.py``). A new one is a new file and
entries that name it; nothing here names one.
"""
from __future__ import annotations

import importlib.util
from functools import lru_cache

from bench.spec import BENCH


@lru_cache(maxsize=None)
def load(kind: str, name: str):
    """The module ``<kind>/<name>.py`` under ``bench/``, loaded once."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no {kind} plug-in {name!r}: "
            f"{path.relative_to(BENCH.parent)} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(conf: dict):
    return load("families", conf.get("family", "dense"))


def backend(conf: dict):
    return load("backends", conf["serve"]["backend"])


def check(conf: dict) -> None:
    """Fail, naming the missing file, unless the configuration's family
    and (for a serving configuration) backend are there."""
    family(conf)
    if "serve" in conf:
        backend(conf)
