"""Training cells: the program's train step (``train/step.make_train_step``,
jitted with the state donated), fed rows from ``traffic.train_batch``.

Set-up makes the weights on the device, builds the AdamW state from them
in one jitted call, compiles the step and drives that same step through
its first ``checked_steps`` steps, reading what the check compares: each
step's loss, the first gradient as the optimizer holds it (first moment
over 1 - beta1), and each weight's change after the last checked step
(from the float32 master weights the next step starts from). The window
then runs further steps, fetching each step's metrics as the program's
launcher does, until ``seconds`` have passed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import reference, traffic, weights
from bench.spec import model_config, train_config
from bench.tracing import WINDOW

HOST_SPANS = ("data", "step")


def leaf_norms(tree, scale: float = 1.0, minus=None) -> np.ndarray:
    """Per-leaf float32 norms of ``tree`` (or of ``tree - minus``)."""
    import jax
    import jax.numpy as jnp

    def norms(t, m):
        if m is None:
            m = jax.tree_util.tree_map(jnp.zeros_like, t)
        return jnp.stack([jnp.linalg.norm(
            (a.astype(jnp.float32) - b.astype(jnp.float32)).ravel()) * scale
            for a, b in zip(jax.tree_util.tree_leaves(t),
                            jax.tree_util.tree_leaves(m))])
    return np.asarray(jax.jit(norms)(tree, minus), np.float64)


def leaf_names(tree) -> list:
    import jax
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def program_step(cfg, tcfg, mesh):
    from repro.train import step as step_lib
    return step_lib.make_train_step(cfg, tcfg, mesh)


make_step = program_step          # what a run builds; tests break it here


class Trainer:
    def __init__(self, cell, seed: int, step_factory=None):
        import jax
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.train import optimizer as opt
        self.conf, self.mix, self.seed = cell.config, cell.traffic, seed
        self.cfg, self.tcfg = model_config(self.conf), train_config(self.conf)
        weights.check_layout(self.conf, build_model(self.cfg))
        mesh = make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
        factory = step_factory or make_step
        self.step_fn = jax.jit(factory(self.cfg, self.tcfg, mesh),
                               donate_argnums=0)
        tcfg = self.tcfg
        self.state = jax.jit(
            lambda p: {"params": p, "opt": opt.init_opt_state(p, tcfg)},
            donate_argnums=0)(weights.make_weights(self.conf, seed))
        self.steps = 0
        self.losses: list = []

    def batch(self):
        import jax
        return jax.device_put(traffic.train_batch(
            self.mix, self.seed, self.steps, self.cfg.vocab_size))

    def step(self) -> dict:
        import jax
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("data"):
            batch = self.batch()
        self.state, metrics = self.step_fn(self.state, batch)
        metrics = jax.tree_util.tree_map(np.asarray, metrics)
        self.steps += 1
        return metrics

    def checked_steps(self) -> dict:
        """The first steps, with what the check compares."""
        out = {"losses": []}
        for i in range(self.mix["checked_steps"]):
            out["losses"].append(float(self.step()["loss"]))
            if i == 0:
                out["grad"] = leaf_norms(self.state["opt"]["m"],
                                         1.0 / (1.0 - self.tcfg.beta1))
        w0 = weights.make_weights(self.conf, self.seed)
        out["update"] = leaf_norms(self.state["opt"]["master"], minus=w0)
        out["names"] = leaf_names(w0)
        del w0
        return out

    def run(self, seconds: float) -> tuple[int, float]:
        from jax.profiler import TraceAnnotation
        n0 = self.steps
        t0 = time.perf_counter()
        with TraceAnnotation(WINDOW):
            while time.perf_counter() - t0 < seconds:
                with TraceAnnotation("step"):
                    self.step()
            return self.steps - n0, time.perf_counter() - t0

    def free(self) -> None:
        self.state = None
        gc.collect()


def reference_readings(cell, seed: int, fp8: bool = False) -> dict:
    """The reference's losses, first clipped gradient and weight changes
    over the same first batches, from the same weights."""
    import jax
    import jax.numpy as jnp
    conf, mix = cell.config, cell.traffic
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               weights.make_weights(conf, seed))
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    step = jax.jit(lambda w, m, v, t, b: reference.adamw_step(
        conf, w, m, v, t, b, fp8=fp8), donate_argnums=(0, 1, 2))
    out = {"losses": []}
    for i in range(mix["checked_steps"]):
        batch = jax.device_put(traffic.train_batch(
            mix, seed, i, conf["vocab_size"]))
        w, m, v, loss, g = step(w, m, v, jnp.float32(i + 1), batch)
        out["losses"].append(float(loss))
        if i == 0:
            out["grad"] = leaf_norms(g)
        del g
    del m, v
    out["update"] = leaf_norms(w, minus=weights.make_weights(conf, seed))
    return out


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared: worst relative loss gap over the checked
    steps, and by the worst leaf the gap between the program's and the
    reference's gradient norm and weight-change norm, each against the
    larger of that leaf's reference norm and the median leaf's. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    are left out of the change (they move by round-off alone)."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    g_med = float(np.median(ref["grad"]))
    moving = ref["grad"] >= 1e-3 * g_med
    for key, keep in (("grad", np.ones_like(moving)), ("update", moving)):
        r, p = ref[key][keep], prog[key][keep]
        denom = np.maximum(r, np.median(r))
        gaps = np.abs(p - r) / denom
        out[f"{key}_gap"] = float(gaps.max())
        names = np.asarray(prog["names"])[keep]
        out[f"{key}_worst_leaf"] = str(names[int(gaps.argmax())])
    return out
