"""shard_map and the queue streams on a 1-device mesh, where every hop is
a self-loop: the smallest program that drives the link modes end to end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import queues
from repro.core.topology import ring
from repro.launch.mesh import make_mesh


def test_shard_map_runs():
    mesh = make_mesh((1,), ("model",))
    fn = jax.shard_map(lambda x: x * 2, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    y = jax.jit(fn)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(y), np.arange(4.0) * 2)


@pytest.mark.parametrize("mode", queues.MODES)
def test_queues_stream_single_device(mode):
    """queues.stream runs in every link mode on a 1-device mesh."""
    mesh = make_mesh((1,), ("model",))
    topo = ring("model", 1)

    def body(x):
        def consume(acc, buf, t):
            return acc + jnp.sum(buf)
        state, buf = queues.stream(topo, x, 3, consume, jnp.zeros(()), mode)
        return state[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("model"),
                       out_specs=P("model"), check_vma=False)
    out = jax.jit(fn)(jnp.ones((4,)))
    # self-loop ring: the same shard is consumed at every one of the 3 steps
    assert float(out[0]) == 12.0


@pytest.mark.parametrize("shape,axes", [((1,), ("model",)),
                                        ((1, 1), ("data", "model"))])
def test_make_mesh_axes_are_auto(shape, axes):
    """Every mesh has Auto axes: the model's sharding constraints refuse
    the explicit axes that jax.make_mesh defaults to."""
    from jax.sharding import AxisType
    mesh = make_mesh(shape, axes)
    assert mesh.axis_names == axes
    assert mesh.axis_types == (AxisType.Auto,) * len(axes)


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_enable_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set; otherwise
    the cache goes to one fixed directory in the checkout."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.launch import compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if env_dir:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            root = compile_cache.CHECKOUT_CACHE_DIR
            assert got == str(root) and root.name == ".jax_cache"
            assert (root.parent / "src" / "repro").is_dir()
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()
