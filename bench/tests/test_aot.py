"""``tools/aot.py`` compiles the dense cells' programs at full size for a
described TPU v5e, through the family dispatch, to the bytes it compiled
them to before families were files (figures of that code, written in)."""
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import spec  # noqa: E402
from bench.tools import aot  # noqa: E402

# (argument, output, temp, alias) bytes on one described v5e
BYTES = {
    "qwen3-0.6b.chat": {
        "decode_step": (8708328448, 7535657472, 839798272, 7516209152),
        "prefill_into_cache": (8708329472, 7516819968, 1410543104,
                               7516209152),
        "reference_hidden": (1192249344, 134217728, 2416887808, 0)},
    "olmo-1b.batch-decode": {
        "decode_step": (10943472640, 8596382208, 225792, 8589942784),
        "prefill_into_cache": (10943473664, 8590148096, 1510594560,
                               8589942784),
        "reference_hidden": (2353659904, 268435456, 2416629760, 0)},
}


@pytest.fixture(scope="module")
def device():
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.mark.parametrize("name", sorted(BYTES))
def test_dense_cells_compile_to_the_same_bytes(name, device):
    got = aot.serve_programs(spec.load_cell(name), device)
    assert {k: tuple(v.values()) for k, v in got.items()} == BYTES[name]
