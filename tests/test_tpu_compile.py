"""The Pallas kernels compile for a TPU v5e at main-path widths.

No chip is needed: the TPU compiler is installed and compiles for a
described ``v5e:2x2`` topology. The kernel wrappers pick interpret mode
from ``jax.default_backend()``, which is the CPU here, so each test steers
that choice to ``"tpu"`` itself. The topology is described inside a
fixture (never at import), and every test skips from there if it cannot be.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kmeans import kmeans_assign
from repro.kernels.window_agg import window_agg

BF16, F32 = jnp.bfloat16, jnp.float32

#: name -> (wrapper, argument shapes and dtypes)
CASES = {
    "flash_gqa_2048": (
        flash_attention,
        [
            ((1, 2048, 16, 128), BF16),
            ((1, 2048, 8, 128), BF16),
            ((1, 2048, 8, 128), BF16),
        ],
    ),
    "decode_cache_4096": (
        decode_attention,
        [
            ((8, 16, 128), BF16),
            ((8, 4096, 8, 128), BF16),
            ((8, 4096, 8, 128), BF16),
            ((8, 4096), jnp.bool_),
        ],
    ),
    "kmeans_524288x3_k4": (kmeans_assign, [((524288, 3), F32), ((4, 3), F32)]),
    "window_mean_524288x4_w8": (
        lambda x: window_agg(x, window=8, agg="mean"),
        [((524288, 4), F32)],
    ),
    "window_max_524288x4_w8": (
        lambda x: window_agg(x, window=8, agg="max"),
        [((524288, 4), F32)],
    ),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    jax.clear_caches()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, name
