"""Compile rehearsals: the serving-path Pallas kernels compiled for a
described TPU v5e (no chip attached), at serving buckets.

Interpret-mode tests cannot see what the TPU compiler refuses (block
shapes off the (8, 128) tiling, VMEM overuse); these compiles can.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one running this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_solve.ops import _fused_impl
from repro.kernels.pareto_filter.kernel import pareto_filter_pallas
from repro.kernels.ws_reduce.kernel import ws_reduce_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **kwargs) -> str:
    return jax.jit(fn, static_argnames=tuple(kwargs)).lower(
        *args, **kwargs).compile().as_text()


# 704 rows: the fused route's global filter over 64 candidates x 11 weights.
@pytest.mark.parametrize("n", [512, 704, 4096])
def test_pareto_filter_compiles_for_v5e(one_chip, n):
    text = _compiled_text(pareto_filter_pallas,
                          _spec((n, 2), jnp.float32, one_chip),
                          _spec((n,), jnp.bool_, one_chip), interpret=False)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [4, 64])
def test_ws_reduce_compiles_for_v5e(one_chip, m):
    text = _compiled_text(ws_reduce_pallas,
                          _spec((m, 48, 2), jnp.float32, one_chip),
                          _spec((11, 2), jnp.float32, one_chip),
                          interpret=False)
    assert "tpu_custom_call" in text


def test_fused_solve_compiles_for_v5e(one_chip):
    Np, mp = 64, 64
    text = _compiled_text(_fused_impl,
                          _spec((Np, mp, 48, 2), jnp.float32, one_chip),
                          _spec((11, 2), jnp.float32, one_chip),
                          interpret=False)
    assert "tpu_custom_call" in text
