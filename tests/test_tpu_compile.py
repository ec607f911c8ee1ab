"""Compile the main path for a TPU v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts: unaligned slices, more
fast memory than a kernel may use, a program that does not fit the chip.
These tests lower the ``event_join`` kernel at the shapes the worker feeds
it and the full-width ``llama3.2-3b`` serving steps, and compile them for one
chip of a ``v5e:2x2`` topology.  Nothing runs, so they say nothing about
results or times.

The topology is described inside a fixture only: one process at a time may
load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.models import Model, unbox

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


# (N events, T triggers): one worker batch at Table-1 width, the whole
# Table-1 join in one call, the sharded pool's steady per-shard batch, and
# the shape buckets join dispatch pads a default batch and a larger one to.
@pytest.mark.parametrize("n,t", [(4096, 100), (200_000, 100), (512, 13),
                                 (512, 128), (1024, 128)])
def test_event_join_compiles_for_v5e(one_chip, no_compile_cache, n, t):
    from repro.kernels.event_join.ops import event_join

    events = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one_chip)
    compiled = event_join.lower(events, rows, rows).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_llama3_2_3b_serving_step_fits_one_v5e(one_chip, no_compile_cache,
                                               step):
    """Full-width llama3.2-3b at the serving engine's batch 4 and max_len
    256: the step compiles for one chip and its arguments, outputs and
    temporaries fit the chip's 16 GB."""
    model = Model(get_config("llama3.2-3b"))
    batch, max_len, prompt_len = 4, 256, 32

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            tree)

    params = on_chip(jax.eval_shape(
        lambda: unbox(model.init(jax.random.PRNGKey(0)))))
    if step == "decode":
        cache = on_chip(jax.eval_shape(
            lambda: unbox(model.init_cache(batch, max_len))))
        tokens = on_chip(jax.ShapeDtypeStruct((batch, 1), jnp.int32))
        lowered = jax.jit(model.decode).lower(params, cache,
                                              {"tokens": tokens})
    else:
        tokens = on_chip(jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32))
        lowered = jax.jit(
            lambda p, b: model.prefill(p, b, max_len=max_len)).lower(
                params, {"tokens": tokens})
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 7e9  # 3.6 B bf16 parameters
    assert total < V5E_HBM_BYTES, total
