"""Compile the serving main path for a described TPU v5e chip.

The TPU compiler is installed where the tests run, and compiles for a chip
that is described, not attached: nothing here executes, so these tests say
nothing about results or times. They catch at full width what interpret-mode
tests cannot: a kernel the chip's compiler refuses, and a step that does not
fit one chip's 16 GiB of HBM. Shapes are those ``chip_smoke.py`` serves.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each test worker imports
every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.kernels.sampling import greedy_sample
from repro.models.model import Model
from repro.serving import ServingEngine

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described ``v5e:2x2`` host, with the persistent compile
    cache off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # the error type depends on the TPU library
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


@pytest.fixture(scope="module")
def served(one_chip, chip_smoke):
    """Full-width qwen2-0.5b parameters and KV cache as shapes on one chip."""
    model = Model(get(chip_smoke.ARCH))
    params = _on(one_chip, model.abstract_params())
    cache = _on(one_chip, model.init_cache(chip_smoke.MAX_SLOTS,
                                           chip_smoke.MAX_LEN, concrete=False))
    return model, params, cache


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)


def test_greedy_sample_compiles(one_chip):
    logits = jax.ShapeDtypeStruct((8, 151936), jnp.float32, sharding=one_chip)
    compiled = greedy_sample.lower(logits).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_and_sample_compiles(served, one_chip, chip_smoke):
    model, params, cache = served
    b = chip_smoke.MAX_SLOTS
    i32, flag = (jax.ShapeDtypeStruct((b,), t, sharding=one_chip)
                 for t in (jnp.int32, jnp.bool_))
    prev = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    step = ServingEngine.compile_decode(model, sampling="fused",
                                        sample_backend="pallas")
    compiled = step.lower(params, cache, prev, i32, flag, i32, flag).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_decode_program_is_named(served, one_chip, chip_smoke):
    """The decode step shows in a device profile under its own name."""
    model, params, cache = served
    b = chip_smoke.MAX_SLOTS
    i32, flag = (jax.ShapeDtypeStruct((b,), t, sharding=one_chip)
                 for t in (jnp.int32, jnp.bool_))
    prev = jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip)
    step = ServingEngine.compile_decode(model, sampling="fused",
                                        sample_backend="pallas")
    text = step.lower(params, cache, prev, i32, flag, i32, flag).as_text()
    assert text.startswith("module @jit_decode_and_sample ")


def _prefill_lowered(served, one_chip, chip_smoke):
    model, params, cache = served
    b = chip_smoke.MAX_SLOTS
    chunk = jax.ShapeDtypeStruct((chip_smoke.PREFILL_CHUNK,), jnp.int32,
                                 sharding=one_chip)
    pos0 = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    n_valid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip)
    return ServingEngine.compile_prefill(model).lower(
        params, cache, chunk, pos0, n_valid, mask)


def test_prefill_chunk_compiles(served, one_chip, chip_smoke):
    _fits_one_chip(_prefill_lowered(served, one_chip, chip_smoke).compile())


def test_dense_prefill_chunk_writes_the_cache_in_place(served, one_chip,
                                                       chip_smoke):
    """The dense chunk path keeps its profile name, fits, and holds no second
    KV cache: its temporaries stay under the cache it is donated (the scan
    of masked decode steps needed 4.4x the cache)."""
    model, _, cache = served
    assert model.prefill_path == "chunk"
    lowered = _prefill_lowered(served, one_chip, chip_smoke)
    assert lowered.as_text().startswith("module @jit_prefill_chunk ")
    compiled = lowered.compile()
    _fits_one_chip(compiled)
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes
