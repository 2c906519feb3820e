"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with JAX, so the kernels of the served path
and a whole served step can be compiled for a chip that is described, not
attached.  That catches what interpret mode cannot: Mosaic lowering
refusals, unaligned slices, VMEM overruns, programs that do not fit HBM.
Nothing runs, so these say nothing about results or speed.

Shapes are internlm2-1.8b's published widths (d_model 2048, approximator
hidden 256, bf16, block_t 128, n_approx 3 plus the zero pseudo-class).
The decode tick feeds the kernels ``worst_case_rows(8, 4, 128)`` class-
sorted rows from an 8-slot table; a prefill chunk feeds 8 x 16 rows.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels import fused_dispatch, ops, switched_mlp
from repro.models import model as M
from repro.runtime import steps

D, D_H, N_CLASSES, BLOCK_T = 2048, 256, 4, 128
BATCH, MAX_LEN, PAGE, CHUNK = 8, 1024, 16, 16
N_PAGES = BATCH * MAX_LEN // PAGE
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def _weights(one_chip):
    bf = jnp.bfloat16
    return (_spec(one_chip, (N_CLASSES, D, D_H), bf),
            _spec(one_chip, (N_CLASSES, 1, D_H), bf),
            _spec(one_chip, (N_CLASSES, D_H, D), bf),
            _spec(one_chip, (N_CLASSES, 1, D), bf))


@pytest.mark.parametrize("rows", [BATCH, BATCH * CHUNK],
                         ids=["decode", "prefill_chunk"])
def test_switched_mlp_compiles_for_v5e(one_chip, no_persistent_cache, rows):
    t_pad = ops.worst_case_rows(rows, N_CLASSES, BLOCK_T)
    fn = jax.jit(lambda x, tc, *w: switched_mlp.switched_mlp(
        x, tc, *w, block_t=BLOCK_T))
    compiled = fn.lower(
        _spec(one_chip, (t_pad, D), jnp.bfloat16),
        _spec(one_chip, (t_pad // BLOCK_T,), jnp.int32),
        *_weights(one_chip)).compile()
    _check(compiled)


@pytest.mark.parametrize("rows", [BATCH, BATCH * CHUNK],
                         ids=["decode", "prefill_chunk"])
def test_switched_mlp_fused_compiles_for_v5e(one_chip, no_persistent_cache,
                                             rows):
    t_pad = ops.worst_case_rows(rows, N_CLASSES, BLOCK_T)
    fn = jax.jit(lambda x, r, tc, *w: fused_dispatch.switched_mlp_fused(
        x, r, tc, *w, block_t=BLOCK_T, vector_io=False))
    compiled = fn.lower(
        _spec(one_chip, (rows, D), jnp.bfloat16),
        _spec(one_chip, (t_pad,), jnp.int32),
        _spec(one_chip, (t_pad // BLOCK_T,), jnp.int32),
        *_weights(one_chip)).compile()
    _check(compiled)


@pytest.mark.parametrize("backend", ["pallas", "pallas_fused"])
@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_served_step_compiles_for_v5e(one_chip, no_persistent_cache,
                                      backend, step):
    """The served tick-scope step of internlm2-1.8b at full width on the
    paged cache, cut to 2 layers: the layers are scanned, so the compile
    barely depends on depth.  The cfg carries the compiled kernels
    directly (``interpret=False``); ``mcma_serve_config`` would pick
    interpret mode here because this process sees only a CPU."""
    cfg = get_config("internlm2-1.8b")
    cfg = dataclasses.replace(cfg, n_layers=2, approx=dataclasses.replace(
        cfg.approx, enable=True, backend=backend, interpret=False,
        route_scope="tick"))
    place = lambda tree: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: M.init_model(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: M.init_cache(
        cfg, BATCH, MAX_LEN, page_size=PAGE, kv_pages=N_PAGES)))
    if step == "decode":
        fn = steps.make_decode_step(cfg, with_stats=True)
        args = (_spec(one_chip, (BATCH, 1), jnp.int32),
                _spec(one_chip, (BATCH,), jnp.bool_))
    else:
        fn = steps.make_prefill_chunk_step(cfg, with_stats=True)
        args = (_spec(one_chip, (BATCH, CHUNK), jnp.int32),
                _spec(one_chip, (BATCH,), jnp.int32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    _check(compiled)
