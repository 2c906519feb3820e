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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import scopes
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
    interpret mode here because this process sees only a CPU.  The
    decode step also reads its KV pages in place (the paged attention
    kernel, no whole-pool copy or slice)."""
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
    if step == "decode":
        _check_pages_read_in_place(compiled.as_text(), cfg.n_layers,
                                   cfg.n_kv_heads, cfg.hd)


# operations that would move a whole KV pool or stack
_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
          "dynamic-update-slice", "slice", "concatenate", "custom-call")


def _check_pages_read_in_place(hlo: str, n_layers: int, kh: int, hd: int,
                               n_pages: int = N_PAGES):
    """The decode step runs the paged attention kernel, and nothing in it
    (fused computations included) produces a layer's ``(N, P, Kh, hd)``
    pool or copies or slices the ``(L, N, P, Kh, hd)`` stack: the step's
    K/V scatter into the stack in place and the kernel reads it there."""
    kernel = [ln for ln in hlo.splitlines()
              if re.search(rf"%{scopes.PAGED_ATTENTION}[.\d]* = ", ln)]
    assert kernel and all('custom_call_target="tpu_custom_call"' in ln
                          for ln in kernel), kernel
    stack = f"{n_layers},{n_pages},{PAGE},{kh},{hd}"
    pool = f"{n_pages},{PAGE},{kh},{hd}"
    for m in re.finditer(r"= \(?\w+\[([\d,]+)\]\S*\)? ([\w-]+)\(", hlo):
        shape, op = m.groups()
        assert shape != pool, m.group(0)
        assert not (shape == stack and op in _MOVES), m.group(0)


GRANITE_BATCH = 32
GRANITE_PAGES = GRANITE_BATCH * MAX_LEN // PAGE
N_PP = MAX_LEN // PAGE


def _granite():
    """granite-4.0-h-micro as the benchmark's configuration file has it
    (40 layers, published widths), with the compiled kernels."""
    from chipbench import run
    cfg = run.model_config(run.load_config("granite-4.0-h-micro"))
    return dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, backend="pallas", interpret=False, route_scope="tick"))


def test_packed_paged_kernel_compiles_for_v5e(one_chip, no_persistent_cache):
    """granite-4.0-h's attention layers: 32 query and 8 KV heads of 64,
    two KV heads to a page row of 128 lanes (``layers.pool_rows``); a
    head of 64 in a row of its own is refused by the TPU compiler."""
    from repro.kernels.paged_attention import paged_attention
    from repro.models.layers import pool_rows
    rows, width = pool_rows(_granite())
    assert (rows, width) == (4, 128)
    bf = jnp.bfloat16
    fn = jax.jit(lambda q, k, v, layer, bt, n: paged_attention(
        q, k, v, layer, bt, n, scale=1 / 64))
    stack = _spec(one_chip, (2, N_PAGES, PAGE, rows, width), bf)
    compiled = fn.lower(
        _spec(one_chip, (BATCH, 32, 64), bf), stack, stack,
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (BATCH, N_PP), jnp.int32),
        _spec(one_chip, (BATCH,), jnp.int32)).compile()
    _check(compiled)


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_layered_served_step_compiles_for_v5e(one_chip, no_persistent_cache,
                                              step):
    """The served tick-scope step of granite-4.0-h-micro as the chat cell
    runs it: full width and depth, batch 32, pages of 16, chunks of 64
    (the periods of its layer pattern are scanned, so the compile barely
    depends on depth).  The decode step reads its attention layers'
    pages in place and updates the Mamba2 state stack without copying
    it, and the whole fits the chip's 16 GB."""
    from repro.models.layers import pool_rows
    cfg = _granite()
    batch, chunk, n_pages = GRANITE_BATCH, 64, GRANITE_PAGES
    place = lambda tree: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        lambda: M.init_model(jax.random.PRNGKey(0), cfg)))
    cache = place(jax.eval_shape(lambda: M.init_cache(
        cfg, batch, MAX_LEN, page_size=PAGE, kv_pages=n_pages)))
    if step == "decode":
        fn = steps.make_decode_step(cfg, with_stats=True)
        args = (_spec(one_chip, (batch, 1), jnp.int32),
                _spec(one_chip, (batch,), jnp.bool_))
    else:
        fn = steps.make_prefill_chunk_step(cfg, with_stats=True)
        args = (_spec(one_chip, (batch, chunk), jnp.int32),
                _spec(one_chip, (batch,), jnp.int32))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    _check(compiled)
    if step == "decode":
        hlo = compiled.as_text()
        rows, width = pool_rows(cfg)
        _check_pages_read_in_place(hlo, 4, rows, width, n_pages)
        state = f"f32[36,{batch},64,64,128]"
        assert not re.search(rf"= {re.escape(state)}\S* copy\(", hlo)
