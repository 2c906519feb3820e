"""The program's names for its own work in a profiler trace
(repro/scopes.py): every device scope and kernel name is on the op paths
of the compiled served steps, which is where a trace reads them, and a
request's stamps are on ``time.perf_counter()`` and in order."""
import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.configs.registry import get_config, smoke_config
from repro.models import model as M
from repro.runtime.options import ServeOptions
from repro.runtime.server import DecodeServer, Request

jax.config.update("jax_platform_name", "cpu")

BATCH, MAX_LEN, PAGE, CHUNK = 2, 64, 8, 8


def _server(backend: str = "pallas") -> DecodeServer:
    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, interpret=True))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return DecodeServer(cfg, params, options=ServeOptions(
        batch=BATCH, max_len=MAX_LEN, use_mcma_dispatch=True,
        route_scope="tick", kv_page_size=PAGE, prefill_chunk=CHUNK,
        backend=backend))


def _op_paths(srv: DecodeServer, step: str) -> list[str]:
    """The op path (``op_name`` metadata) of every compiled operation."""
    if step == "decode":
        lowered = srv.decode.lower(srv.params, srv.cache,
                                   jnp.zeros((BATCH, 1), jnp.int32),
                                   jnp.ones((BATCH,), bool))
    else:
        lowered = srv.chunk.lower(srv.params, srv.cache,
                                  jnp.zeros((BATCH, CHUNK), jnp.int32),
                                  jnp.full((BATCH,), CHUNK, jnp.int32))
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


@pytest.mark.parametrize("backend,kernel", [
    ("pallas", scopes.SWITCH_KERNEL),
    ("pallas_fused", scopes.SWITCH_KERNEL_FUSED)])
@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_scope_and_kernel_names_are_in_the_lowered_step(step, backend,
                                                        kernel):
    paths = _op_paths(_server(backend), step)
    # a chunk step samples nothing, so it has no unembed; a decode step
    # under a Pallas backend reads its pages in place, so it gathers
    # nothing and runs the paged attention kernel instead; a dense model
    # has no Mamba2 mixer
    absent = {"chunk": (scopes.UNEMBED,),
              "decode": (scopes.KV_GATHER,)}[step] \
        + (scopes.SSM_MIXER, scopes.SSM_STATE)
    for name in scopes.DEVICE_SCOPES:
        assert any(f"/{name}/" in p for p in paths) != (name in absent), \
            name
    assert any(f"/{kernel}/" in p for p in paths)
    assert any(f"/{scopes.PAGED_ATTENTION}/" in p for p in paths) \
        == (step == "decode")


def _layered_server() -> DecodeServer:
    from repro.configs.base import ApproxConfig, ModelConfig, SSMConfig
    cfg = ModelConfig(
        name="layered", family="layered", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=64, d_ff=128, vocab=512,
        rope_pct=0.0, tie_embeddings=True,
        layer_types=("mamba", "mamba", "attention"),
        ssm=SSMConfig(d_state=16, expand=1, head_dim=32, chunk=8,
                      conv_width=4, conv_bias=True),
        approx=ApproxConfig(enable=True, n_approx=2, d_hidden=32,
                            interpret=True),
        param_dtype="float32", act_dtype="float32", remat=False)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return DecodeServer(cfg, params, options=ServeOptions(
        batch=BATCH, max_len=MAX_LEN, use_mcma_dispatch=True,
        route_scope="tick", kv_page_size=PAGE, prefill_chunk=CHUNK))


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_the_state_scope_nests_inside_the_mixer(step):
    """A layered model's Mamba2 work is under ``ssm_mixer``, and what
    reads or writes its state under ``ssm_mixer/ssm_state``, once each."""
    paths = _op_paths(_layered_server(), step)
    mixer, state = scopes.SSM_MIXER, scopes.SSM_STATE
    assert any(f"/{mixer}/" in p and f"/{state}/" not in p for p in paths)
    assert any(f"/{mixer}/{state}/" in p for p in paths)
    for p in paths:
        parts = p.split("/")
        assert parts.count(mixer) <= 1 and parts.count(state) <= 1, p
        if state in parts:
            assert mixer in parts[:parts.index(state)], p


def test_the_kv_scopes_nest_inside_attention():
    srv = _server()
    for step, kvs in (("decode", (scopes.KV_WRITE,)),
                      ("chunk", (scopes.KV_WRITE, scopes.KV_GATHER))):
        paths = _op_paths(srv, step)
        for kv in kvs:
            assert any(f"/{scopes.ATTENTION}/{kv}/" in p for p in paths), kv


def test_the_paged_kernel_sits_under_attention_outside_the_kv_scopes():
    """Where the trace reader finds it: the kernel's time goes to
    ``attention`` and ``kv_*`` keep only the scatter of the step's K/V."""
    paths = [p for p in _op_paths(_server(), "decode")
             if f"/{scopes.PAGED_ATTENTION}/" in p]
    assert paths
    for p in paths:
        parts = p.split("/")
        assert scopes.ATTENTION in parts
        assert scopes.KV_WRITE not in parts and scopes.KV_GATHER not in parts


def test_request_stamps_are_ordered_on_the_perf_counter():
    srv = _server()
    rng = np.random.default_rng(0)
    t_before = time.perf_counter()
    reqs = [Request(rid=i, prompt=rng.integers(0, 512, n, dtype=np.int32),
                    max_new=3) for i, n in enumerate((5, 19, 11))]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    t_after = time.perf_counter()
    for r in reqs:
        assert r.done and not r.aborted
        assert t_before <= r.arrival_s <= r.admitted_s <= r.first_token_s \
            <= t_after, (r.arrival_s, r.admitted_s, r.first_token_s)
    # three requests, two slots: the last one waited for a slot
    assert max(r.admitted_s - r.arrival_s for r in reqs) > 0
