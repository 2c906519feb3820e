"""Paged KV cache: block-table serving memory vs the dense oracle.

Pins, per the PR's acceptance criteria:
  * paged == dense bit-for-bit greedy tokens — token-by-token AND
    chunked prefill, on one device and on the (data=2, model=4) mesh
    (subprocess with 8 forced virtual devices);
  * page-boundary writes: prompt lengths straddling ``page_size``
    (P-1, P, P+1, 2P+1) land inside the right pages;
  * the allocator's lifecycle: lazy acquisition as ``pos`` crosses page
    boundaries, release on finish AND on abort/strand (``pages_in_use``
    returns to 0, the free list is whole again);
  * a constrained pool (kv_pages < batch * max_len/page_size) defers
    admission (``alloc_failures`` counts the pressure) but still serves
    every request bit-identically — worst-case reservation at admit
    means lazy growth can never deadlock;
  * pool overflow is LOUD: a request that could never be scheduled is a
    submit-time ValueError, and one injected past submit() is aborted at
    admission instead of clamp-corrupting the pool;
  * page allocation/free churn never retraces the decode or chunk step
    (the block table is a same-shape traced leaf refreshed per tick);
  * MCMA dispatch invoke stats are identical to dense (the cache layout
    is invisible to routing);
  * memory: a mixed-length workload's ``kv_bytes_resident`` is strictly
    below the dense worst case when ``max_len`` overshoots the typical
    request (the whole point of paging).
"""
import dataclasses
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jit_cache import assert_zero_retrace
from repro.configs.registry import get_config, smoke_config
from repro.models import model as M
from repro.runtime.options import LibrarySpec, ServeOptions
from repro.runtime.server import DecodeServer, Request

jax.config.update("jax_platform_name", "cpu")

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


def _cfg(**over):
    cfg = smoke_config(get_config("internlm2-1.8b"))
    return dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, exact_frac=1.0, invoke_frac=1.0, **over))


_PARAMS = {}


def _params(cfg):
    key = (cfg.approx.exact_frac, cfg.approx.library_size)
    if key not in _PARAMS:
        _PARAMS[key] = M.init_model(jax.random.PRNGKey(0), cfg)
    return _PARAMS[key]


def _boundary_requests(vocab, seed=0, max_new=6):
    """Prompt lengths straddling page_size=8: P-1, P, P+1, 2P+1, plus
    short/long fillers so slots churn through alloc/free cycles."""
    rng = np.random.default_rng(seed)
    lens = (7, 8, 9, 17, 3, 25, 12, 31, 5)
    return [Request(rid=i, prompt=rng.integers(1, vocab, n).astype(np.int32),
                    max_new=max_new)
            for i, n in enumerate(lens)]


def _serve(cfg, reqs, **kw):
    base = dict(batch=4, max_len=64, admission="fifo")
    base.update(kw)
    srv = DecodeServer(cfg, _params(cfg), options=ServeOptions(**base))
    for r in reqs:
        srv.submit(r)
    stats = srv.run_until_drained(2000)
    return srv, stats


# ---------------------------------------------------------------------------
# cache construction + slot reset units
# ---------------------------------------------------------------------------

def test_init_cache_paged_layout():
    cfg = _cfg()
    L = cfg.n_layers
    kh = cfg.n_kv_heads or cfg.n_heads
    hd = cfg.hd
    c = M.init_cache(cfg, 4, 64, page_size=8, kv_pages=10)
    assert c["k"].shape == (L, 10, 8, kh, hd)
    assert c["v"].shape == c["k"].shape
    assert c["block_table"].shape == (4, 8)         # max_len // page_size
    assert c["block_table"].dtype == jnp.int32
    assert (np.asarray(c["block_table"]) == -1).all()
    assert c["pos"].shape == (4,)
    with pytest.raises(AssertionError):
        M.init_cache(cfg, 4, 64, page_size=7, kv_pages=10)   # 7 ∤ 64


def test_reset_slot_clears_block_table_row_only():
    cfg = _cfg()
    c = M.init_cache(cfg, 3, 32, page_size=8, kv_pages=6)
    c = dict(c)
    c["block_table"] = jnp.asarray(
        [[0, 1, -1, -1], [2, 3, 4, -1], [5, -1, -1, -1]], jnp.int32)
    c["k"] = c["k"] + 1.0                       # pool contents are SHARED
    c2 = M.reset_slot(cfg, c, 1)
    bt = np.asarray(c2["block_table"])
    assert (bt[1] == -1).all()                  # the reset slot's row
    assert (bt[0] == [0, 1, -1, -1]).all()      # neighbours untouched
    assert (bt[2] == [5, -1, -1, -1]).all()
    # pools must NOT be zeroed: other slots' pages live there
    np.testing.assert_array_equal(np.asarray(c2["k"]), np.asarray(c["k"]))
    assert int(c2["pos"][1]) == 0


# ---------------------------------------------------------------------------
# bit-exactness vs the dense oracle (single device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 8], ids=["token", "chunked"])
def test_paged_matches_dense_bitexact(chunk):
    """Page-boundary-straddling prompts, both prefill modes: identical
    greedy tokens, every page returned at drain."""
    cfg = _cfg()
    a = _boundary_requests(cfg.vocab)
    b = _boundary_requests(cfg.vocab)
    _, st_d = _serve(cfg, a, prefill_chunk=chunk)
    srv_p, st_p = _serve(cfg, b, prefill_chunk=chunk, kv_page_size=8)
    assert all(r.done and not r.aborted for r in a + b)
    for ra, rb in zip(a, b):
        assert ra.out == rb.out, (ra.rid, ra.out, rb.out)
    assert st_p["pages_in_use"] == 0
    assert len(srv_p._free_pages) == srv_p.n_pages
    assert st_p["page_hwm"] > 0
    assert st_p["kv_bytes_resident"] <= st_d["kv_bytes_resident"]


def test_paged_page_sizes_agree():
    """Two page sizes and the dense oracle all sample the same tokens —
    the layout is invisible to the math."""
    cfg = _cfg()
    outs = []
    for kw in (dict(), dict(kv_page_size=8), dict(kv_page_size=16)):
        reqs = _boundary_requests(cfg.vocab)
        _serve(cfg, reqs, prefill_chunk=8, **kw)
        outs.append([r.out for r in reqs])
    assert outs[0] == outs[1] == outs[2]


def test_paged_mcma_dispatch_stats_identical():
    """Routing/invocation accounting cannot see the cache layout."""
    cfg = _cfg()
    a = _boundary_requests(cfg.vocab)
    b = _boundary_requests(cfg.vocab)
    kw = dict(prefill_chunk=8, use_mcma_dispatch=True, route_scope="tick",
              backend="xla")
    _, st_d = _serve(cfg, a, **kw)
    _, st_p = _serve(cfg, b, kv_page_size=8, **kw)
    for ra, rb in zip(a, b):
        assert ra.out == rb.out
    assert st_d["invocation_rate"] == st_p["invocation_rate"]
    assert st_d["routed_per_class"] == st_p["routed_per_class"]
    assert st_d["prefill_invocation_rate"] == st_p["prefill_invocation_rate"]


def test_paged_library_residency_matches_dense():
    """Paged cache under the approximator-library engine: swaps and
    pages churn independently, tokens stay the dense oracle's."""
    cfg = _cfg(library_size=6)
    lib = LibrarySpec(library_size=6, n_resident=2, observe_window=2,
                      cooldown=2)
    a = _boundary_requests(cfg.vocab)
    b = _boundary_requests(cfg.vocab)
    kw = dict(prefill_chunk=4, use_mcma_dispatch=True, backend="xla",
              library=lib)
    _, st_d = _serve(cfg, a, **kw)
    _, st_p = _serve(cfg, b, kv_page_size=8, **kw)
    assert all(r.done for r in a + b)
    for ra, rb in zip(a, b):
        assert ra.out == rb.out, (ra.rid, ra.out, rb.out)
    assert st_d["lib_routed_per_class"] == st_p["lib_routed_per_class"]


# ---------------------------------------------------------------------------
# allocator lifecycle: constrained pool, exhaustion, abort/strand release
# ---------------------------------------------------------------------------

def test_constrained_pool_defers_admission_but_serves_all():
    """kv_pages far below batch * pages_per_slot: admission head-of-line
    blocks under pool pressure (alloc_failures counts it), every request
    still finishes with the dense oracle's tokens, and the pool drains
    back to empty."""
    cfg = _cfg()
    a = _boundary_requests(cfg.vocab)
    b = _boundary_requests(cfg.vocab)
    _, _ = _serve(cfg, a, prefill_chunk=8)
    srv, st = _serve(cfg, b, prefill_chunk=8, kv_page_size=8, kv_pages=12)
    assert all(r.done and not r.aborted for r in b)
    for ra, rb in zip(a, b):
        assert ra.out == rb.out
    assert st["alloc_failures"] > 0          # the pool really was tight
    assert st["page_hwm"] <= 12
    assert st["pages_in_use"] == 0
    assert sorted(srv._free_pages) == list(range(srv.n_pages))


def test_pool_overflow_rejected_at_submit():
    cfg = _cfg()
    srv = DecodeServer(cfg, _params(cfg), options=ServeOptions(
        batch=2, max_len=64, prefill_chunk=8, kv_page_size=8, kv_pages=4))
    # needs ceil((30+6)/8) = 5 pages > 4 in the whole pool
    with pytest.raises(ValueError, match="KV pages"):
        srv.submit(Request(rid=0, prompt=np.ones(30, np.int32), max_new=6))
    assert not srv.queue
    # the boundary case fits exactly (4 pages) and is served
    r = Request(rid=1, prompt=np.ones(26, np.int32), max_new=6)
    srv.submit(r)
    st = srv.run_until_drained(500)
    assert r.done and len(r.out) == 6
    assert st["pages_in_use"] == 0


def test_injected_never_fits_request_aborted_at_admit():
    """A request injected past submit() validation must not wedge the
    admission loop: it is aborted when picked, its (zero) pages freed,
    and the queue keeps draining."""
    cfg = _cfg()
    srv = DecodeServer(cfg, _params(cfg), options=ServeOptions(
        batch=1, max_len=64, prefill_chunk=8, kv_page_size=8, kv_pages=4))
    bad = Request(rid=0, prompt=np.ones(30, np.int32), max_new=6)
    good = Request(rid=1, prompt=np.ones(5, np.int32), max_new=4)
    srv.queue.append(bad)                    # straight past validation
    srv.submit(good)
    st = srv.run_until_drained(500)
    assert bad.aborted and not bad.out
    assert good.done and len(good.out) == 4
    assert st["pages_in_use"] == 0
    assert st["undrained_queued"] == st["undrained_inflight"] == 0


def test_pages_released_on_abort_and_strand():
    """The free-on-abort satellite: an unservable injected prompt is
    released mid-flight, and requests stranded at max_ticks exhaustion
    hand their pages back in run_until_drained — pages_in_use returns to
    0 either way (the dense window merely lingered; a page leak would
    starve the pool)."""
    cfg = _cfg()
    # (a) injected overflow aborts AFTER admission (prompt fits pages but
    # not max_len): the tick pre-write abort path must release
    srv = DecodeServer(cfg, _params(cfg), options=ServeOptions(
        batch=1, max_len=32, prefill_chunk=0, kv_page_size=8))
    bad = Request(rid=0, prompt=np.ones(40, np.int32), max_new=4)
    good = Request(rid=1, prompt=np.ones(5, np.int32), max_new=4)
    srv.queue.append(bad)
    srv.submit(good)
    st = srv.run_until_drained(500)
    assert bad.aborted and good.done
    assert st["pages_in_use"] == 0
    assert sorted(srv._free_pages) == list(range(srv.n_pages))
    # (b) stranded at tick exhaustion: pages still come back
    srv2 = DecodeServer(cfg, _params(cfg), options=ServeOptions(
        batch=1, max_len=32, prefill_chunk=0, kv_page_size=8))
    r = Request(rid=0, prompt=np.ones(10, np.int32), max_new=20)
    srv2.submit(r)
    st2 = srv2.run_until_drained(3)          # nowhere near enough ticks
    assert r.aborted and not r.done
    assert st2["undrained_inflight"] == 1
    assert st2["pages_in_use"] == 0
    assert sorted(srv2._free_pages) == list(range(srv2.n_pages))


# ---------------------------------------------------------------------------
# zero-retrace across allocation churn
# ---------------------------------------------------------------------------

def test_alloc_free_churn_never_retraces():
    """9 requests through 4 slots = multiple alloc/free cycles per slot
    with ever-different block-table contents; the decode and chunk steps
    must each have compiled exactly one program."""
    cfg = _cfg()
    reqs = _boundary_requests(cfg.vocab)
    srv, st = _serve(cfg, reqs, prefill_chunk=8, kv_page_size=8,
                     kv_pages=12)
    assert all(r.done for r in reqs)
    assert st["ticks"] > 10
    assert_zero_retrace(srv.decode, "page allocation/free churn")
    assert_zero_retrace(srv.chunk, "page allocation/free churn (chunk)")


# ---------------------------------------------------------------------------
# the (data=2, model=4) mesh, via subprocess (8 forced virtual devices)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import dataclasses, json
    import numpy as np
    import jax
    jax.config.update("jax_platform_name", "cpu")
    from repro.configs.registry import get_config, smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M
    from repro.runtime.options import ServeOptions
    from repro.runtime.server import DecodeServer, Request

    cfg = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
        cfg.approx, enable=True, exact_frac=1.0, invoke_frac=1.0))
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    mesh = make_host_mesh(data=2, model=4)
    out = {}
    for page in (0, 8):
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i,
                        prompt=rng.integers(1, cfg.vocab, n)
                        .astype(np.int32), max_new=4)
                for i, n in enumerate((7, 8, 9, 17, 25))]
        srv = DecodeServer(cfg, params, options=ServeOptions(
            batch=2, max_len=64, use_mcma_dispatch=True,
            route_scope="tick", mesh=mesh, prefill_chunk=8,
            admission="fifo", kv_page_size=page))
        for r in reqs:
            srv.submit(r)
        stats = srv.run_until_drained(500)
        out[str(page)] = {
            "tokens": {r.rid: r.out for r in reqs},
            "done": all(r.done for r in reqs),
            "pages_in_use": stats.get("pages_in_use"),
            "invocation_rate": stats["invocation_rate"],
        }
    print("RESULT" + json.dumps(out))
""")


def test_paged_matches_dense_on_mesh_subprocess():
    r = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.split("RESULT")[1])
    assert out["0"]["done"] and out["8"]["done"]
    assert out["0"]["tokens"] == out["8"]["tokens"]
    assert out["8"]["pages_in_use"] == 0
    assert out["0"]["invocation_rate"] == out["8"]["invocation_rate"]


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8); covered by the CI multidevice leg")


@needs_8_devices
def test_paged_matches_dense_on_mesh_inprocess():
    """CI multidevice leg: same equality without the subprocess."""
    from repro.launch.mesh import make_host_mesh
    cfg = _cfg()
    mesh = make_host_mesh(data=2, model=4)
    a = _boundary_requests(cfg.vocab, max_new=4)
    b = _boundary_requests(cfg.vocab, max_new=4)
    _, st_d = _serve(cfg, a, batch=2, prefill_chunk=8, mesh=mesh,
                     use_mcma_dispatch=True, route_scope="tick")
    srv_p, st_p = _serve(cfg, b, batch=2, prefill_chunk=8, mesh=mesh,
                         use_mcma_dispatch=True, route_scope="tick",
                         kv_page_size=8)
    assert all(r.done for r in a + b)
    for ra, rb in zip(a, b):
        assert ra.out == rb.out, (ra.rid, ra.out, rb.out)
    assert st_p["pages_in_use"] == 0
    assert st_d["invocation_rate"] == st_p["invocation_rate"]
