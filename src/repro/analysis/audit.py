"""Stage 2: the trace auditor.

The linter (stage 1) proves contracts the AST can see; this stage proves
the ones only tracing can: it builds the real engine entrypoints —
``make_dispatch_plan`` / ``execute_dispatch``, ``mcma_dispatch``,
``mcma_dispatch_sharded`` on a mesh, and the decode / prefill-chunk
steps (dense AND paged-KV layouts — 2 page sizes x 2 row masks with
varying block-table contents) — and drives each compiled program across
a capacity ladder, QoS margin settings, residency sets, and row masks,
asserting the three runtime contracts every PR so far has defended ad
hoc:

  TA001  exactly one compile per entrypoint per capacity point: QoS
         margins, residency vectors, tiers, and row masks are TRACED
         inputs — only capacities (shapes) may compile a new program;
  TA002  invoke-stats counters are int32 — a dtype drift (int64 under
         x64, int16 from a careless cast) breaks the psum exactness
         contract and the autotuner's accumulators;
  TA003  no host callbacks inside the traced program — a stray
         ``jax.debug.callback`` / ``pure_callback`` stalls every decode
         tick on a device->host round trip.

Findings use the same ``Finding`` record as the linter, with
``audit:<entrypoint>`` paths, so the CLI and baseline machinery treat
both stages uniformly.  The helpers (``retrace_findings``,
``stats_dtype_findings``, ``callback_findings``) are reusable on any
jitted function — tests use them directly instead of copy-pasting
``fn._cache_size() == 1`` asserts.
"""
from __future__ import annotations

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.analysis.jit_cache import cache_size

# host-callback primitives by jaxpr name (TA003)
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "host_callback_call", "outside_call",
})


# ---------------------------------------------------------------------------
# reusable checks
# ---------------------------------------------------------------------------

def retrace_findings(fn, *, scope: str, path: str = "audit:trace",
                     expected: int = 1) -> list[Finding]:
    """TA001 on an already-exercised jitted ``fn``: its compile cache
    must hold exactly ``expected`` programs.  Silently passes when the
    jax build does not expose a cache counter."""
    n = cache_size(fn)
    if n is None or n == expected:
        return []
    return [Finding(
        rule="TA001", path=path, line=0, scope=scope, detail="retrace",
        message=(f"{scope}: {n} compiled programs where {expected} "
                 "expected — a traced input (margins / residency / tier "
                 "/ row_mask) forced a retrace; only capacities (shapes) "
                 "may compile new programs"))]


def stats_dtype_findings(stats, *, scope: str,
                         path: str = "audit:trace") -> list[Finding]:
    """TA002: every integer-dtype leaf of an invoke-stats pytree must be
    exactly int32 (the psum exactness contract and the autotuner's
    accumulators assume it)."""
    findings = []
    leaves = jax.tree_util.tree_leaves_with_path(
        stats.asdict() if hasattr(stats, "asdict") else stats)
    for keypath, leaf in leaves:
        dt = getattr(leaf, "dtype", None)
        if dt is None or not jnp.issubdtype(dt, jnp.integer):
            continue
        if dt != jnp.int32:
            name = jax.tree_util.keystr(keypath)
            findings.append(Finding(
                rule="TA002", path=path, line=0, scope=scope,
                detail=f"stats-dtype:{name}",
                message=(f"{scope}: stats leaf {name} is {dt}, not int32 "
                         "— integer counters must stay int32 end to end "
                         "(psum exactness, autotune accumulators)")))
    return findings


def _sub_jaxprs(value):
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _walk_eqns(sub)


def callback_findings(fn, args, *, scope: str, kwargs=None,
                      path: str = "audit:trace") -> list[Finding]:
    """TA003: abstractly trace ``fn(*args)`` and walk the jaxpr (and all
    nested call/scan/cond jaxprs) for host-callback primitives."""
    jaxpr = jax.make_jaxpr(fn)(*args, **(kwargs or {}))
    findings = []
    seen = set()
    for eqn in _walk_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMITIVES and name not in seen:
            seen.add(name)
            findings.append(Finding(
                rule="TA003", path=path, line=0, scope=scope,
                detail=f"callback:{name}",
                message=(f"{scope}: traced program contains a {name} "
                         "host callback — every invocation round-trips "
                         "to the host and stalls the decode tick")))
    return findings


# ---------------------------------------------------------------------------
# the audited entrypoints
# ---------------------------------------------------------------------------

# capacity ladder: ≥3 (exact_cap, invoke_cap) points; each is its own
# compilation unit by design (capacities are shapes)
CAPACITY_LADDER = ((64, 32), (48, 16), (32, 8))
MARGIN_SETS = ([8.0, 0.0, -8.0], [0.0, 0.0, 0.0])      # 2 QoS margin vectors
RESIDENCY_SETS = ([4, 1], [2, 5])                      # 2 hot sets, lib=6
_T, _LIB, _D, _DH = 64, 6, 32, 12


def _mk_engine_case(seed: int = 0):
    """Inputs + library-wide router logits + prepadded library stacks,
    mirroring the shapes the library tests pin."""
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (_T, _D), jnp.float32) * 0.5
    router = jax.random.normal(ks[1], (_D, _LIB + 1)) * 0.5
    w1 = jax.random.normal(ks[2], (_LIB, _D, _DH)) * 0.2
    b1 = jax.random.normal(ks[3], (_LIB, _DH)) * 0.1
    w2 = jax.random.normal(ks[4], (_LIB, _DH, _D)) * 0.2
    b2 = jax.random.normal(ks[5], (_LIB, _D)) * 0.1
    wi = jax.random.normal(jax.random.fold_in(ks[0], 7), (_D, 2 * _D)) * 0.1
    wo = jax.random.normal(jax.random.fold_in(ks[0], 8), (2 * _D, _D)) * 0.1
    stacks = ops.prepad_switched_weights(w1, b1, w2, b2)
    return x, x @ router, stacks, (wi, wo)


def _variants():
    """The traced-input grid every compiled program must absorb:
    2 margin vectors x 2 residency sets x 2 row masks, with a mixed
    3-tier vector throughout."""
    tier = jnp.asarray([i % 3 for i in range(_T)], jnp.int32)
    masks = (jnp.ones((_T,), bool),
             jnp.asarray([True] * (_T - 8) + [False] * 8))
    out = []
    for m in MARGIN_SETS:
        for r in RESIDENCY_SETS:
            for mask in masks:
                out.append((tier, jnp.asarray(m, jnp.float32),
                            jnp.asarray(r, jnp.int32), mask))
    return out


def _audit_engine(backend: str) -> list[Finding]:
    """jit(mcma_dispatch) per capacity-ladder point: TA001/TA002/TA003."""
    from repro.runtime import dispatch as D
    x, logits, stacks, (wi, wo) = _mk_engine_case()
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    findings = []
    for exact_cap, invoke_cap in CAPACITY_LADDER:
        scope = f"mcma_dispatch[{backend},cap=({exact_cap},{invoke_cap})]"

        def run(xv, lg, tier, margins, residency, mask):
            return D.mcma_dispatch(
                xv, lg, exact_fn, *stacks, exact_cap=exact_cap,
                invoke_cap=invoke_cap, backend=backend, block_t=16,
                interpret=backend != "xla", weights_prepadded=True,
                row_mask=mask, tier=tier, tier_margins=margins,
                residency=residency)

        fn = jax.jit(run)
        stats = None
        for tier, margins, residency, mask in _variants():
            _, stats = fn(x, logits, tier, margins, residency, mask)
        findings += retrace_findings(fn, scope=scope, path="audit:engine")
        findings += stats_dtype_findings(stats, scope=scope,
                                         path="audit:engine")
        findings += callback_findings(run, (x, logits) + _variants()[0],
                                      scope=scope, path="audit:engine")
    return findings


def _audit_plan_execute(backend: str) -> list[Finding]:
    """The split API: one compiled plan builder + one compiled executor
    absorb every traced-input variant at a fixed capacity point."""
    from repro.runtime import dispatch as D
    x, logits, stacks, (wi, wo) = _mk_engine_case(1)
    exact_fn = lambda xb: jnp.dot(jax.nn.silu(jnp.dot(xb, wi)), wo)
    exact_cap, invoke_cap = CAPACITY_LADDER[1]
    scope_p = f"make_dispatch_plan[{backend}]"
    scope_e = f"execute_dispatch[{backend}]"

    plan_fn = jax.jit(lambda lg, tier, margins, residency, mask:
                      D.make_dispatch_plan(
                          lg, mask, exact_cap=exact_cap,
                          invoke_cap=invoke_cap, backend=backend,
                          block_t=16, tier=tier, tier_margins=margins,
                          residency=residency))
    # a plan built against a residency set executes against the
    # resident-GATHERED stacks (the hot set), exactly as the server does
    from repro.kernels import ops
    exec_fn = jax.jit(lambda plan, xv, residency: D.execute_dispatch(
        plan, xv, exact_fn, *ops.gather_resident_stacks(*stacks, residency),
        interpret=backend != "xla", weights_prepadded=True))
    findings = []
    for tier, margins, residency, mask in _variants():
        plan = plan_fn(logits, tier, margins, residency, mask)
        exec_fn(plan, x, residency)
    findings += retrace_findings(plan_fn, scope=scope_p, path="audit:engine")
    findings += retrace_findings(exec_fn, scope=scope_e, path="audit:engine")
    tier, margins, residency, mask = _variants()[0]
    findings += callback_findings(
        lambda lg, t, m, r, k: D.plan_invoke_stats(
            D.make_dispatch_plan(lg, k, exact_cap=exact_cap,
                                 invoke_cap=invoke_cap, backend=backend,
                                 block_t=16, tier=t, tier_margins=m,
                                 residency=r)).asdict(),
        (logits, tier, margins, residency, mask),
        scope=scope_p, path="audit:engine")
    return findings


def _audit_sharded(backend: str) -> list[Finding]:
    """mcma_dispatch_sharded on a 1-device ("data",) mesh: the shard_map
    wrapper must preserve the zero-retrace contract."""
    import numpy as np
    from repro.runtime import dispatch as D
    x, logits, stacks, (wi, wo) = _mk_engine_case(2)
    exact_fn = lambda p, xb: jnp.dot(jax.nn.silu(jnp.dot(xb, p[0])), p[1])
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    exact_cap, invoke_cap = CAPACITY_LADDER[0]
    scope = f"mcma_dispatch_sharded[{backend}]"

    fn = jax.jit(lambda xv, lg, tier, margins, residency, mask:
                 D.mcma_dispatch_sharded(
                     mesh, xv, lg, exact_fn, (wi, wo), *stacks,
                     exact_cap=exact_cap, invoke_cap=invoke_cap,
                     backend=backend, block_t=16,
                     interpret=backend != "xla",
                     weights_prepadded=True, row_mask=mask, tier=tier,
                     tier_margins=margins, residency=residency))
    stats = None
    for tier, margins, residency, mask in _variants():
        _, stats = fn(x, logits, tier, margins, residency, mask)
    findings = retrace_findings(fn, scope=scope, path="audit:engine")
    findings += stats_dtype_findings(stats, scope=scope, path="audit:engine")
    return findings


def _audit_steps(backend: str) -> list[Finding]:
    """The served entrypoints: one compiled decode step and one compiled
    prefill-chunk step absorb margins, residency swaps, tiers, and row
    masks on the smoke model with a 6-wide library."""
    import dataclasses

    from repro.configs.registry import get_config, smoke_config
    from repro.models import model as M
    from repro.runtime import steps as steps_lib

    base = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(base, approx=dataclasses.replace(
        base.approx, enable=True, library_size=6, backend=backend,
        **(dict(interpret=True, block_t=16) if backend != "xla" else {})))
    b = 4
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    tier = jnp.asarray([0, 1, 2, 1], jnp.int32)
    masks = (jnp.ones((b,), bool), jnp.asarray([True, True, True, False]))

    decode = steps_lib.make_decode_step(cfg, use_mcma_dispatch=True,
                                        with_stats=True)
    chunk = steps_lib.make_prefill_chunk_step(cfg, use_mcma_dispatch=True,
                                              with_stats=True)
    decode_fn, chunk_fn = jax.jit(decode), jax.jit(chunk)
    ctoks = jnp.tile(toks, (1, 4))
    n_valid = jnp.asarray([4, 2, 4, 0], jnp.int32)

    findings, metrics = [], None
    for m in MARGIN_SETS:
        for r in RESIDENCY_SETS:
            for mask in masks:
                margins = jnp.asarray(m, jnp.float32)
                residency = jnp.asarray(r, jnp.int32)
                cache = M.init_cache(cfg, b, 32)
                _, _, metrics = decode_fn(params, cache, toks, mask, tier,
                                          margins, residency)
                cache = M.init_cache(cfg, b, 32)
                chunk_fn(params, cache, ctoks, n_valid, mask, tier,
                         margins, residency)
    findings += retrace_findings(decode_fn, scope=f"decode_step[{backend}]",
                                 path="audit:steps")
    findings += retrace_findings(chunk_fn,
                                 scope=f"prefill_chunk_step[{backend}]",
                                 path="audit:steps")
    findings += stats_dtype_findings(
        {k: v for k, v in metrics.items()
         if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.integer)},
        scope=f"decode_step[{backend}]", path="audit:steps")
    findings += callback_findings(
        decode, (params, M.init_cache(cfg, b, 32), toks, masks[0], tier,
                 jnp.asarray(MARGIN_SETS[0], jnp.float32),
                 jnp.asarray(RESIDENCY_SETS[0], jnp.int32)),
        scope=f"decode_step[{backend}]", path="audit:steps")
    return findings


def _audit_paged_steps(backend: str) -> list[Finding]:
    """The paged-KV serving entrypoints: per page size (its own shape,
    so its own compilation unit) ONE compiled decode step and ONE
    compiled prefill-chunk step absorb every block-table content, slot
    position, and row mask the allocator can produce — page allocation
    and free churn are traced-input changes, never retraces."""
    import dataclasses

    from repro.configs.registry import get_config, smoke_config
    from repro.models import model as M
    from repro.runtime import steps as steps_lib

    base = smoke_config(get_config("internlm2-1.8b"))
    cfg = dataclasses.replace(base, approx=dataclasses.replace(
        base.approx, enable=True, library_size=6, backend=backend,
        **(dict(interpret=True, block_t=16) if backend != "xla" else {})))
    b, max_len = 4, 32
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    toks = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    tier = jnp.asarray([0, 1, 2, 1], jnp.int32)
    masks = (jnp.ones((b,), bool), jnp.asarray([True, True, True, False]))
    margins = jnp.asarray(MARGIN_SETS[0], jnp.float32)
    residency = jnp.asarray(RESIDENCY_SETS[0], jnp.int32)

    ctoks = jnp.tile(toks, (1, 4))
    n_valid = jnp.asarray([4, 2, 4, 0], jnp.int32)

    findings = []
    for page_size in (8, 16):
        assert max_len % page_size == 0, (max_len, page_size)
        n_pp = max_len // page_size
        n_pages = b * n_pp
        # fresh step closures per page size: jax.jit keys its cache on
        # the underlying callable, so re-wrapping ONE closure would count
        # the other page size's (legitimately different-shape) program
        # as a retrace
        decode = steps_lib.make_decode_step(cfg, use_mcma_dispatch=True,
                                            with_stats=True)
        chunk = steps_lib.make_prefill_chunk_step(
            cfg, use_mcma_dispatch=True, with_stats=True)
        # two allocator states: in-order pages vs a scrambled free list
        # with partially-filled rows (holes stay -1)
        ident = jnp.arange(n_pages, dtype=jnp.int32).reshape(b, n_pp)
        perm = jnp.asarray(
            [(7 * k + 3) % n_pages for k in range(n_pages)],
            jnp.int32).reshape(b, n_pp)
        perm = perm.at[:, n_pp // 2:].set(-1) if n_pp > 1 else perm
        tables = (ident, perm)

        decode_fn, chunk_fn = jax.jit(decode), jax.jit(chunk)
        for bt in tables:
            for mask in masks:
                cache = M.init_cache(cfg, b, max_len, page_size=page_size,
                                     kv_pages=n_pages)
                cache = dict(cache, block_table=bt)
                decode_fn(params, cache, toks, mask, tier, margins,
                          residency)
                cache = M.init_cache(cfg, b, max_len, page_size=page_size,
                                     kv_pages=n_pages)
                cache = dict(cache, block_table=bt)
                chunk_fn(params, cache, ctoks, n_valid, mask, tier,
                         margins, residency)
        tag = f"[{backend},P={page_size}]"
        findings += retrace_findings(
            decode_fn, scope=f"paged_decode_step{tag}", path="audit:steps")
        findings += retrace_findings(
            chunk_fn, scope=f"paged_prefill_chunk_step{tag}",
            path="audit:steps")
        findings += callback_findings(
            decode,
            (params, M.init_cache(cfg, b, max_len, page_size=page_size,
                                  kv_pages=n_pages),
             toks, masks[0], tier, margins, residency),
            scope=f"paged_decode_step{tag}", path="audit:steps")
    return findings


def run_audit(*, backends=("xla", "pallas", "pallas_fused"),
              with_steps: bool = True) -> list[Finding]:
    """Trace-audit every engine entrypoint; [] = every contract holds.

    The default sweep covers all three executors — the XLA oracle, the
    unfused Pallas kernel, and the fused-dispatch kernel
    (``pallas_fused``, kernels/fused_dispatch.py) — so the fused
    entrypoint is held to the same one-compile / int32-stats /
    no-callback contracts.  ``backends`` narrows the sweep;
    ``with_steps=False`` skips the (heavier) decode / prefill-chunk
    model steps for quick engine-only runs."""
    jax.config.update("jax_platform_name", "cpu")
    findings: list[Finding] = []
    for be in backends:
        findings += _audit_engine(be)
        findings += _audit_plan_execute(be)
        findings += _audit_sharded(be)
        if with_steps:
            findings += _audit_steps(be)
            findings += _audit_paged_steps(be)
    findings.sort(key=lambda f: (f.path, f.scope, f.rule))
    return findings
