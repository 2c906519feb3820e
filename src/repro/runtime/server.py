"""Batched decode server loop (the inference-side driver).

Continuous batching over a fixed-size slot table (``batch`` concurrent
sequences): finished sequences (EOS or max_len) free their slot and queued
requests fill freed slots each tick, admitted by a cost model (prompt
length x QoS tier, with aging) rather than FIFO.

Prefill is CHUNKED (``prefill_chunk`` = S > 0): a slot consumes its prompt
S tokens per tick through a compiled (B, S) prefill-chunk step
(steps.make_prefill_chunk_step) that writes the same decode cache layout,
and the scheduler interleaves prefill chunks with decode ticks so in-flight
decodes keep streaming while new prompts load.  Only the FINAL prompt
token goes through the decode step — so the first sampled token, and every
decode-phase computation after it, is bit-identical to token-by-token
serving (``prefill_chunk=0``, the pre-chunking reference mode, still
available and used by the bench as the TTFT baseline).  Chunking needs the
uniform or the layered family with full attention; xLSTM/zamba2/
sliding-window models fall back to token-by-token feeding automatically.
A layered model's Mamba2 layers keep a fixed-size recurrent state per
slot beside the KV cache; a slot's reset zeroes it.

The ``max_len`` contract: positions are absolute, never recycled.
``submit()`` enforces ``len(prompt) + max_new <= max_len`` loudly (or
trims the prompt's HEAD under ``overflow="trim"``), and the tick loop
aborts — never clamp-writes — any slot whose prompt cannot fit.

The KV cache is dense ``(batch, max_len)`` by default, or PAGED when
``ServeOptions.kv_page_size > 0``: a fixed pool of ``kv_pages`` blocks
of ``kv_page_size`` tokens plus a per-slot block table (a TRACED leaf of
the cache pytree — page allocation changes never retrace).  The
host-side allocator here hands pages to slots lazily as their ``pos``
crosses page boundaries, and takes every page back the moment a request
finishes, aborts, or strands, so resident KV memory tracks tokens
actually HELD instead of batch x max_len worst case.  Admission then
reserves each request's worst-case page count up front (so in-flight
growth can never deadlock the pool) and the cost model prices pages
instead of raw prompt length.  Paged serving is bit-identical to the
dense oracle (docs/serving.md).

This is deliberately the same decode_step the dry-run lowers — the serving
path at scale IS the lowered cell, just driven by this loop.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import scopes
from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.runtime import steps as steps_lib
from repro.runtime.options import LibrarySpec, ServeOptions


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,) int32
    max_new: int = 32
    # per-request QoS: the requested relative-error bound (validated and
    # quantized onto the server's tier table at submit time), or a tier
    # index directly.  None = the deployment's default tier.
    error_bound: float | None = None
    tier: int | None = None
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # set True when the server gave up on the request instead of finishing
    # it: stranded at run_until_drained(max_ticks) exhaustion, or an
    # unservable prompt that bypassed submit() validation.  ``done`` stays
    # False for stranded requests — aborted is the explicit signal.
    aborted: bool = False
    # latency bookkeeping.  arrival_* are stamped at submit(), admitted_s
    # when the request takes a slot, first_token_* at the tick that
    # sampled its first output token.  Ticks count BOTH phases, so a
    # prefill-heavy schedule shows up in TTFT-in-ticks directly.  The *_s
    # stamps are time.perf_counter() seconds: differences only, on the
    # clock a caller timing its own submits and ticks uses too.
    arrival_tick: int | None = None
    first_token_tick: int | None = None
    arrival_s: float | None = None
    admitted_s: float | None = None
    first_token_s: float | None = None


@dataclasses.dataclass
class DrainStats:
    """Typed ``run_until_drained`` summary (was an ad-hoc dict).

    Optional fields stay ``None`` when their feature was off for the run
    (no MCMA dispatch -> no ``invocation_rate``; no QoS -> no
    ``per_tier``; ...).  The mapping protocol preserves every historic
    dict-style call site: ``stats["ticks"]``, ``"invocation_rate" in
    stats`` (``None`` counts as absent, exactly like the old dict's
    missing key), ``stats.get(...)``, and ``stats["anything"] = v``
    (unknown keys land in ``extras`` — bench_serve stamps
    ``replay_wall_s`` that way).  ``asdict()`` flattens to the old dict
    shape for CSV/JSON writers, skipping ``None`` fields.
    """

    ticks: int = 0
    wall_s: float = 0.0
    undrained_queued: int = 0
    undrained_inflight: int = 0
    prefill_ticks: int = 0
    prefill_tokens: int = 0
    invocation_rate: Optional[float] = None
    prefill_invocation_rate: Optional[float] = None
    dropped_rows: Optional[float] = None
    routed_per_class: Optional[list] = None
    dispatched_per_class: Optional[list] = None
    dropped_frac: Optional[float] = None
    served_invocation_rate: Optional[float] = None
    per_tier: Optional[list] = None
    autotune: Optional[dict] = None
    # approximator-library residency (LibrarySpec deployments only)
    lib_routed_per_class: Optional[list] = None   # (library_size + 1,)
    off_set_exact_rows: Optional[float] = None    # routed off-set, served exact
    residency: Optional[dict] = None              # ResidencyController.summary()
    # paged KV cache (kv_page_size > 0 deployments only)
    pages_in_use: Optional[int] = None            # pages held at drain end
    page_hwm: Optional[int] = None                # peak pages held
    alloc_failures: Optional[int] = None          # admission deferrals (pool
                                                  # pressure) + pool-exhaust
                                                  # aborts
    page_util: Optional[float] = None             # held tokens / (held pages
                                                  # x page_size), tick-meaned
    # peak resident KV bytes: dense reports its (constant) worst case, a
    # paged run reports page_hwm pages' worth — the bench's memory column
    kv_bytes_resident: Optional[int] = None
    extras: dict = dataclasses.field(default_factory=dict)

    def __getitem__(self, k):
        if k in self.extras:
            return self.extras[k]
        if k in _DRAIN_FIELDS:
            v = getattr(self, k)
            if v is not None:
                return v
        raise KeyError(k)

    def __setitem__(self, k, v):
        if k in _DRAIN_FIELDS and k != "extras":
            setattr(self, k, v)
        else:
            self.extras[k] = v

    def __contains__(self, k):
        return k in self.extras or (
            k in _DRAIN_FIELDS and getattr(self, k) is not None)

    def __iter__(self):
        # iterate present keys like the dict this replaced (without
        # this, the legacy __getitem__ iteration protocol probes s[0])
        return iter(self.asdict())

    def get(self, k, default=None):
        try:
            return self[k]
        except KeyError:
            return default

    def asdict(self) -> dict:
        d = {f: getattr(self, f) for f in _DRAIN_FIELDS
             if f != "extras" and getattr(self, f) is not None}
        d.update(self.extras)
        return d

    def keys(self):
        return self.asdict().keys()

    def items(self):
        return self.asdict().items()


_DRAIN_FIELDS = tuple(f.name for f in dataclasses.fields(DrainStats))

# a host span of the tick's phases (repro/scopes.py); records nothing
# unless a profiler is running
_span = jax.profiler.TraceAnnotation

# the historic DecodeServer.__init__ keyword surface (PRs 1-6) — every
# name is also a ServeOptions field, so the shim is a dataclasses.replace
_LEGACY_SERVE_KWARGS = tuple(
    f.name for f in dataclasses.fields(ServeOptions) if f.name != "library")


class DecodeServer:
    def __init__(self, cfg: ModelConfig, params, *,
                 options: ServeOptions | None = None, **legacy):
        """``DecodeServer(cfg, params, options=ServeOptions(...))`` is the
        canonical constructor — ``ServeOptions`` (runtime/options.py) is
        the only way new serve-time state enters the server.

        The historic kwarg form (``DecodeServer(cfg, params, batch=8,
        use_mcma_dispatch=True, ...)``, PRs 1-6) still works: the kwargs
        fold into the ServeOptions via ``dataclasses.replace`` under ONE
        ``DeprecationWarning``, so legacy and options-style construction
        are bit-identical (tests/test_serve_options.py pins it)."""
        if legacy:
            unknown = sorted(set(legacy) - set(_LEGACY_SERVE_KWARGS))
            if unknown:
                raise TypeError(
                    f"DecodeServer: unknown kwargs {unknown} — serve-time "
                    "state enters via options=ServeOptions(...) "
                    f"(legacy kwargs: {sorted(_LEGACY_SERVE_KWARGS)})")
            warnings.warn(
                "DecodeServer(cfg, params, **kwargs) is deprecated — pass "
                "options=ServeOptions(...) (runtime/options.py); the "
                "kwargs were folded into one for you",
                DeprecationWarning, stacklevel=2)
            options = dataclasses.replace(options or ServeOptions(),
                                          **legacy)
        o = self.options = options if options is not None else ServeOptions()
        batch, max_len, eos = o.batch, o.max_len, o.eos
        greedy, seed = o.greedy, o.seed
        use_mcma_dispatch, mesh = o.use_mcma_dispatch, o.mesh
        autotune, drop_budget = o.autotune, o.drop_budget
        autotune_kwargs, route_scope = o.autotune_kwargs, o.route_scope
        qos_tiers, qos_app = o.qos_tiers, o.qos_app
        qos_margin_scale = o.qos_margin_scale
        prefill_chunk, admission = o.prefill_chunk, o.admission
        overflow, aging, backend = o.overflow, o.aging, o.backend
        self.cfg, self.params = cfg, params
        self.batch, self.max_len, self.eos = batch, max_len, eos
        # qos_tiers: per-request error-bound tiers.  True -> the default
        # (tight, trained, loose) table bracketing the config's (or the
        # registry app's) error bound; a tuple of ascending bounds -> that
        # table.  Each tier maps to an exact-logit router margin
        # (autotune.margins_from_bounds) that is a TRACED input of the one
        # compiled decode step — mixing tiers in a batch, or recalibrating
        # margins, never retraces.  ``qos_app`` names an apps/registry.py
        # app whose quality.py bound anchors the table and the submit-time
        # validation.
        self.tier_bounds = None
        self.qos_app = None
        if qos_app is not None:
            from repro.apps.registry import get_app
            self.qos_app = get_app(qos_app)
            if qos_tiers is None:
                qos_tiers = True
        if qos_tiers:
            from repro.runtime import autotune as at
            assert use_mcma_dispatch, \
                "per-request QoS tiers route through the dispatch engine; " \
                "needs use_mcma_dispatch"
            base = self.qos_app.error_bound if self.qos_app is not None \
                else cfg.approx.error_bound
            if qos_tiers is True:
                qos_tiers = cfg.approx.tier_bounds \
                    or at.default_tier_bounds(base)
            self.tier_bounds = tuple(sorted(float(b) for b in qos_tiers))
            assert self.tier_bounds[0] > 0, self.tier_bounds
            self.tier_margins = np.asarray(
                at.margins_from_bounds(self.tier_bounds, base,
                                       scale=qos_margin_scale), np.float32)
            # requests without a bound serve at the tier closest to the
            # bound the router was trained at
            self.default_tier = int(np.argmin(
                [abs(b - base) for b in self.tier_bounds]))
            cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
                cfg.approx, n_tiers=len(self.tier_bounds),
                tier_bounds=self.tier_bounds,
                tier_margins=tuple(float(m) for m in self.tier_margins)))
            self.cfg = cfg
        # library: approximator-library residency (runtime/options.
        # LibrarySpec).  The checkpoint's full library stays in params
        # (stacks sized by cfg.approx.n_live); the SERVING n_approx
        # becomes the spec's resident-slot count, so capacities, the
        # dispatch plan, and the autotune ladder are all per-slot.  Each
        # tick feeds the current residency vector — a TRACED input — into
        # the compiled step (kernels/ops.gather_resident_stacks picks the
        # resident rows), and the ResidencyController promotes/demotes
        # library classes from the served lib_counts EMA: a swap is a new
        # vector through the same compiled program, zero retraces.
        self.library = o.library
        self.residency_controller = None
        self.residency = None
        if self.library is not None:
            from repro.runtime import autotune as at
            spec = self.library
            assert use_mcma_dispatch, \
                "library residency routes through the dispatch engine; " \
                "needs use_mcma_dispatch"
            assert cfg.approx.n_live == spec.library_size, (
                f"LibrarySpec.library_size={spec.library_size} must equal "
                f"the checkpoint's trained approximator count "
                f"(cfg.approx.n_live={cfg.approx.n_live})")
            assert not cfg.approx.invoke_fracs \
                or len(cfg.approx.invoke_fracs) == spec.n_resident, (
                    "per-class invoke_fracs are per resident SLOT "
                    f"(need {spec.n_resident}, got "
                    f"{len(cfg.approx.invoke_fracs)})")
            cfg = dataclasses.replace(cfg, approx=dataclasses.replace(
                cfg.approx, n_approx=spec.n_resident,
                library_size=spec.library_size))
            self.cfg = cfg
            self.residency_controller = at.ResidencyController(spec)
            self.residency = np.asarray(spec.initial_residency(), np.int32)
        # route_scope: "tick" routes once per decode tick (one DispatchPlan
        # from the tick-router head, reused by every layer of the scan) —
        # the per-tick metrics the server (and the autotune controller)
        # observe are then the single tick-level dispatch decision rather
        # than a mean of L per-layer ones.  None honors the config.
        self.route_scope = route_scope
        # use_mcma_dispatch: decode ticks run the ApproxFFN through the
        # MCMA Pallas weight-switch engine (runtime/dispatch.py) and the
        # server accumulates the invocation rate, weighting each tick by
        # its active-slot count.  Every tick passes the active-slot mask
        # into the decode step, so free slots (fed token 0) are excluded
        # from the router, the capacity dispatch, and every invoke stat —
        # the rates are exact even on a mostly-idle slot table.
        # ``backend`` overrides the dispatch engine ("pallas" default,
        # "pallas_fused" = the gather/scatter-fused kernel, "xla" = the
        # oracle the benches gate both kernels against).
        self.use_mcma_dispatch = use_mcma_dispatch
        self.backend = backend
        # mesh: distributed deployment.  Params/cache are sharded by the
        # declarative rules (sharding/rules.py) and every decode step is
        # traced under steps.serve_mesh_context, so the serve-mode FFNs run
        # their shard_map-native dispatch (the MCMA engine per data shard,
        # invoke_stats psum-reduced to global totals).  The mesh's
        # data-axis size must divide ``batch`` for the manual path to
        # engage.
        self.mesh = mesh
        # prefill_chunk: S > 0 turns on chunked prefill — prompts load S
        # tokens per prefill tick through the (B, S) chunk step, leaving
        # the final prompt token for the decode step (bit-exact first
        # sample).  0 = token-by-token reference mode.  The xLSTM and
        # zamba2 recurrences and sliding-window ring buffers cannot
        # address the cache positionally and silently fall back; the
        # layered family's Mamba2 layers chunk from each slot's state.
        topo = M.topology(cfg)
        self.chunkable = topo.kind in ("uniform", "layered") \
            and not cfg.sliding_window
        self.prefill_chunk = int(prefill_chunk) if self.chunkable else 0
        assert self.prefill_chunk >= 0, prefill_chunk
        # admission: "cost" (default) admits the cheapest queued request
        # into each freed slot — cost = prompt_len x tier multiplier -
        # aging x queue-age-in-ticks.  Longer prompts and TIGHTER tiers
        # cost more (a tight tier consumes more exact-FFN capacity per
        # token), so short/loose work is not stuck behind a long premium
        # prompt; the aging term guarantees no starvation (any request's
        # cost eventually goes negative).  Ties break FIFO.  "fifo" keeps
        # strict arrival order.
        if admission not in ("cost", "fifo"):
            raise ValueError(f"unknown admission policy: {admission!r} "
                             "(expected 'cost' or 'fifo')")
        self.admission = admission
        self.aging = float(aging)
        # overflow: submit()-time policy for prompts that cannot fit the
        # (batch, max_len) cache next to their max_new budget.  "reject"
        # (default) raises; "trim" keeps the LAST max_len - max_new prompt
        # tokens (the recency-biased truncation every fixed-window server
        # ends up with) and serves the request.
        if overflow not in ("reject", "trim"):
            raise ValueError(f"unknown overflow policy: {overflow!r} "
                             "(expected 'reject' or 'trim')")
        self.overflow = overflow
        # paged KV cache (kv_page_size > 0): k/v become per-layer pools of
        # kv_pages blocks and this host-side allocator owns the per-slot
        # block table.  Pages are acquired lazily as a slot's pos crosses
        # a page boundary and released the moment its request finishes,
        # aborts, or strands; admission reserves each request's worst-case
        # ceil((prompt + max_new) / page_size) pages up front, so the lazy
        # growth below can never run the pool dry mid-flight.
        # kv_page_size=0 keeps the dense layout — the bit-exact oracle
        # every paged deployment is pinned against.
        self.page_size = int(o.kv_page_size)
        self.n_pages = 0
        if self.page_size:
            assert self.chunkable, (
                "paged KV caches need the uniform or layered family with "
                f"full attention (got family={cfg.family!r}, "
                f"sliding_window={cfg.sliding_window})")
            assert max_len % self.page_size == 0, (
                f"kv_page_size={self.page_size} must divide "
                f"max_len={max_len} — the gathered page view must keep "
                "the dense reduction shape for bit-exactness")
            self.pages_per_slot = max_len // self.page_size
            self.n_pages = int(o.kv_pages) or batch * self.pages_per_slot
            assert self.n_pages >= 1, o.kv_pages
            self._free_pages = list(range(self.n_pages))
            self._slot_pages: list[list[int]] = [[] for _ in range(batch)]
            self._bt = np.full((batch, self.pages_per_slot), -1, np.int32)
            self._reserved = [0] * batch      # worst-case pages per slot
            self._reserved_total = 0
            self._pos_host = np.zeros((batch,), np.int64)
            self._held_token_ticks = 0        # sum over ticks of held tokens
            self._held_page_ticks = 0         # sum over ticks of held pages
        self.pages_in_use = 0
        self.page_hwm = 0
        self.alloc_failures = 0
        # autotune: online capacity adaptation (runtime/autotune.py).
        # True -> the default ladder around cfg's static operating point;
        # a sequence of OperatingPoints -> that ladder.  One decode step
        # per rung is compiled lazily on first use; the controller picks
        # the rung per tick from the served global invoke_stats, targeting
        # ``drop_budget`` dropped-row fraction at minimum capacity.
        self.controller = None
        if autotune:
            from repro.runtime import autotune as at
            assert use_mcma_dispatch, \
                "autotune consumes invoke_stats; needs use_mcma_dispatch"
            ladder = at.default_ladder(cfg) if autotune is True \
                else tuple(autotune)
            shards = self._dp_shards()
            assert batch % shards == 0, (batch, shards)
            n = cfg.approx.n_approx
            caps_fn = lambda pt: at.point_caps(pt, batch // shards, n,
                                               n_shards=shards)
            # cold-start at the configured static operating point when the
            # ladder contains it (the controller then only MOVES once the
            # served stats justify it), else at the cheapest rung
            base = at.OperatingPoint(cfg.approx.exact_frac,
                                     cfg.approx.invoke_frac,
                                     cfg.approx.shard_slack)
            kw = dict(autotune_kwargs or {})
            if "start" not in kw and base in ladder:
                kw["start"] = ladder.index(base)
            self.controller = at.CapacityController(
                ladder, caps_fn, drop_budget=drop_budget, **kw)
        self._steps = {}             # ladder index -> jitted decode step
        self._chunk_steps = {}       # ladder index -> jitted chunk step
        self.decode = self._make_step(None)
        self.chunk = self._make_chunk_step(None) if self.prefill_chunk \
            else None
        self.invocation_sum = 0.0    # active-slot-weighted invocation sum
        self.active_sum = 0          # total active slots over all ticks
        self.dropped_sum = 0.0       # layer-mean dropped rows over ticks
        self.dispatched_sum = None   # (n+1,) layer-mean dispatched rows
        self.routed_sum = None       # (n+1,) layer-mean routed rows
        self.routed_history = []     # per-tick (n+1,) routed counts — the
                                     # ladder_from_counts signal; bounded
                                     # to the most recent window so a
                                     # long-lived server never grows
                                     # memory linearly in ticks
        self.routed_history_cap = 4096
        self.tier_routed_sum = None      # (n_tiers, n+1) per-tier routed
        self.tier_dispatched_sum = None  # (n_tiers, n+1) per-tier served
        self.lib_routed_sum = None       # (library_size+1,) full-library
                                         # routed demand (decode phase)
        self.off_set_sum = 0.0           # rows routed to off-set library
                                         # classes (served on the exact path)
        # prefill-phase dispatch stats accumulate SEPARATELY: the
        # invocation rate, the autotune controller, routed_history and the
        # QoS ledger are decode-phase signals (the paper's metric is the
        # per-invocation decode rate) — mixing prompt-chunk rows in would
        # shift all of them with load shape.
        self.prefill_invocation_sum = 0.0   # token-weighted, chunk ticks
        self.prefill_tokens = 0             # real prompt tokens chunked
        self.prefill_ticks = 0
        # bounded per-tick trace: (phase, tokens processed, invocation or
        # None) — the decode-phase stat-equality tests replay it
        self.tick_log: list[tuple] = []
        self.cache = M.init_cache(cfg, batch, max_len,
                                  page_size=self.page_size,
                                  kv_pages=self.n_pages)
        if mesh is not None:
            self.params = self._shard_params(params)
            self.cache = self._shard_cache(self.cache)
        self.slots: list[Request | None] = [None] * batch
        self.queue: list[Request] = []
        self.remaining_prompt: list[np.ndarray] = [np.zeros((0,), np.int32)] * batch
        self.key = jax.random.PRNGKey(seed)
        self.greedy = greedy
        self.ticks = 0
        # one compiled reset for every slot (the slot is traced), updating
        # the donated cache in place: a layered model's recurrent state is
        # a large share of the device's memory
        self._reset = jax.jit(functools.partial(M.reset_slot, self.cfg),
                              donate_argnums=(0,))
        self._phase_flip = False  # alternates prefill/decode when both ready
        self._submit_seq = 0      # FIFO tiebreak for cost admission

    def _dp_shards(self) -> int:
        if self.mesh is None:
            return 1
        from repro.sharding import rules as R
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return int(np.prod([sizes[a] for a in R.dp_axes(self.mesh)]))

    def _make_step(self, point):
        return jax.jit(
            steps_lib.make_decode_step(
                self.cfg, use_mcma_dispatch=self.use_mcma_dispatch,
                with_stats=self.use_mcma_dispatch, operating_point=point,
                route_scope=self.route_scope, backend=self.backend),
            donate_argnums=(1,))

    def _make_chunk_step(self, point):
        return jax.jit(
            steps_lib.make_prefill_chunk_step(
                self.cfg, use_mcma_dispatch=self.use_mcma_dispatch,
                with_stats=self.use_mcma_dispatch, operating_point=point,
                route_scope=self.route_scope, backend=self.backend),
            donate_argnums=(1,))

    def _active_step(self):
        """The decode step for this tick: the controller's current ladder
        rung when autotuning (compiled lazily per rung, then cached — a
        switch is a dict lookup, never a retrace), else the static step."""
        if self.controller is None:
            return self.decode
        idx = self.controller.index
        if idx not in self._steps:
            self._steps[idx] = self._make_step(self.controller.ladder[idx])
        return self._steps[idx]

    def _active_chunk_step(self):
        """Chunk-step twin of _active_step: prefill runs at the SAME
        ladder rung as decode (one dispatch configuration per tick pair),
        but its stats never feed the controller."""
        if self.controller is None:
            return self.chunk
        idx = self.controller.index
        if idx not in self._chunk_steps:
            self._chunk_steps[idx] = self._make_chunk_step(
                self.controller.ladder[idx])
        return self._chunk_steps[idx]

    def _named_shardings(self, specs):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.tree.map(lambda q: NamedSharding(self.mesh, q), specs,
                            is_leaf=lambda q: isinstance(q, P))

    def _shard_params(self, params):
        from repro.sharding import rules as R
        specs, _ = R.param_pspecs(self.mesh, params)
        return jax.device_put(params, self._named_shardings(specs))

    def _shard_cache(self, cache):
        from repro.sharding import rules as R
        return jax.device_put(cache,
                              self._named_shardings(R.cache_pspecs(self.mesh,
                                                                   cache)))

    def _decode(self, *args, **kw):
        with steps_lib.serve_mesh_context(self.mesh):
            return self._active_step()(*args, **kw)

    def _prefill(self, *args, **kw):
        with steps_lib.serve_mesh_context(self.mesh):
            return self._active_chunk_step()(*args, **kw)

    def _residency_kw(self) -> dict:
        """The traced residency vector for this tick's step call (empty
        for non-library deployments — the steps' default ``None``)."""
        if self.residency is None:
            return {}
        return {"residency": jnp.asarray(self.residency)}

    def submit(self, req: Request):
        """Queue a request; per-request limits and QoS are validated HERE,
        loudly — nothing that reaches the tick loop can clamp a cache
        write or wedge a slot.

        Length contract: the prompt must be non-empty and
        ``len(prompt) + max_new <= max_len`` must hold (every prompt token
        and every sampled token occupies one cache position; positions are
        never recycled).  Overlong prompts raise under the default
        ``overflow="reject"`` policy; ``overflow="trim"`` keeps the LAST
        ``max_len - max_new`` prompt tokens instead.

        ``req.error_bound`` is checked against the deployment's tier table
        (anchored on the registry app's quality.py bound when ``qos_app``
        was given): a bound tighter than the tightest tier cannot be
        honored and raises, as does a non-positive/non-finite one; a valid
        bound quantizes onto the largest tier bound <= the request (served
        at-or-tighter than asked, never looser).  ``req.tier`` selects a
        tier index directly and must be in range."""
        req.prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if req.prompt.size == 0:
            raise ValueError(f"request {req.rid}: empty prompt — a request "
                             "must carry at least one prompt token")
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new {req.max_new} "
                             "must be >= 1")
        budget = self.max_len - int(req.max_new)
        if req.prompt.size > budget:
            if self.overflow == "reject":
                raise ValueError(
                    f"request {req.rid}: prompt ({req.prompt.size} tokens) "
                    f"+ max_new ({req.max_new}) exceeds max_len "
                    f"({self.max_len}) — the cache is a dense "
                    f"(batch, max_len) table with no position recycling; "
                    f"shorten the prompt/max_new or serve with "
                    f"overflow='trim'")
            if budget < 1:
                raise ValueError(
                    f"request {req.rid}: max_new ({req.max_new}) leaves no "
                    f"room for any prompt token within max_len "
                    f"({self.max_len}) — cannot trim")
            req.prompt = req.prompt[-budget:]   # trim policy: keep the tail
        if self.page_size:
            need = self._pages_needed(req.prompt.size + int(req.max_new))
            if need > self.n_pages:
                raise ValueError(
                    f"request {req.rid}: prompt ({req.prompt.size} tokens) "
                    f"+ max_new ({req.max_new}) needs {need} KV pages but "
                    f"the pool holds only {self.n_pages} "
                    f"(kv_page_size={self.page_size}) — the request could "
                    "never be scheduled; raise kv_pages or shorten it")
        if (req.error_bound is not None or req.tier is not None) \
                and self.tier_bounds is None:
            raise ValueError(
                f"request {req.rid} carries a QoS error_bound/tier but this "
                "server has no tier table — construct DecodeServer("
                "qos_tiers=...) (or qos_app=...) to serve per-request "
                "quality")
        if req.error_bound is not None:
            eb = float(req.error_bound)
            lo = self.tier_bounds[0]
            app = f" (app '{self.qos_app.name}' registry quality bound " \
                  f"{self.qos_app.error_bound})" if self.qos_app else ""
            if not np.isfinite(eb) or eb <= 0.0:
                raise ValueError(f"request {req.rid}: error_bound {eb!r} "
                                 f"is not a positive finite relative "
                                 f"error{app}")
            if eb < lo - 1e-12:
                raise ValueError(
                    f"request {req.rid}: error_bound {eb} is tighter than "
                    f"the tightest served tier {lo} — out of range for "
                    f"tiers {self.tier_bounds}{app}")
            # largest tier bound <= the request: at-or-tighter than asked
            # (a bound looser than every tier clamps to the loosest)
            req.tier = max(i for i, b in enumerate(self.tier_bounds)
                           if b <= eb + 1e-12)
        elif req.tier is not None:
            if not 0 <= int(req.tier) < len(self.tier_bounds):
                raise ValueError(
                    f"request {req.rid}: tier {req.tier} out of range for "
                    f"{len(self.tier_bounds)} tiers {self.tier_bounds}")
            req.tier = int(req.tier)
        req.arrival_tick = self.ticks
        req.arrival_s = time.perf_counter()
        req._seq = self._submit_seq          # FIFO tiebreak under "cost"
        self._submit_seq += 1
        self.queue.append(req)

    def _admission_cost(self, req: Request) -> float:
        """Cost-model admission key: the request's appetite for the
        resource that actually constrains the server, scaled by the
        tier's capacity appetite (tight tiers route more rows to the
        exact FFN, so a tight-tier token is more expensive to serve),
        minus an aging credit so queue time eventually dominates any
        length/tier gap.  Dense caches price prompt length; paged caches
        price the worst-case PAGE count (what admission reserves and what
        the pool runs out of)."""
        mult = 1.0
        if self.tier_bounds is not None and len(self.tier_bounds) > 1:
            tier = req.tier if req.tier is not None else self.default_tier
            n = len(self.tier_bounds)
            mult = 1.0 + 0.5 * (n - 1 - tier) / (n - 1)   # tightest x1.5
        age = self.ticks - (req.arrival_tick or 0)
        work = float(self._pages_needed(req.prompt.size + int(req.max_new))) \
            if self.page_size else float(len(req.prompt))
        return work * mult - self.aging * age

    def _pages_needed(self, tokens: int) -> int:
        """Worst-case page count for ``tokens`` cache positions."""
        return (int(tokens) + self.page_size - 1) // self.page_size

    def _ensure_slot_pages(self, i: int, tokens: int):
        """Grow slot ``i``'s block table to cover ``tokens`` positions,
        taking pages from the free pool (lazy acquisition — a slot only
        holds pages for tokens it has actually written or is about to
        write this tick).  Admission reserved the worst case up front, so
        the pool can never actually run dry here; if it does a scheduling
        invariant broke and we fail LOUDLY rather than drop a live
        token's write."""
        need = self._pages_needed(tokens)
        held = self._slot_pages[i]
        while len(held) < need:
            if not self._free_pages:
                self.alloc_failures += 1
                raise RuntimeError(
                    f"KV page pool exhausted growing slot {i} to {tokens} "
                    f"tokens (needs {need} pages; {self.pages_in_use}/"
                    f"{self.n_pages} in use) — admission reservations "
                    "should make this unreachable")
            pg = self._free_pages.pop()
            self._bt[i, len(held)] = pg
            held.append(pg)
            self.pages_in_use += 1
            self.page_hwm = max(self.page_hwm, self.pages_in_use)

    def _release_slot(self, i: int):
        """Return slot ``i``'s pages to the pool and drop its reservation
        — called the moment a request finishes, aborts, or strands
        (free-on-abort: a long-lived server's pool must never leak)."""
        if not self.page_size:
            return
        self._free_pages.extend(self._slot_pages[i])
        self.pages_in_use -= len(self._slot_pages[i])
        self._slot_pages[i] = []
        self._bt[i, :] = -1
        self._reserved_total -= self._reserved[i]
        self._reserved[i] = 0
        self._pos_host[i] = 0

    def _sync_block_table(self):
        """Refresh the cache's TRACED block-table leaf from the host
        allocator's mirror.  Same shape/dtype every tick, so allocation
        changes flow through the compiled steps as data — zero
        retraces."""
        if self.page_size:
            self.cache = dict(self.cache,
                              block_table=jnp.asarray(self._bt))

    def _admit(self):
        for i in range(self.batch):
            while self.slots[i] is None and self.queue:
                if self.admission == "cost":
                    j = min(range(len(self.queue)),
                            key=lambda j: (self._admission_cost(self.queue[j]),
                                           getattr(self.queue[j], "_seq", j)))
                else:
                    j = 0
                req = self.queue[j]
                need = 0
                if self.page_size:
                    need = self._pages_needed(
                        req.prompt.size + int(req.max_new))
                    if need > self.n_pages:
                        # can NEVER fit the pool (injected past submit()
                        # validation): abort instead of wedging the head
                        # of the queue forever
                        self.queue.pop(j)
                        req.aborted = True
                        req.done = True
                        continue            # retry this slot
                    if self._reserved_total + need > self.n_pages:
                        # worst-case reservation doesn't fit right now —
                        # head-of-line block (skipping ahead to a cheaper
                        # request would starve this one under sustained
                        # load); pages free as in-flight requests finish
                        self.alloc_failures += 1
                        return
                self.queue.pop(j)
                self.slots[i] = req
                req.admitted_s = time.perf_counter()
                self.remaining_prompt[i] = np.asarray(req.prompt, np.int32)
                if self.page_size:
                    self._reserved[i] = need
                    self._reserved_total += need
                with _span(scopes.RESET_SLOT):
                    self.cache = self._reset(self.cache, i)
                break

    def _abort_unservable(self):
        """Defensive wedge guard: abort (never clamp-write) any slot whose
        remaining prompt cannot fit the cache.  Unreachable through
        submit() validation — this catches requests injected straight into
        ``queue``/``slots`` (and any future scheduling bug) BEFORE a
        single out-of-range KV write happens."""
        pos = None
        for i, req in enumerate(self.slots):
            if req is None or not self.remaining_prompt[i].size:
                continue
            if pos is None:
                pos = np.asarray(self.cache["pos"])
            if int(pos[i]) + self.remaining_prompt[i].size > self.max_len:
                req.aborted = True
                req.done = True
                self.slots[i] = None
                self.remaining_prompt[i] = np.zeros((0,), np.int32)
                self._release_slot(i)       # free-on-abort: pages go back

    def _tiers_arr(self) -> np.ndarray:
        return np.asarray(
            [self.default_tier if s is None or s.tier is None
             else s.tier for s in self.slots], np.int32)

    def _prefill_rows(self) -> list[int]:
        """Slots mid-prompt with more than the final token left — the
        chunk step's work list (the last token always decodes)."""
        return [i for i, s in enumerate(self.slots)
                if s is not None and self.remaining_prompt[i].size > 1]

    def _prefill_tick(self, rows: list[int]):
        """One chunked-prefill tick: up to S prompt tokens per listed slot
        into the decode cache; no logits, no sampling.  Slots not listed
        have n_valid 0 — the chunk step writes nothing for them and their
        ``pos`` holds."""
        S = self.prefill_chunk
        with _span(scopes.PREPARE):
            toks = np.zeros((self.batch, S), np.int32)
            nv = np.zeros((self.batch,), np.int32)
            for i in rows:
                n = min(S, self.remaining_prompt[i].size - 1)
                toks[i, :n] = self.remaining_prompt[i][:n]
                self.remaining_prompt[i] = self.remaining_prompt[i][n:]
                nv[i] = n
            if self.page_size:
                for i in rows:
                    self._ensure_slot_pages(
                        i, int(self._pos_host[i]) + int(nv[i]))
                self._sync_block_table()
            args = [self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(nv)]
            if self.use_mcma_dispatch and self.tier_bounds is not None:
                args += [None, jnp.asarray(self._tiers_arr()),
                         jnp.asarray(self.tier_margins)]
            kw = self._residency_kw()
        with _span(scopes.STEP):
            self.cache, m = self._prefill(*args, **kw)
        with _span(scopes.READ_STATS):
            tokens = int(nv.sum())
            inv = None
            if self.use_mcma_dispatch and "invocation" in m:
                inv = float(m["invocation"])
                self.prefill_invocation_sum += inv * tokens
            self.prefill_tokens += tokens
            self.prefill_ticks += 1
            self._log_tick("prefill", tokens, inv)
        with _span(scopes.COMMIT):
            if self.page_size:
                for i in rows:
                    self._pos_host[i] += int(nv[i])

    def _decode_tick(self, rows: list[int]):
        """One decode tick for the listed slots; every other slot is
        masked out (its ``pos`` holds — the row's dummy write is
        overwritten by its next real token)."""
        with _span(scopes.PREPARE):
            toks = np.zeros((self.batch, 1), np.int32)
            fed_prompt = [False] * self.batch
            active = [False] * self.batch
            for i in rows:
                req = self.slots[i]
                active[i] = True
                if self.remaining_prompt[i].size:   # prompt-feeding phase
                    toks[i, 0] = self.remaining_prompt[i][0]
                    self.remaining_prompt[i] = self.remaining_prompt[i][1:]
                    fed_prompt[i] = True
                elif req.out:
                    toks[i, 0] = req.out[-1]
                else:
                    toks[i, 0] = req.prompt[-1]
            if self.page_size:
                # every listed row writes ONE token at its pos this tick —
                # make sure the page covering it is allocated (lazy
                # acquisition at the boundary crossing)
                for i in rows:
                    self._ensure_slot_pages(i, int(self._pos_host[i]) + 1)
                self._sync_block_table()
            args = [self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(active)]
            kw = {}
            if self.use_mcma_dispatch:
                # active-row mask: idle and mid-prefill slots are excluded
                # from the dispatch and its stats inside the step (the
                # free-slot bias fix), so every metric below is exact for
                # the decoding slots only
                if self.tier_bounds is not None:
                    # per-slot QoS tier vector, riding next to the mask;
                    # the margins vector is a traced input — one compiled
                    # step serves every tier mix
                    args += [jnp.asarray(self._tiers_arr()),
                             jnp.asarray(self.tier_margins)]
                kw = self._residency_kw()
        with _span(scopes.STEP):
            out = self._decode(*args, **kw)
            logits, self.cache = out[0], out[1]
        with _span(scopes.READ_STATS):
            if self.use_mcma_dispatch:
                self._read_decode_stats(out[2], sum(active))
            else:
                self._log_tick("decode", sum(active), None)
        with _span(scopes.SAMPLE):
            if self.greedy:
                nxt = np.asarray(jnp.argmax(logits, -1))
            else:
                self.key, k = jax.random.split(self.key)
                nxt = np.asarray(jax.random.categorical(k, logits))
        with _span(scopes.COMMIT):
            self._commit_decode(rows, nxt, fed_prompt)

    def _read_decode_stats(self, m: dict, n_active: int):
        """Fold one decode step's dispatch metrics into the server's sums
        and controllers (each read blocks on the step)."""
        inv = None
        if "invocation" in m:
            inv = float(m["invocation"])
            self.invocation_sum += inv * n_active
            self.active_sum += n_active
        if "dropped_rows" in m:
            self.dropped_sum += float(m["dropped_rows"])
            disp = np.asarray(m["dispatched"], float)
            routed = np.asarray(m["class_counts"], float)
            self.dispatched_sum = disp if self.dispatched_sum is None \
                else self.dispatched_sum + disp
            self.routed_sum = routed if self.routed_sum is None \
                else self.routed_sum + routed
            self.routed_history.append(routed)
            if len(self.routed_history) > self.routed_history_cap:
                del self.routed_history[0]
            if "tier_counts" in m:
                tc = np.asarray(m["tier_counts"], float)
                td = np.asarray(m["tier_dispatched"], float)
                self.tier_routed_sum = tc \
                    if self.tier_routed_sum is None \
                    else self.tier_routed_sum + tc
                self.tier_dispatched_sum = td \
                    if self.tier_dispatched_sum is None \
                    else self.tier_dispatched_sum + td
            if self.controller is not None:
                self.controller.observe(
                    {"class_counts": routed, "dropped": m["dropped_rows"]})
            if self.residency_controller is not None \
                    and "lib_counts" in m:
                # full-library demand histogram: feed the residency
                # controller and adopt whatever hot set it returns —
                # the next tick's step call carries the new vector
                # through the SAME compiled program (zero retraces)
                lib = np.asarray(m["lib_counts"], float)
                self.lib_routed_sum = lib \
                    if self.lib_routed_sum is None \
                    else self.lib_routed_sum + lib
                self.off_set_sum += float(m["off_set_exact_rows"])
                self.residency = np.asarray(
                    self.residency_controller.observe(
                        {"lib_counts": lib}), np.int32)
        self._log_tick("decode", n_active, inv)

    def _commit_decode(self, rows: list[int], nxt: np.ndarray,
                       fed_prompt: list[bool]):
        """Append each listed row's sampled token, stamp first tokens, and
        finish and release the requests that are done."""
        pos = np.asarray(self.cache["pos"])           # (B,) per-slot
        if self.page_size:
            for i in rows:
                self._pos_host[i] += 1
                # the mirror drives page acquisition — drift would leak
                # or clamp, so pin it against the device truth
                assert int(pos[i]) == int(self._pos_host[i]), \
                    (i, int(pos[i]), int(self._pos_host[i]))
        now = None
        for i in rows:
            req = self.slots[i]
            if fed_prompt[i] and self.remaining_prompt[i].size:
                continue                              # still consuming prompt
            req.out.append(int(nxt[i]))
            if req.first_token_tick is None:
                req.first_token_tick = self.ticks + 1   # tick about to close
                now = time.perf_counter() if now is None else now
                req.first_token_s = now
            if (self.eos is not None and req.out[-1] == self.eos) \
                    or len(req.out) >= req.max_new \
                    or int(pos[i]) >= self.max_len - 1:
                req.done = True
                self.slots[i] = None
                self._release_slot(i)   # finished: pages back to the pool

    def _log_tick(self, phase: str, tokens: int, invocation):
        self.tick_log.append((phase, tokens, invocation))
        if len(self.tick_log) > self.routed_history_cap:
            del self.tick_log[0]

    def tick(self):
        """One scheduler tick: admit, then run ONE compiled step — a
        prefill chunk or a decode step.  When both phases have work the
        scheduler alternates them, so queued prompts load S tokens per
        prefill tick while in-flight decodes keep streaming (neither
        phase can starve the other)."""
        with _span(scopes.TICK):
            with _span(scopes.ADMIT):
                self._admit()
            with _span(scopes.ABORT_CHECK):
                self._abort_unservable()
            if not any(s is not None for s in self.slots):
                return False
            prefill_rows = self._prefill_rows() if self.prefill_chunk else []
            decode_rows = [i for i, s in enumerate(self.slots)
                           if s is not None and i not in prefill_rows]
            if prefill_rows and (not decode_rows or not self._phase_flip):
                self._phase_flip = True
                self._prefill_tick(prefill_rows)
            else:
                self._phase_flip = False
                self._decode_tick(decode_rows)
            self.ticks += 1
            if self.page_size:
                # page_util's raw signal: tokens actually held vs the
                # token capacity of the pages holding them, sampled once
                # per tick
                self._held_token_ticks += int(sum(
                    self._pos_host[i] for i in range(self.batch)
                    if self._slot_pages[i]))
                self._held_page_ticks += self.pages_in_use
            return True

    def run_until_drained(self, max_ticks: int = 10_000) -> DrainStats:
        """Tick until queue and slots are empty (or ``max_ticks``); returns
        a ``DrainStats`` — dict-style access preserved for old callers."""
        t0 = time.time()
        while (self.queue or any(s is not None for s in self.slots)) \
                and self.ticks < max_ticks:
            self.tick()
        stats = DrainStats(ticks=self.ticks, wall_s=time.time() - t0)
        # tick-budget exhaustion is NOT a quiet success: stranded requests
        # are marked aborted (done stays False) and counted here, so a
        # caller can never mistake a truncated drain for a finished one
        undrained_inflight = sum(s is not None for s in self.slots)
        for i, s in enumerate(self.slots):
            if s is not None:
                s.aborted = True
                # stranded slots release their KV state eagerly — with a
                # paged pool this would otherwise be a real leak (the
                # dense cache merely lingered until slot reuse)
                self.slots[i] = None
                self.remaining_prompt[i] = np.zeros((0,), np.int32)
                self._release_slot(i)
        for r in self.queue:
            r.aborted = True
        stats["undrained_queued"] = len(self.queue)
        stats["undrained_inflight"] = undrained_inflight
        stats["prefill_ticks"] = self.prefill_ticks
        stats["prefill_tokens"] = self.prefill_tokens
        if self.use_mcma_dispatch:
            stats["invocation_rate"] = \
                self.invocation_sum / max(self.active_sum, 1)
            if self.prefill_tokens:
                stats["prefill_invocation_rate"] = \
                    self.prefill_invocation_sum / self.prefill_tokens
            # the autotuner's objective, observable from server stats:
            # global dropped rows and per-class routed/dispatched counts
            # (layer-mean per tick, summed over ticks; mesh runs report
            # psum-reduced global totals).  Decode-phase only — prefill
            # chunks report into the prefill_* accumulators above.
            stats["dropped_rows"] = self.dropped_sum
            if self.routed_sum is not None:
                stats["routed_per_class"] = self.routed_sum.tolist()
                stats["dispatched_per_class"] = self.dispatched_sum.tolist()
                total = max(float(self.routed_sum.sum()), 1.0)
                stats["dropped_frac"] = self.dropped_sum / total
                # invocation actually SERVED (approx rows executed, not
                # just routed) — what capacity autotuning maximizes
                stats["served_invocation_rate"] = \
                    float(self.dispatched_sum[1:].sum()) / total
            if self.tier_bounds is not None \
                    and self.tier_routed_sum is not None:
                # the drain summary's QoS ledger: served invocation and
                # dropped fraction attributed to each error-bound tier
                per = []
                for k, bound in enumerate(self.tier_bounds):
                    routed_k = self.tier_routed_sum[k]
                    disp_k = self.tier_dispatched_sum[k]
                    rows = float(routed_k.sum())
                    per.append({
                        "tier": k,
                        "error_bound": float(bound),
                        "margin": float(self.tier_margins[k]),
                        "rows": rows,
                        "served_invocation_rate":
                            float(disp_k[1:].sum()) / max(rows, 1.0),
                        "routed_invocation_rate":
                            float(routed_k[1:].sum()) / max(rows, 1.0),
                        "dropped_rows": float((routed_k - disp_k).sum()),
                        "dropped_frac":
                            float((routed_k - disp_k).sum()) / max(rows, 1.0),
                    })
                stats["per_tier"] = per
            if self.lib_routed_sum is not None:
                # full-library routed demand vs what the resident set
                # could serve: off_set_exact_rows is the residency
                # opportunity cost (rows a bigger/better-tuned hot set
                # would have approximated)
                stats["lib_routed_per_class"] = self.lib_routed_sum.tolist()
                stats["off_set_exact_rows"] = self.off_set_sum
        if self.controller is not None:
            stats["autotune"] = self.controller.summary()
        if self.residency_controller is not None:
            stats["residency"] = self.residency_controller.summary()
        if self.page_size:
            stats["pages_in_use"] = self.pages_in_use
            stats["page_hwm"] = self.page_hwm
            stats["alloc_failures"] = self.alloc_failures
            stats["page_util"] = self._held_token_ticks / max(
                self._held_page_ticks * self.page_size, 1)
        stats["kv_bytes_resident"] = self._kv_bytes_resident()
        return stats

    def _kv_bytes_resident(self) -> int:
        """Peak resident KV-cache bytes.  Dense caches reserve their
        worst case permanently (batch x max_len whatever is held); a
        paged run pays only for the pages at its high-water mark — the
        bench's paged-vs-dense memory gate compares exactly this."""
        k = self.cache.get("k") if isinstance(self.cache, dict) else None
        if k is None:
            return 0                      # pure-SSM caches: no KV to page
        total = int(k.nbytes) * 2         # the k + v stacks
        if not self.page_size:
            return total
        assert total % self.n_pages == 0, (total, self.n_pages)
        return (total // self.n_pages) * self.page_hwm

    def derived_ladder(self, **kwargs):
        """runtime/autotune.ladder_from_counts over this server's served
        per-tick ``routed_per_class`` history: capacity rungs whose
        per-class budgets track the observed class-count quantiles — the
        asymmetric ladder to deploy for the NEXT run of this mix."""
        from repro.runtime import autotune as at
        assert self.routed_history, \
            "no served invoke stats yet (needs use_mcma_dispatch ticks)"
        return at.ladder_from_counts(
            np.asarray(self.routed_history), self.batch,
            tier_margins=tuple(float(m) for m in self.tier_margins)
            if self.tier_bounds is not None else (), **kwargs)
