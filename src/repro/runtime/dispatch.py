"""MCMA dispatch runtime — the single serving-side invocation engine.

The paper's NPU swaps the invoked approximator "within a cycle" by shipping
a weight set from on-chip cache to the PE weight buffers (§III-D).  This
module is the TPU-serving analog, one jit-stable pipeline behind
``mcma_dispatch``:

  classify   router/classifier logits -> per-row class (0 = exact / nC)
  capacity   static per-class token budgets (GShard convention:
             over-capacity rows contribute zero; the residual carries them)
  class-sort rows grouped into single-class row-tiles
             (kernels/ops.class_sort_plan)
  switch     the scalar-prefetch Pallas kernel streams each tile's
             approximator weights HBM->VMEM behind the previous tile's
             compute (kernels/switched_mlp.py) — the weight-buffer swap
  exact      class-0 (non-approximable, "nC") rows run the exact function
             on a gathered capacity buffer; in the Pallas path the
             nC/over-capacity rows ride through the kernel under a
             zero-weight pseudo-approximator so the grouped matmul stays
             one kernel launch (their contribution is exactly 0)
  scatter    results return to the original row order

Backends:
  * ``backend="pallas"`` — the weight-switch kernel path above
    (``interpret=True`` runs it on CPU; compiled on TPU).
  * ``backend="pallas_fused"`` — the same plan executed by the FUSED
    kernel (kernels/fused_dispatch.py): the class-sort permutation rides
    into the kernel as a scalar-prefetched row-index vector, so the
    gather/scatter legs disappear from the XLA program and activations
    cross HBM once per layer.  Bit-identical to "pallas".
  * ``backend="xla"``    — the portable per-class gather/scatter loop the
    seed shipped.  It is the semantic oracle: tests require both Pallas
    paths to match it on every dispatched row.

Every call also returns ``invoke_stats`` (per-class routed counts,
post-capacity dispatched counts, dropped rows, exact fraction, executed
rows vs useful rows) so servers and benchmarks can report invocation rate
— the paper's headline metric — per request batch.

The engine is shard_map-native: called inside a ``shard_map`` over the
data axes with ``stats_axes=<those axes>``, each data shard classifies,
capacities, class-sorts, and runs the weight-switch kernel on its OWN
rows (no cross-shard dispatch traffic — the same lesson as the manual MoE
path), while the invoke_stats are ``psum``-reduced over ``stats_axes`` so
every caller sees the global totals, exactly equal to summing each
shard's local stats on one device.  ``mcma_dispatch_sharded`` is the
ready-made wrapper for flat row batches; the model layers
(models/approx_ffn.py) embed the engine in their own shard_map instead.

Per-request QoS: quality is a per-ROW runtime value, not a config
constant.  Every row may carry a tier (``tier``, (T,) int32) indexing a
TRACED ``(n_tiers,)`` vector of exact-logit margins (``route``): tight
error bounds bias borderline rows to the exact path, loose bounds hand
them to their best approximator — one compiled program serves every
margin setting, and the invoke_stats split routed/dispatched/dropped
per tier so servers can report served invocation per QoS class.

Plan/execute split: the route -> capacity -> class-sort half of the
pipeline is ``make_dispatch_plan`` and returns a ``DispatchPlan`` (class
ids, within-class ranks, the class-sort permutation, keep/slot buffers,
per-class counts — everything that depends on the LOGITS but not on the
layer's weights); ``execute_dispatch`` applies one layer's approximators
and exact path against a plan.  ``mcma_dispatch`` is exactly
``make_dispatch_plan`` + ``execute_dispatch`` + ``plan_invoke_stats``,
so the paper's one-decision-per-input semantics fall out for free: route
once per decode tick, reuse the SAME plan across all L layers of the
scan (``ApproxConfig.route_scope = "tick"``), and each layer is one
weight-switch kernel launch on already-sorted rows.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import ops

# Backends whose plans carry a live class-sort (order/pos/tile_cls) and
# launch the worst-case single-class-tile grid; "xla" carries placeholders.
PALLAS_BACKENDS = ("pallas", "pallas_fused")
DISPATCH_BACKENDS = ("xla",) + PALLAS_BACKENDS


def route(logits: jax.Array, tier: jax.Array | None = None,
          tier_margins: jax.Array | None = None) -> jax.Array:
    """Router/classifier logits (T, n+1) -> class ids (T,); 0 = exact.

    Per-request QoS: ``tier`` ((T,) int32 in [0, n_tiers)) indexes
    ``tier_margins`` ((n_tiers,) float32, a TRACED vector — margins change
    per call without retracing), a per-tier bias added to the EXACT-path
    logit before the argmax.  A positive margin makes the exact path win
    ties it would otherwise lose (tighter error bound, less invocation); a
    negative margin hands borderline rows to their best approximator
    (looser bound, more invocation).  ``tier=None`` — or any tier whose
    margin is 0.0 — reproduces the plain argmax bit-for-bit (x + 0.0
    changes no float comparison), so a uniform default-tier batch routes
    exactly as the margin-free engine did.
    """
    lg = logits.astype(jnp.float32)
    if tier is not None and tier_margins is not None:
        lg = lg.at[:, 0].add(tier_margins.astype(jnp.float32)[tier])
    return jnp.argmax(lg, -1).astype(jnp.int32)


def apply_approximator(xb: jax.Array, w1: jax.Array, b1: jax.Array,
                       w2: jax.Array, b2: jax.Array) -> jax.Array:
    """One approximator's tanh MLP on a row block — the single definition
    of the per-class math shared by the XLA oracle backend and the manual
    sharded serve path (models/approx_ffn._approx_serve_manual)."""
    h = jnp.tanh(jnp.dot(xb, w1.astype(xb.dtype)) + b1.astype(xb.dtype))
    return jnp.dot(h, w2.astype(xb.dtype)) + b2.astype(xb.dtype)


def _rank_in_class(cls: jax.Array, n_classes: int) -> jax.Array:
    """rank[i] = #rows j<=i with cls[j]==cls[i], minus one (arrival order)."""
    oh = jax.nn.one_hot(cls, n_classes, dtype=jnp.int32)      # (T, n_classes)
    return jnp.take_along_axis(jnp.cumsum(oh, 0) - 1, cls[:, None], 1)[:, 0]


# ---------------------------------------------------------------------------
# Shared capacity gather/scatter primitives.  These four functions are the
# ONE implementation of sort-based capacity dispatch in the repo: the MCMA
# engine below, the manual expert-parallel MoE path (models/moe.py), and
# the grouped MoE reference all build on them.
# ---------------------------------------------------------------------------

def class_sort_ranks(cls: jax.Array, n: int):
    """Stable class-sort with within-class arrival ranks.

    cls: (R,) int32 in [0, n).  Returns ``(order, cls_sorted, rank,
    counts)``: visiting rows in ``order`` walks class 0 first, then 1, ...;
    ``rank[i]`` is row ``order[i]``'s arrival rank within its class;
    ``counts`` is the per-class histogram (length n).
    """
    order = jnp.argsort(cls, stable=True)
    cls_sorted = cls[order]
    counts = jnp.bincount(cls, length=n)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype), jnp.cumsum(counts)])
    rank = jnp.arange(cls.shape[0]) - starts[cls_sorted]
    return order, cls_sorted, rank, counts


def capacity_slots(cls_sorted: jax.Array, rank: jax.Array, cap: int, *,
                   n_local: int, offset=0):
    """keep mask + buffer slots for a (n_local, cap) capacity buffer.

    Rows of classes outside [offset, offset + n_local) or ranked past
    ``cap`` fall into the trash slot ``n_local * cap`` (the GShard
    convention — dropped rows contribute zero).  ``offset`` may be traced
    (e.g. this model-shard's first expert id).
    """
    local = (cls_sorted >= offset) & (cls_sorted < offset + n_local)
    keep = (rank < cap) & local
    slot = jnp.where(keep, (cls_sorted - offset) * cap + rank, n_local * cap)
    return keep, slot


def scatter_rows(rows: jax.Array, slot: jax.Array, keep: jax.Array,
                 n_slots: int) -> jax.Array:
    """rows (R, d) -> (n_slots, d) buffer; slot n_slots is the trash row.

    Degenerate slots are PINNED, not incidental (the same contract as
    ops.gather_resident_stacks): a slot outside [0, n_slots] is dropped —
    routed to the trash row, never wrapped onto a real slot the way
    jit's negative-index semantics would — and duplicate slots resolve
    deterministically by summation (the buffer is zero-initialized, so
    the engine's unique valid slots are written bit-identically to a
    plain set).
    """
    slot = slot.astype(jnp.int32)
    ok = keep & (slot >= 0) & (slot <= n_slots)
    buf = jnp.zeros((n_slots + 1, rows.shape[-1]), rows.dtype)
    return buf.at[jnp.where(ok, slot, n_slots)] \
        .add(rows * ok[:, None])[:n_slots]


def gather_rows(y: jax.Array, slot: jax.Array, keep: jax.Array) -> jax.Array:
    """(n_slots, d_out) buffer -> per-row outputs; dropped rows are zero.

    A slot outside [0, n_slots) reads the appended zero row (pinned like
    ops.gather_resident_stacks — never jit's clamp onto a real slot) and
    the row comes out exactly zero; duplicate slots simply duplicate the
    buffer row.
    """
    n_slots = y.shape[0]
    y = jnp.concatenate([y, jnp.zeros((1, y.shape[-1]), y.dtype)], 0)
    slot = slot.astype(jnp.int32)
    ok = keep & (slot >= 0) & (slot < n_slots)
    return y[jnp.where(ok, slot, n_slots)] * ok[:, None]


def capacity_path(x: jax.Array, mask: jax.Array, cap: int,
                  fn: Callable[[jax.Array], jax.Array]) -> jax.Array:
    """Gather <=cap rows where mask, apply fn, scatter back (zeros elsewhere).

    Static shapes throughout: rows ranked past ``cap`` fall into a trash
    slot and contribute zero — identical math to the seed's serve path.
    """
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1               # rank in class
    keep = mask & (pos < cap)
    slot = jnp.where(keep, pos, cap)                           # cap = trash
    y = fn(scatter_rows(x, slot, keep, cap))
    return gather_rows(y, slot, keep)


# ---------------------------------------------------------------------------
# Plan/execute: the routing decision as a first-class value.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """One routing decision over a flat row batch, ready to execute.

    Everything here depends on the router LOGITS (and the capacities) but
    not on any layer's weights, so a plan built once per decode tick can
    be replayed against every layer of the scan.  Array fields (traced,
    pytree data):

      cls         (T,) int32 routed class per row (0 = exact; inactive
                  rows under ``row_mask`` are forced to 0)
      rank        (T,) int32 within-class arrival rank of every row
      eff         (T,) int32 kernel class ids: kept approx rows keep
                  ``cls - 1``; exact / over-capacity / inactive rows get
                  the zero-weight pseudo-class ``n_approx``
      order, pos  the class-sort permutation of ``eff`` and each row's
                  padded single-class-tile position (ops.class_sort_plan;
                  identity/zero placeholders on "xla" plans — only the
                  Pallas executor consumes the sort)
      tile_cls    (t_pad // block_t,) per-tile class for the weight switch
      exact_keep  (T,) bool — class-0 rows inside the exact capacity
      exact_slot  (T,) int32 capacity-buffer slot (exact_cap = trash)
      counts      (n_approx + 1,) routed rows per class
      dispatched  (n_approx + 1,) post-capacity executed rows per class
      t_total     () int32 active rows
      executed    () int32 rows of compute the executor will launch
      tier        (T,) int32 per-row QoS tier (zeros when the plan was
                  built without a tier vector)
      tier_counts (n_tiers, n_approx + 1) routed rows per tier per class
                  (sums over tiers to ``counts``)
      tier_dispatched  (n_tiers, n_approx + 1) post-capacity executed
                  rows per tier per class (sums over tiers to
                  ``dispatched`` — capacity is tier-blind arrival order,
                  the split just attributes the kept rows)
      lib_counts  (library_size + 1,) routed rows per LIBRARY class when
                  the plan was built with a ``residency`` map (the
                  ResidencyController's demand signal — entry 0 is the
                  router's own exact votes, off-set classes keep their
                  library column); equals ``counts`` on library-less plans
      off_set_rows () int32 active rows routed to a library class with no
                  resident slot this tick — they fall back to the exact
                  path (inside ``counts[0]``), so invocation honestly
                  reflects the residency penalty

    ``counts``/``dispatched``/``t_total``/``executed``, the per-tier
    count matrices, and ``lib_counts``/``off_set_rows`` are psum-reduced
    GLOBAL totals when the plan is built with ``stats_axes`` inside a
    shard_map; the row-shaped fields stay shard-local.  Static metadata
    (pytree aux): ``n_approx``, the capacities (``invoke_cap`` is an int
    for the uniform budget or a per-class tuple for asymmetric ones —
    ``class_caps`` normalizes), ``block_t``, ``backend``, ``n_tiers``,
    and ``library_size`` (0 = built without a residency map).
    """

    cls: jax.Array
    rank: jax.Array
    eff: jax.Array
    order: jax.Array
    pos: jax.Array
    tile_cls: jax.Array
    exact_keep: jax.Array
    exact_slot: jax.Array
    counts: jax.Array
    dispatched: jax.Array
    t_total: jax.Array
    executed: jax.Array
    tier: jax.Array
    tier_counts: jax.Array
    tier_dispatched: jax.Array
    lib_counts: jax.Array
    off_set_rows: jax.Array
    n_approx: int
    exact_cap: int
    invoke_cap: int | tuple
    block_t: int
    backend: str
    n_tiers: int
    library_size: int

    @property
    def class_caps(self) -> tuple:
        """Per-class invoke capacities, length ``n_approx`` (normalizes
        the uniform-int and asymmetric-tuple forms of ``invoke_cap``)."""
        ic = self.invoke_cap
        return tuple(ic) if isinstance(ic, (tuple, list)) \
            else (ic,) * self.n_approx


_PLAN_DATA = ("cls", "rank", "eff", "order", "pos", "tile_cls",
              "exact_keep", "exact_slot", "counts", "dispatched",
              "t_total", "executed", "tier", "tier_counts",
              "tier_dispatched", "lib_counts", "off_set_rows")
_PLAN_META = ("n_approx", "exact_cap", "invoke_cap", "block_t", "backend",
              "n_tiers", "library_size")

jax.tree_util.register_pytree_node(
    DispatchPlan,
    lambda p: (tuple(getattr(p, f) for f in _PLAN_DATA),
               tuple(getattr(p, f) for f in _PLAN_META)),
    lambda meta, data: DispatchPlan(*data, *meta))


def make_dispatch_plan(logits: jax.Array,
                       row_mask: jax.Array | None = None, *,
                       exact_cap: int | None = None,
                       invoke_cap=None,
                       operating_point=None, backend: str = "xla",
                       block_t: int = 128,
                       stats_axes: tuple = (),
                       tier: jax.Array | None = None,
                       tier_margins: jax.Array | None = None,
                       n_tiers: int | None = None,
                       residency: jax.Array | None = None) -> DispatchPlan:
    """classify -> capacity -> class-sort, once, as a reusable plan.

    logits: (T, n_approx + 1) router/classifier scores (class 0 = exact);
    ``row_mask`` marks ACTIVE rows exactly as in ``mcma_dispatch``.
    Capacities come either from explicit ``exact_cap``/``invoke_cap``
    (``invoke_cap`` is an int shared by every class or a length-n_approx
    tuple of asymmetric per-class budgets) or from an ``operating_point``
    (runtime/autotune.OperatingPoint, applied to this batch's row count
    via sharding/rules.shard_capacity; its ``invoke_fracs`` yield the
    per-class form).  ``stats_axes`` psum-reduces the count fields to
    global totals when building inside a shard_map — build and consume
    the plan inside the same shard_map region
    (sharding/rules.dispatch_plan_specs describes how its fields shard
    between the two).

    Per-request QoS: ``tier`` ((T,) int32) + ``tier_margins`` ((n_tiers,)
    float32, TRACED — one compiled program serves every margin setting)
    replace the plain argmax with the tier-indexed exact-logit margin
    (see ``route``), and the plan's ``tier_counts``/``tier_dispatched``
    split the routed/executed rows per tier.  ``tier=None`` keeps the
    margin-free routing bit-for-bit and records everything as tier 0.

    Approximator-library residency: with ``residency`` ((n_resident,)
    int32 of library ids, TRACED — a hot-set swap is a new vector through
    the same compiled program), ``logits`` carry ``library_size + 1``
    columns and routing happens over the FULL library; a slot map then
    folds each library class onto its resident slot (or onto the exact
    path when the class is off-set this tick).  The plan's ``n_approx``
    stays the RESIDENT slot count — capacities, class-sort, and the
    executor are untouched — while ``lib_counts`` keeps the full-library
    demand histogram (the ResidencyController's signal) and
    ``off_set_rows`` counts the rows paying the residency penalty.
    """
    t = logits.shape[0]
    if residency is not None:
        library_size = logits.shape[-1] - 1
        n = int(residency.shape[0])
        assert n <= library_size, (
            f"residency map holds {n} slots but the library has only "
            f"{library_size} approximators")
    else:
        library_size = 0
        n = logits.shape[-1] - 1
    if operating_point is not None:
        from repro.sharding.rules import shard_capacity
        assert exact_cap is None and invoke_cap is None, \
            "pass capacities OR an operating_point, not both"
        exact_cap = shard_capacity(t, operating_point.exact_frac,
                                   slack=operating_point.shard_slack)
        if operating_point.invoke_fracs:
            invoke_cap = tuple(
                shard_capacity(t, f, slack=operating_point.shard_slack)
                for f in operating_point.class_fracs(n))
        else:
            invoke_cap = shard_capacity(t, operating_point.invoke_frac,
                                        slack=operating_point.shard_slack)
    if isinstance(invoke_cap, list):
        invoke_cap = tuple(invoke_cap)      # hashable pytree meta
    class_caps = tuple(invoke_cap) if isinstance(invoke_cap, tuple) \
        else (int(invoke_cap),) * n
    assert len(class_caps) == n, (
        f"per-class invoke_cap tuple (len {len(class_caps)}) must carry "
        f"one budget per approximator (n_approx={n})")

    # tier bookkeeping: the static tier count comes from the margins
    # vector (or the explicit n_tiers override); tier-less plans carry a
    # single tier 0 so the per-tier stats schema is backend- and
    # caller-independent.  A tier vector without either is refused: the
    # per-tier bincount would silently drop every tier >= 1 row (and no
    # margin would apply), corrupting the QoS stats instead of failing.
    assert tier is None or tier_margins is not None or n_tiers is not None, \
        "tiered dispatch needs the (n_tiers,) tier_margins vector (or an " \
        "explicit n_tiers) alongside the tier ids"
    nt = int(tier_margins.shape[0]) if tier_margins is not None \
        else int(n_tiers or 1)
    tier_ids = jnp.zeros((t,), jnp.int32) if tier is None \
        else tier.astype(jnp.int32)

    cls = route(logits, None if tier is None else tier_ids, tier_margins)
    lib_cls = cls
    if residency is not None:
        # fold library classes onto resident slots: slot_map[lib id + 1] =
        # resident slot + 1, everything else (exact votes AND off-set
        # classes) lands on 0 = the exact path.  The fold happens BEFORE
        # capacity/class-sort, so downstream the plan is indistinguishable
        # from an n_resident-approximator plan.
        slot_map = jnp.zeros((library_size + 1,), jnp.int32) \
            .at[residency.astype(jnp.int32) + 1] \
            .set(jnp.arange(1, n + 1, dtype=jnp.int32))
        cls = slot_map[lib_cls]
    if row_mask is not None:
        mask = row_mask.astype(bool)
        # inactive rows: class 0 so they never claim an approximator rank;
        # the exact keep below additionally excludes them via the mask,
        # and the sentinel class n+1 keeps them out of counts.
        cls = jnp.where(mask, cls, 0)
        routed_col = jnp.where(mask, cls, n + 1)
        counts = jnp.bincount(routed_col, length=n + 2)[:n + 1]
        exact_mask = (cls == 0) & mask
        t_total = jnp.sum(mask.astype(jnp.int32))
    else:
        routed_col = cls
        counts = jnp.bincount(cls, length=n + 1)
        exact_mask = cls == 0
        t_total = jnp.asarray(t, jnp.int32)
    # per-tier routed split (sentinel column n+1 absorbs inactive rows)
    tier_counts = jnp.bincount(tier_ids * (n + 2) + routed_col,
                               length=nt * (n + 2)) \
        .reshape(nt, n + 2)[:, :n + 1]

    # library demand histogram + off-set accounting (residency plans only):
    # lib_counts keeps the router's FULL-library votes (off-set classes
    # keep their own column — the promotion signal), off_set_rows counts
    # the active rows folded onto the exact path for lack of a slot.
    if residency is not None:
        off_mask = (lib_cls > 0) & (cls == 0)
        if row_mask is not None:
            lib_col = jnp.where(mask, lib_cls, library_size + 1)
            off_mask = off_mask & mask
        else:
            lib_col = lib_cls
        lib_counts = jnp.bincount(lib_col, length=library_size + 2) \
            [:library_size + 1]
        off_set_rows = jnp.sum(off_mask.astype(jnp.int32))
    else:
        lib_counts = counts
        off_set_rows = jnp.zeros((), jnp.int32)

    # approximator side: capacity first, then the single-class-tile sort
    # of the effective classes (kept rows keep cls-1; exact/over-capacity/
    # inactive rows ride the zero-weight pseudo-class n).  Only the Pallas
    # executor consumes the sort fields (the XLA oracle re-derives per-class
    # slots from cls/rank), so an "xla" plan carries cheap identity/zero
    # placeholders of the same shapes instead of paying a dead argsort —
    # the plan SCHEMA is backend-independent, the sort work is not.
    rank = _rank_in_class(cls, n + 1)
    cap_of = jnp.asarray((0,) + class_caps, jnp.int32)
    kept = (cls > 0) & (rank < cap_of[cls])
    eff = jnp.where(kept, cls - 1, n).astype(jnp.int32)
    if backend in PALLAS_BACKENDS:
        order, pos, tile_cls, _, _ = ops.class_sort_plan(eff, n + 1, block_t)
    else:
        n_tiles = ops.worst_case_rows(t, n + 1, block_t) // block_t
        order = pos = jnp.arange(t, dtype=jnp.int32)
        tile_cls = jnp.zeros((n_tiles,), jnp.int32)

    # exact ("nC") side: capacity-buffer keep/slot (exact_cap = trash)
    epos = jnp.cumsum(exact_mask.astype(jnp.int32)) - 1
    exact_keep = exact_mask & (epos < exact_cap)
    exact_slot = jnp.where(exact_keep, epos, exact_cap)

    caps = jnp.asarray([exact_cap, *class_caps], counts.dtype)
    dispatched = jnp.minimum(counts, caps)
    # per-tier dispatched split: capacity keeps rows by tier-blind arrival
    # rank, so attributing the KEPT rows (exact_keep | kept) to their tier
    # sums back to ``dispatched`` per class exactly
    disp_col = jnp.where(exact_keep | kept, cls, n + 1)
    tier_dispatched = jnp.bincount(tier_ids * (n + 2) + disp_col,
                                   length=nt * (n + 2)) \
        .reshape(nt, n + 2)[:, :n + 1]
    if backend in PALLAS_BACKENDS:
        # both Pallas executors launch the full static worst-case grid
        # (including trailing zero tiles past the occupied region) — n+1
        # classes including the pseudo-class
        executed = jnp.asarray(
            exact_cap + ops.worst_case_rows(t, n + 1, block_t), jnp.int32)
    elif backend == "xla":
        executed = jnp.asarray(exact_cap + sum(class_caps), jnp.int32)
    else:
        raise ValueError(f"unknown dispatch backend: {backend!r}")
    if stats_axes:
        # inside shard_map: reduce to GLOBAL stats.  Each quantity is a sum
        # of per-shard terms, so psum of the local values equals the
        # single-device totals over the same per-shard capacities exactly.
        ax = tuple(stats_axes)
        t_total = jax.lax.psum(t_total, ax)
        counts = jax.lax.psum(counts, ax)
        dispatched = jax.lax.psum(dispatched, ax)
        executed = jax.lax.psum(executed, ax)
        tier_counts = jax.lax.psum(tier_counts, ax)
        tier_dispatched = jax.lax.psum(tier_dispatched, ax)
        if residency is not None:
            lib_counts = jax.lax.psum(lib_counts, ax)
            off_set_rows = jax.lax.psum(off_set_rows, ax)
        else:
            lib_counts = counts        # stay aliased to the reduced counts
    return DispatchPlan(cls=cls, rank=rank, eff=eff, order=order, pos=pos,
                        tile_cls=tile_cls, exact_keep=exact_keep,
                        exact_slot=exact_slot, counts=counts,
                        dispatched=dispatched, t_total=t_total,
                        executed=executed, tier=tier_ids,
                        tier_counts=tier_counts,
                        tier_dispatched=tier_dispatched,
                        lib_counts=lib_counts, off_set_rows=off_set_rows,
                        n_approx=n, exact_cap=exact_cap,
                        invoke_cap=invoke_cap, block_t=block_t,
                        backend=backend, n_tiers=nt,
                        library_size=library_size)


@dataclasses.dataclass(frozen=True)
class InvokeStats:
    """The engine's per-call invocation statistics, typed.

    Every field is a jnp scalar/vector (a pytree of pure data — the class
    is a registered pytree node, so it rides through jit / shard_map /
    ``jax.tree.map(np.asarray, stats)`` like the dict it replaces).  The
    stable public field names:

      class_counts     (n_approx + 1,) routed ACTIVE rows per RESIDENT
                       class (0 = exact); sums to the active row count
      dispatched       (n_approx + 1,) rows actually executed after
                       capacity
      dropped          scalar int, over-capacity rows (zero contribution)
      exact_frac       scalar float, class_counts[0] / active rows
      invocation       scalar float, 1 - exact_frac (the paper's
                       invocation rate; 0.0 on a fully idle batch)
      executed_rows    scalar int, rows of compute actually launched
      padding_rows     scalar int, executed_rows - sum(dispatched)
      tier_counts      (n_tiers, n_approx + 1) routed rows per QoS tier
      tier_dispatched  (n_tiers, n_approx + 1) executed rows per tier
      tier_dropped     (n_tiers,) over-capacity rows per tier
      tier_served_invocation  (n_tiers,) executed approximator rows over
                       that tier's active rows
      lib_counts       (library_size + 1,) routed rows per LIBRARY class
                       under a residency map (equals class_counts on
                       library-less calls) — the promotion signal
      off_set_exact_rows  scalar int, active rows routed to an off-set
                       library class and folded onto the exact path (0
                       without a residency map) — the residency penalty

    Dict-style access (``stats["invocation"]``, ``.get``, ``in``,
    ``dict(stats)``) is kept for existing call sites and the CSV writers;
    ``.asdict()`` is the explicit spelling.
    """

    class_counts: jax.Array
    dispatched: jax.Array
    dropped: jax.Array
    exact_frac: jax.Array
    invocation: jax.Array
    executed_rows: jax.Array
    padding_rows: jax.Array
    tier_counts: jax.Array
    tier_dispatched: jax.Array
    tier_dropped: jax.Array
    tier_served_invocation: jax.Array
    lib_counts: jax.Array
    off_set_exact_rows: jax.Array

    # -- mapping protocol (drop-in for the dict this class replaced) --------
    def __getitem__(self, key: str):
        try:
            return getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None

    def __contains__(self, key) -> bool:
        return key in _STATS_FIELDS

    def __iter__(self):
        # iterate keys like the dict this replaced (without this, the
        # legacy __getitem__ iteration protocol probes stats[0])
        return iter(_STATS_FIELDS)

    def get(self, key: str, default=None):
        return getattr(self, key, default)

    def keys(self):
        return iter(_STATS_FIELDS)

    def items(self):
        return ((f, getattr(self, f)) for f in _STATS_FIELDS)

    def asdict(self) -> dict:
        return {f: getattr(self, f) for f in _STATS_FIELDS}


_STATS_FIELDS = tuple(f.name for f in dataclasses.fields(InvokeStats))

jax.tree_util.register_pytree_node(
    InvokeStats,
    lambda s: (tuple(getattr(s, f) for f in _STATS_FIELDS), None),
    lambda _, data: InvokeStats(*data))


def plan_invoke_stats(plan: DispatchPlan) -> InvokeStats:
    """The engine's ``InvokeStats``, derived from a plan (elementwise —
    cheap to call per layer; identical fields/values to
    ``mcma_dispatch``'s second return).  Already global totals for plans
    built with ``stats_axes``, so no collectives are needed here."""
    exact_frac = (plan.counts[0] / jnp.maximum(plan.t_total, 1)) \
        .astype(jnp.float32)
    # zero active rows (possible under row_mask): report invocation 0, not
    # the 1.0 that 1 - 0/1 would claim for a fully idle batch
    invocation = jnp.where(plan.t_total > 0, 1.0 - exact_frac, 0.0) \
        .astype(jnp.float32)
    tier_rows = jnp.sum(plan.tier_counts, -1)
    return InvokeStats(
        class_counts=plan.counts,
        dispatched=plan.dispatched,
        dropped=jnp.sum(plan.counts - plan.dispatched),
        exact_frac=exact_frac,
        invocation=invocation,
        executed_rows=plan.executed,
        padding_rows=plan.executed
        - jnp.sum(plan.dispatched).astype(jnp.int32),
        # per-tier QoS split (tier 0 only on tier-less plans): routed /
        # post-capacity per class, dropped rows, and the SERVED invocation
        # per tier — approximator rows actually executed over that tier's
        # active rows, the quantity a loose error bound buys more of
        tier_counts=plan.tier_counts,
        tier_dispatched=plan.tier_dispatched,
        tier_dropped=jnp.sum(plan.tier_counts - plan.tier_dispatched, -1),
        tier_served_invocation=(
            jnp.sum(plan.tier_dispatched[:, 1:], -1)
            / jnp.maximum(tier_rows, 1)).astype(jnp.float32),
        lib_counts=plan.lib_counts,
        off_set_exact_rows=plan.off_set_rows)


def execute_dispatch(plan: DispatchPlan, x: jax.Array,
                     exact_fn: Callable[[jax.Array], jax.Array],
                     a_w1: jax.Array, a_b1: jax.Array,
                     a_w2: jax.Array, a_b2: jax.Array, *,
                     interpret: bool = False,
                     weights_prepadded: bool = False) -> jax.Array:
    """Apply one layer's approximators + exact path against a plan.

    x: (T, d) rows in ORIGINAL order (the plan's permutation is applied
    internally); returns (T, d_out) in original order.  Both backends
    consume the same plan — ``plan.backend`` picks the executor — so the
    Pallas path stays bit-exact against the XLA oracle under plan reuse.
    No routing, sorting, or counting happens here: at tick scope this is
    the entire per-layer cost.
    """
    n = plan.n_approx
    assert a_w1.shape[0] - (1 if weights_prepadded else 0) == n, (
        f"approximator stack (leading dim {a_w1.shape[0]}, "
        f"weights_prepadded={weights_prepadded}) does not match the plan's "
        f"n_approx={n}")
    # exact ("nC") rows: capacity gather -> exact_fn -> scatter-back
    xg = scatter_rows(x, plan.exact_slot, plan.exact_keep, plan.exact_cap)
    out = gather_rows(exact_fn(xg), plan.exact_slot, plan.exact_keep)

    if plan.backend == "xla":
        d_out = out.shape[-1]
        for i, cap_i in enumerate(plan.class_caps):
            if weights_prepadded:
                # logical views of the padded stacks; padded regions are
                # exact zeros, so the sliced math is unchanged
                d_in = x.shape[1]
                def approx_i(xb, i=i):
                    return apply_approximator(
                        xb, a_w1[i, :d_in], a_b1[i],
                        a_w2[i][:, :d_out], a_b2[i, :d_out])
            else:
                def approx_i(xb, i=i):
                    return apply_approximator(xb, a_w1[i], a_b1[i],
                                              a_w2[i], a_b2[i])
            keep = (plan.cls == i + 1) & (plan.rank < cap_i)
            slot = jnp.where(keep, plan.rank, cap_i)
            xb = scatter_rows(x, slot, keep, cap_i)
            out = out + gather_rows(approx_i(xb), slot, keep)
    else:  # pallas family — validated by make_dispatch_plan
        # one grouped kernel launch over ALL rows on the plan's precomputed
        # class-sort: exact + over-capacity (and masked-inactive) rows ride
        # the zero-weight pseudo-class n, whose tiles compute exact zeros
        # (tanh(0)@0 + 0), so no post-mask is needed.  "pallas_fused" runs
        # the same plan through the fused kernel — the sort permutation is
        # scalar-prefetched and the standalone gather/scatter legs vanish.
        apply = ops.switched_apply if plan.backend == "pallas" \
            else ops.switched_apply_fused
        sort_plan = (plan.order, plan.pos, plan.tile_cls)
        if weights_prepadded:
            out = out + apply(
                x, plan.eff, a_w1, a_b1, a_w2, a_b2, block_t=plan.block_t,
                interpret=interpret, prepadded=True, d_out=out.shape[-1],
                sort_plan=sort_plan)
        else:
            zcls = lambda w: jnp.concatenate([w, jnp.zeros_like(w[:1])], 0)
            out = out + apply(
                x, plan.eff, zcls(a_w1), zcls(a_b1), zcls(a_w2), zcls(a_b2),
                block_t=plan.block_t, interpret=interpret,
                sort_plan=sort_plan)
    return out


def mcma_dispatch(x: jax.Array, logits: jax.Array,
                  exact_fn: Callable[[jax.Array], jax.Array],
                  a_w1: jax.Array, a_b1: jax.Array,
                  a_w2: jax.Array, a_b2: jax.Array, *,
                  exact_cap: int, invoke_cap, backend: str = "xla",
                  block_t: int = 128, interpret: bool = False,
                  stats_axes: tuple = (), row_mask: jax.Array | None = None,
                  weights_prepadded: bool = False,
                  tier: jax.Array | None = None,
                  tier_margins: jax.Array | None = None,
                  residency: jax.Array | None = None):
    """Full MCMA invocation pipeline over a flat row batch.

    x: (T, d); logits: (T, n_approx+1) router scores (class 0 = exact);
    exact_fn: (cap, d) -> (cap, d_out) exact path applied to the gathered
    class-0 buffer; a_*: stacked approximator weights, leading dim n_approx.
    ``exact_cap``/``invoke_cap``/``backend``/``block_t``/``interpret`` must
    be static under jit (they determine shapes / the traced program).

    ``stats_axes``: mesh axis names to ``psum`` the invoke_stats over when
    the call runs inside a ``shard_map`` (the compute stays fully local to
    each shard — only the scalar/per-class stats are reduced, so every
    shard reports the GLOBAL totals: counts/dispatched/dropped/executed
    summed across shards, exact_frac/invocation over the global row count).
    Empty (the default) outside shard_map.

    ``row_mask``: optional (T,) bool marking ACTIVE rows.  Inactive rows
    (e.g. a decode server's free slots, fed token 0) are forced out of
    every path: they take no class, consume no capacity slot, and are
    excluded from every stat (t_total = active rows) — so ``invocation``/
    ``exact_frac`` and the autotuner signal stay exact on partially-full
    slot tables instead of being polluted by routed garbage.  None (the
    default) treats every row as active and traces the exact same program
    as before the mask existed.

    ``weights_prepadded``: the a_* stacks are already in serving form
    (kernels/ops.prepad_switched_weights — one zero pseudo-class appended,
    feature dims lane-padded), so the Pallas path ships them to the kernel
    with zero per-call copies and the XLA oracle slices logical views.

    ``tier``/``tier_margins``: per-request QoS (see ``route`` /
    ``make_dispatch_plan``) — the per-row tier indexes a traced per-tier
    exact-logit margin, and the returned stats gain the per-tier
    ``tier_counts``/``tier_dispatched``/``tier_dropped``/
    ``tier_served_invocation`` split.  ``invoke_cap`` may be a per-class
    tuple (asymmetric capacities, e.g. from
    runtime/autotune.ladder_from_counts).

    ``residency``: optional (n_resident,) int32 of LIBRARY ids.  The a_*
    stacks then hold the FULL prepadded library (leading dim
    library_size + 1, zero pseudo-class last) and ``logits`` carry
    ``library_size + 1`` columns; the resident rows are gathered out
    (kernels/ops.gather_resident_stacks) and library classes fold onto
    resident slots in the plan (see ``make_dispatch_plan``).  Because the
    map is traced data, a hot-set swap is a new vector through the SAME
    compiled program — zero retraces.

    Returns ``(y, invoke_stats)`` with y: (T, d_out) in the original row
    order and invoke_stats an ``InvokeStats`` (typed, dict-style access —
    see its docstring for the field inventory).
    """
    if residency is not None:
        assert weights_prepadded, (
            "library residency requires prepadded stacks "
            "(ops.prepad_switched_weights over the full library)")
        assert logits.shape[-1] == a_w1.shape[0], (
            f"router width {logits.shape[-1]} != library_size + 1 = "
            f"{a_w1.shape[0]}: with a residency map the logits must cover "
            "the FULL library (pseudo-class excluded)")
        a_w1, a_b1, a_w2, a_b2 = ops.gather_resident_stacks(
            a_w1, a_b1, a_w2, a_b2, residency)
    n = a_w1.shape[0] - (1 if weights_prepadded else 0)
    # schema guard: the router always has n_approx+1 classes, so a stack
    # whose leading dim disagrees (e.g. a pre-serving-form checkpoint fed
    # through weights_prepadded=True, where the last REAL approximator
    # would silently play the zero pseudo-class) fails loudly here
    assert residency is not None or logits.shape[-1] == n + 1, (
        f"router width {logits.shape[-1]} != n_approx + 1 = {n + 1}: "
        f"approximator stack (leading dim {a_w1.shape[0]}, "
        f"weights_prepadded={weights_prepadded}) does not match — "
        "prepadded stacks must come from ops.prepad_switched_weights")
    plan = make_dispatch_plan(logits, row_mask, exact_cap=exact_cap,
                              invoke_cap=invoke_cap, backend=backend,
                              block_t=block_t, stats_axes=stats_axes,
                              tier=tier, tier_margins=tier_margins,
                              residency=residency)
    out = execute_dispatch(plan, x, exact_fn, a_w1, a_b1, a_w2, a_b2,
                           interpret=interpret,
                           weights_prepadded=weights_prepadded)
    return out, plan_invoke_stats(plan)


def mcma_dispatch_sharded(mesh, x: jax.Array, logits: jax.Array,
                          exact_fn: Callable[[object, jax.Array], jax.Array],
                          exact_params,
                          a_w1: jax.Array, a_b1: jax.Array,
                          a_w2: jax.Array, a_b2: jax.Array, *,
                          exact_cap: int, invoke_cap,
                          backend: str = "xla", block_t: int = 128,
                          interpret: bool = False, data_axes=None,
                          row_mask: jax.Array | None = None,
                          weights_prepadded: bool = False,
                          tier: jax.Array | None = None,
                          tier_margins: jax.Array | None = None,
                          residency: jax.Array | None = None):
    """``mcma_dispatch`` shard_mapped over a mesh's data axes.

    x/logits are row-sharded over the data axes (specs from
    sharding/rules.mcma_dispatch_specs); the router/approximator/exact
    weights are replicated.  ``exact_cap``/``invoke_cap`` are PER-SHARD
    capacities (each shard dispatches its local rows — derive them from a
    global operating point with sharding/rules.shard_capacity).
    ``exact_fn`` takes ``(exact_params, xb)`` so the exact weights ride
    through shard_map as an explicit (replicated) argument rather than a
    closure.  ``row_mask`` (optional, (T,) bool, row-sharded like x) marks
    active rows; inactive rows are excluded from dispatch and from the
    psum-reduced stats on every shard.  ``tier`` (optional, (T,) int32,
    row-sharded like x) + ``tier_margins`` ((n_tiers,) float32,
    replicated) apply the per-request QoS margins per shard; the per-tier
    stats are psum-reduced like every other count.  ``residency``
    (optional, (n_resident,) int32, replicated) enables library routing
    exactly as in ``mcma_dispatch`` — the off-set/library stats are
    psum-reduced too.

    Returns ``(y, invoke_stats)``: y row-sharded like x, invoke_stats
    psum-reduced to the global totals (replicated on every shard).
    """
    from repro.sharding.rules import dp_axes, mcma_dispatch_specs
    dp = tuple(data_axes) if data_axes is not None else dp_axes(mesh)
    specs = mcma_dispatch_specs(mesh, data_axes=dp,
                                with_mask=row_mask is not None,
                                with_tier=tier is not None,
                                with_residency=residency is not None)
    has_mask, has_tier = row_mask is not None, tier is not None
    has_res = residency is not None

    def local(x_l, lg_l, ep, w1, b1, w2, b2, *extra):
        extra = list(extra)
        m_l = extra.pop(0) if has_mask else None
        t_l, tm = (extra.pop(0), extra.pop(0)) if has_tier else (None, None)
        res = extra.pop(0) if has_res else None
        return mcma_dispatch(
            x_l, lg_l, partial(exact_fn, ep), w1, b1, w2, b2,
            exact_cap=exact_cap, invoke_cap=invoke_cap, backend=backend,
            block_t=block_t, interpret=interpret, stats_axes=dp,
            row_mask=m_l, weights_prepadded=weights_prepadded,
            tier=t_l, tier_margins=tm, residency=res)

    fn = jax.shard_map(local, mesh=mesh, in_specs=specs["in"],
                       out_specs=specs["out"],
                       axis_names=frozenset(dp), check_vma=False)
    args = (x, logits, exact_params, a_w1, a_b1, a_w2, a_b2)
    if has_mask:
        args = args + (row_mask,)
    if has_tier:
        assert tier_margins is not None, \
            "sharded tiered dispatch needs the (n_tiers,) margins vector"
        args = args + (tier, tier_margins)
    if has_res:
        args = args + (residency,)
    return fn(*args)
