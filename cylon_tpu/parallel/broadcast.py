"""Replicated small-side blocks: the broadcast half of join algorithm
selection.

The shuffle engine (shuffle.py) moves BOTH sides of every distributed
join so matching keys co-locate — the reference's one bulk pattern
(table_api.cpp:299-352).  When one side is dimension-table sized that
symmetry is pure waste: the fact side pays a two-phase exchange to meet
a few thousand rows that would fit replicated on every shard.  This
module implements the standard remedy (algorithm selection between
shuffle and broadcast joins, arXiv:2212.13732 §hybrid; replicated
operand layouts are cheap on ICI meshes, arXiv:2112.01075): one
``all_gather`` of the small side's column leaves into a REPLICATED
block per shard, after which the existing local kernels run per shard
against the *unmoved* large side — no partition pass, no all_to_all, no
receive-side compaction on the hot path.

Mechanics:

  * ``replicate_table`` gathers every leaf of a (collapsed) DTable and
    compacts the per-shard padding away into a block bucketed by
    ``ops/compact.next_bucket`` — repeated small-side sizes reuse one
    compiled gather program.  The result is an ordinary DTable whose
    every shard holds ALL rows (``counts[i] = total`` for the join
    probe form, or ``[total, 0, …]`` for the single-owner form the
    groupby combine uses), so the existing shard_map kernels consume it
    unchanged.
  * a module-level **replica cache** (the optimistic-dispatch-hint
    idiom of ``shuffle._block_hints``) keyed by the identity of the
    small side's device arrays: a dimension table joined N times per
    query — nation/region/supplier in TPC-H q7/q8/q9 — is gathered
    once and reused across joins AND across bench repetitions (the
    base-table arrays persist; each query run re-projects them).
    Entries pin their source arrays (identity keys must not be reused
    by the allocator), so the cache is bounded FIFO.
  * ``rows_if_small`` is the planner predicate: it answers "is this
    side provably ≤ threshold rows?" WITHOUT ever blocking on a host
    read — from ingest-cached counts when available, else from the
    static capacity bound ``P * cap`` (rows never exceed capacity).
    Algorithm selection therefore costs zero round trips and is
    deterministic across controllers (multi-host) and across deferred
    replays (ops/compact.run_pipeline).

Path selection is observable: callers bump ``trace.count("join.broadcast")``
/ ``trace.count("join.shuffle")``, and the gather itself records a
``join.broadcast_gather`` span + counter (cache hits record
``join.broadcast_replica_hit`` instead), so bench artifacts show which
path each query took.  See docs/tpu_perf_notes.md "broadcast vs shuffle
joins" for threshold semantics and the planner matrix.
"""
from __future__ import annotations

import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from .._jax_compat import shard_map, trace_state_clean
from jax.sharding import PartitionSpec as P

from .. import trace
from ..analysis import plan_check
from ..observe.compile import kernel_factory, named_jit
from ..observe.locks import OrderedLock
from ..analysis._abstract import is_abstract
from ..config import broadcast_join_threshold
from ..ops import compact as ops_compact
from .dtable import DColumn, DTable

# counts layouts for the replicated DTable
ALL = "all"    # counts[i] = total on every shard — the join-probe form
HEAD = "head"  # counts = [total, 0, …] — one shard owns the rows (the
#                groupby combine form: every shard holds the data, only
#                shard 0's copy is "valid", so nothing is double-counted)


def _bcast(mask: jax.Array, like: jax.Array) -> jax.Array:
    return mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim))


@kernel_factory
def _gather_fn(mesh, axis: str, cap: int, outcap: int, head_only: bool):
    """Per shard: all_gather every leaf, drop the per-shard padding, and
    pack the survivors into a [outcap] block — identical on every shard.

    One collective per leaf (dimension tables are narrow; the
    width-classed packing of shuffle.py would save little here) plus the
    one-int count gather.  Output specs are P(axis): each shard's block
    IS the full gathered table, which is exactly what lets the existing
    per-shard join kernels run against it unchanged."""

    def kernel(cnt_blk, leaves):
        gcnts = jax.lax.all_gather(cnt_blk, axis, tiled=True)      # [P]
        valid = (jnp.arange(cap)[None, :] < gcnts[:, None]).reshape(-1)
        idx = ops_compact.compact_indices(valid, outcap, fill=0)
        total = jnp.sum(gcnts).astype(jnp.int32)
        keep = jnp.arange(outcap, dtype=jnp.int32) < total
        outs = []
        for leaf in leaves:
            as_bool = leaf.dtype == jnp.bool_
            x = leaf.astype(jnp.uint8) if as_bool else leaf
            g = jax.lax.all_gather(x, axis, tiled=True)            # [P*cap]
            c = jnp.take(g, idx, axis=0)
            c = jnp.where(_bcast(keep, c), c, jnp.zeros((), c.dtype))
            outs.append(c.astype(jnp.bool_) if as_bool else c)
        if head_only:
            me = jax.lax.axis_index(axis)
            cnt_out = jnp.where(me == 0, total, jnp.int32(0))
        else:
            cnt_out = total
        return tuple(outs), cnt_out[None]

    spec = P(axis)
    # check_vma=False: the all_gathered intermediates are replicated,
    # which shard_map cannot statically infer (same note as shuffle.py)
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec, spec),
                               out_specs=(spec, spec), check_vma=False))


def rows_if_small(dt: DTable, threshold: Optional[int],
                  quiet: bool = False) -> Optional[int]:
    """Global-row upper bound if ``dt`` provably holds ≤ ``threshold``
    rows AND its replica fits the memory budget, else None — WITHOUT a
    host sync (the planner contract above).  ``quiet`` suppresses the
    veto counter/annotation side effects — for advisory pre-checks
    (dist_multiway_join's decision counters) that the authoritative
    re-check inside the join will repeat.

    ``threshold`` None resolves to the session-wide knob
    (config.broadcast_join_threshold); ≤ 0 disables.  A deferred-select
    mask only removes rows, so the capacity bound stays valid for
    mask-carrying tables (the caller collapses before replicating).

    Budget veto (docs/robustness.md): replicating costs every shard the
    all_gathered ``[P*cap]`` blocks plus the compacted replica — "small
    enough to broadcast" must also mean "fits in memory P times over".
    A veto records itself on the current plan node
    (``plan_check.annotate``), bumps ``broadcast.budget_veto``, and the
    caller falls back to the shuffle plan.  The session budget is
    deterministic, so the planner contract (same decision on every
    controller / every deferred replay) holds; only an installed
    FaultPlan — a test-only state — can perturb it per call.
    """
    if threshold is None:
        threshold = broadcast_join_threshold()
    if threshold <= 0:
        return None
    ch = dt._counts_host
    if ch is not None and dt.pending_mask is None:
        n = int(ch.sum())
        rows = n if n <= threshold else None
    else:
        bound = dt.nparts * dt.cap
        rows = bound if bound <= threshold else None
    if rows is None:
        return None
    from .. import observe, resilience
    from . import cost
    rbytes = max(observe.row_bytes(
        [lf for c in dt.columns for lf in (c.data, c.validity)
         if lf is not None]), 1)
    outcap = ops_compact.next_bucket(max(rows, 1), minimum=8)
    # the replica is one more exchange-shaped decision priced through
    # the shared cost model (cost.price_replicate — the all_gathered
    # [P*cap] blocks plus the compacted replica), so the veto, the
    # shuffle chooser and admission can never disagree on footprint math
    priced = cost.price_replicate(dt.nparts, dt.cap, outcap,
                                  rbytes).peak_bytes
    budget = resilience.exchange_budget()
    if priced > budget:
        if not quiet:
            trace.count("broadcast.budget_veto")
            plan_check.annotate(
                broadcast_veto=f"replica would price {priced} B/device "
                               f"over the {budget} B budget")
        return None
    return rows


# Replicated blocks by small-side array identity (see module docstring);
# an entry holds strong refs to its source arrays, so ids stay unique
# while cached.  Bounded FIFO like dist_ops._group_cap_hints.  Guarded
# by a lock: concurrent queries (the serving layer's export pipeline
# overlapping the dispatcher, client threads running eager plans) share
# this module-level dict, and the eviction loop's pop(next(iter(...)))
# racing a clear_replica_cache() raised RuntimeError before the lock;
# the gather itself runs OUTSIDE the lock (two racing misses both
# gather — benign, last insert wins — rather than serializing device
# work behind a host lock).
# The lint contract (graftlint shared-state-unguarded): every write
# to the replica cache holds its lock.  Membership/eviction already
# did (the PR 9 race fix); the catalogue + OrderedLock make the
# discipline checkable.
GUARDED_STATE = {"_replica_cache": "_replica_lock"}

_replica_cache: dict = {}
_replica_lock = OrderedLock("broadcast.replica_cache")
_REPLICA_CACHE_MAX = 64


def clear_replica_cache() -> None:
    """Drop every cached replica (frees the pinned source arrays)."""
    with _replica_lock:
        _replica_cache.clear()


def _cache_key(dt: DTable, mode: str) -> Tuple:
    # names and dictionary identity belong in the key: metadata-only
    # derivations share the device arrays (DTable.rename; the
    # empty-dictionary branch of dictionary unification swaps the
    # dictionary while keeping the codes) and must not hit a replica
    # built under the old metadata.  The cached entry pins dt.columns,
    # which pins the dictionaries, so the ids stay unique while cached.
    return (dt.ctx.mesh, mode, dt.cap,
            tuple((c.name, id(c.data), id(c.validity), id(c.dictionary))
                  for c in dt.columns))


def small_side_reason(dt: DTable, rows: int) -> str:
    """Human-readable planner reason for a ``rows_if_small`` hit — which
    sync-free evidence proved the side small (EXPLAIN / EXPLAIN ANALYZE
    annotations; docs/observability.md)."""
    if dt._counts_host is not None and dt.pending_mask is None:
        return f"{rows} rows <= threshold (ingest-cached counts)"
    return (f"capacity bound {dt.nparts}x{dt.cap} = {rows} "
            "<= threshold")


@plan_check.instrument
def replicate_table(dt: DTable, mode: str = ALL,
                    span_name: str = "join.broadcast_gather",
                    cache: bool = True) -> DTable:
    """Gather ``dt``'s rows into a replicated DTable (every shard holds
    all rows).  ``dt`` must carry no pending mask (callers collapse
    first — the gather reads only counts-valid rows).  Schema,
    dictionaries and column order are preserved, so the result drops
    into any shard_map kernel in the small side's place.  Pass
    ``cache=False`` for one-shot intermediates (the groupby combine) —
    caching them would only pin dead arrays."""
    assert dt.pending_mask is None, "collapse the pending mask first"
    plan_check.note("replicate_table", dt, mode=mode)
    abstract = any(is_abstract(c.data) for c in dt.columns)
    # a CONCRETE-leaf table under an ambient abstract trace (a plan-
    # check run whose ``concrete=`` tables flow into a broadcast, or an
    # optimizer-pruned scan replicated directly) stages the gather into
    # that trace — the outputs are tracers even though the inputs are
    # real arrays.  Caching those would poison the next concrete run
    # with dead-trace tracers, so the cache gate mirrors the byte-
    # accounting guard below: concrete leaves AND a clean trace state.
    if cache and (abstract or not trace_state_clean()):
        cache = False
    key = _cache_key(dt, mode) if cache else None
    if cache:
        with _replica_lock:
            hit = _replica_cache.get(key)
        if hit is not None:
            trace.count("join.broadcast_replica_hit")
            plan_check.annotate(decision="replica-cache hit")
            return hit[1]
    plan_check.annotate(decision="gather")
    ch = dt._counts_host
    total_bound = int(ch.sum()) if ch is not None else dt.nparts * dt.cap
    outcap = ops_compact.next_bucket(max(total_bound, 1), minimum=8)
    leaves = []
    slots = []  # (column index, is_validity)
    for i, c in enumerate(dt.columns):
        leaves.append(c.data)
        slots.append((i, False))
        if c.validity is not None:
            leaves.append(c.validity)
            slots.append((i, True))
    # exchange-volume accounting: each shard's rows travel to the other
    # P-1 shards, so the gather's wire payload is rows x (P-1) x row
    # width (validity lanes 1 byte).  total_bound is exact whenever the
    # planner had ingest counts; else it is the same capacity bound the
    # decision itself used — documented in docs/observability.md.
    # Abstract plan runs move ZERO bytes and must report zero, exactly
    # like the shuffle path (whose post() sees zeroed counts there) —
    # including the closure-captured-concrete-table case, where the
    # leaves are real arrays but the gather is merely STAGED into the
    # ambient eval_shape trace, never executed (trace_state_clean is
    # the same ambient-trace probe DTable.to_table uses).
    if not abstract and trace_state_clean():
        from .. import observe
        moved = total_bound * max(dt.nparts - 1, 0)
        moved_bytes = moved * observe.row_bytes(leaves)
        trace.count("broadcast.rows_sent", moved)
        trace.count("broadcast.bytes_sent", moved_bytes)
        if span_name == "groupby.broadcast_gather":
            # groupby-owned combine gathers feed the per-family bench
            # accounting (tpch_*_groupby_bytes_saved)
            trace.count("groupby.bytes_moved", moved_bytes)
    with trace.span_sync(span_name) as sp:
        trace.count(span_name)  # counter mirrors the span name
        outs, counts = _gather_fn(dt.ctx.mesh, dt.ctx.axis, dt.cap,
                                  outcap, mode == HEAD)(
            dt.counts, tuple(leaves))
        sp.sync(outs)
    data, validity = {}, {}
    for leaf, (i, is_v) in zip(outs, slots):
        (validity if is_v else data)[i] = leaf
    cols = [DColumn(c.name, c.dtype, data[i], validity.get(i),
                    c.dictionary, c.arrow_type)
            for i, c in enumerate(dt.columns)]
    rep = DTable(dt.ctx, cols, outcap, counts)
    if cache:
        with _replica_lock:
            while len(_replica_cache) >= _REPLICA_CACHE_MAX:
                _replica_cache.pop(next(iter(_replica_cache)))
            # pin the source columns: their ids ARE the key
            _replica_cache[key] = (dt.columns, rep)
            size = len(_replica_cache)
        trace.gauge("broadcast.replica_cache_size", size)
    return rep
