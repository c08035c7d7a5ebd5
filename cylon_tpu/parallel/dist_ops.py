"""Distributed operators: partition → shuffle → masked local kernel.

TPU-native mirror of the reference's distributed table ops, which all follow
one pattern — repartition rows so matching keys co-locate, then run the
local operator per rank (reference: cpp/src/cylon/table_api.cpp:299-352
DistributedJoinTables, :904-975 DoDistributedSetOperation, :214-297
Shuffle/ShuffleTwoTables).  Here the pattern is:

  partition   elementwise on the sharded arrays: murmur3 row hash % P
              (ops/hash.py) for the HASH algorithm / distributed set ops,
              or sampled-splitter range partition for the SORT algorithm
              and dist_sort (sample-sort — absent in the reference v0,
              required by BASELINE configs 4);
  shuffle     two-phase static-shape all_to_all (shuffle.shuffle_leaves);
  local op    the ops/ kernel per shard under shard_map, driven by the
              padded-block (count-masked) entry points.

Everything stays on device except the tiny per-shard count vectors (the
analogue of the reference's 8-int header exchange) and the sample-sort
splitters.
"""
from __future__ import annotations

import functools
import threading
from contextlib import contextmanager
from typing import List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from .._jax_compat import shard_map, trace_state_clean
from jax.sharding import PartitionSpec as P

from .. import trace
from ..analysis import plan_check
from ..config import JoinAlgorithm, JoinConfig
from ..dtypes import DataType, is_dictionary_encoded
from ..observe.compile import kernel_factory, named_jit
from ..ops import compact as ops_compact
from ..ops import gather as ops_gather
from ..ops import groupby as ops_groupby
from ..ops import hash as ops_hash
from ..ops import hashjoin as ops_hashjoin
from ..ops import join as ops_join
from ..ops import setops as ops_setops
from ..ops import sort as ops_sort
from ..status import Code, CylonError, Status
from . import broadcast
from .dtable import DColumn, DTable
from .shuffle import shuffle_leaves

_SAMPLES_PER_SHARD = 64  # sample-sort oversampling factor


# ---------------------------------------------------------------------------
# helpers: row masks, partition ids, dictionary unification across DTables
# ---------------------------------------------------------------------------

@kernel_factory
def _mask_fn(mesh, axis: str, cap: int):
    """counts [P] → valid-row mask [P*cap] (True for rows < shard count)."""

    def kernel(cnt_blk):
        return jnp.arange(cap) < cnt_blk[0]

    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=P(axis), out_specs=P(axis)))


def _row_mask(dt: DTable) -> jax.Array:
    return _mask_fn(dt.ctx.mesh, dt.ctx.axis, dt.cap)(dt.counts)


def _resolve_ids(dt: DTable, cols: Sequence[Union[int, str]]) -> List[int]:
    return [dt.column_index(c) for c in cols]


def _shuffle_reason(node, default: str = "no side provably under the "
                                         "broadcast threshold") -> str:
    """The honest planner reason for a shuffle decision: when the
    broadcast predicate was budget-vetoed (rows_if_small recorded
    ``broadcast_veto`` on the node — docs/robustness.md), the side WAS
    small enough and saying otherwise would mislead the EXPLAIN reader."""
    if node is not None and "broadcast_veto" in node.info:
        return "broadcast replica vetoed by the memory budget"
    return default


def _cleared(dt: DTable) -> DTable:
    """A handle on the same blocks with the pending mask dropped — used by
    callers that have already folded the mask into their partition ids
    (the shuffle then must NOT collapse it a second time)."""
    return DTable(dt.ctx, dt.columns, dt.cap, dt.counts)


@kernel_factory
def _hash_pids_fn(mesh, axis: str, cap: int, nparts: int, use_pallas: bool):
    def kernel(cnt_blk, cols, valids):
        mask = jnp.arange(cap) < cnt_blk[0]
        if use_pallas:  # a TPU mesh: the compiled kernel, never interpret
            from ..ops.hash_pallas import partition_ids_fused
            pid = partition_ids_fused(cols, valids, nparts, interpret=False)
        else:
            pid = ops_hash.partition_ids(ops_hash.row_hash(cols, valids),
                                         nparts)
        return jnp.where(mask, pid, jnp.int32(nparts))

    spec = P(axis)
    # check_vma=False: pallas_call can't declare varying-mesh-axes metadata
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 3, out_specs=spec,
                               check_vma=False))


def hash_uses_pallas(mesh) -> bool:
    """Whether the shuffle's partition hash on ``mesh`` is the compiled
    Pallas kernel (a TPU mesh) or the jnp reference path (elsewhere)."""
    return mesh.devices.flat[0].platform == "tpu"


def _hash_pids(dt: DTable, key_ids: Sequence[int]) -> jax.Array:
    """Target shard per row by murmur3 row hash; padding rows → P (dropped).

    On TPU the hash+combine+mod chain runs as the fused Pallas kernel
    (ops/hash_pallas.py, SURVEY §7 hard part 3); elsewhere the jnp
    reference path.  reference: HashPartition (table_api.cpp:461-528) +
    HashPartitionArrays (arrow_partition_kernels.cpp) — the split kernels
    are subsumed by the argsort grouping inside the shuffle exchange.
    """
    cols = tuple(dt.columns[i].data for i in key_ids)
    valids = tuple(dt.columns[i].validity for i in key_ids)
    fn = _hash_pids_fn(dt.ctx.mesh, dt.ctx.axis, dt.cap,
                       dt.ctx.get_world_size(), hash_uses_pallas(dt.ctx.mesh))
    return fn(dt.counts, cols, valids)


def _unify_dtable_dicts(a: DTable, b: DTable,
                        a_ids: Sequence[int], b_ids: Sequence[int]
                        ) -> Tuple[DTable, DTable]:
    """Re-encode paired dictionary columns onto shared dictionaries.

    The host-side map arrays are tiny (dictionary-sized); the code remap is
    one elementwise gather on the sharded arrays.
    """
    acols, bcols = list(a.columns), list(b.columns)
    changed = False
    for ai, bi in zip(a_ids, b_ids):
        ca, cb = acols[ai], bcols[bi]
        if not is_dictionary_encoded(ca.dtype.type):
            continue
        if ca.dictionary is cb.dictionary or (
                len(ca.dictionary) == len(cb.dictionary)
                and bool(np.all(ca.dictionary == cb.dictionary))):
            continue
        merged = np.unique(np.concatenate([ca.dictionary, cb.dictionary]))
        map_a = jnp.asarray(np.searchsorted(merged, ca.dictionary)
                            .astype(np.int32))
        map_b = jnp.asarray(np.searchsorted(merged, cb.dictionary)
                            .astype(np.int32))
        import dataclasses
        acols[ai] = dataclasses.replace(
            ca, data=(map_a[ca.data] if len(ca.dictionary) else ca.data),
            dictionary=merged)
        bcols[bi] = dataclasses.replace(
            cb, data=(map_b[cb.data] if len(cb.dictionary) else cb.data),
            dictionary=merged)
        changed = True
    if not changed:
        return a, b
    return (DTable(a.ctx, acols, a.cap, a.counts, a.pending_mask,
                   a.pending_cnts),
            DTable(b.ctx, bcols, b.cap, b.counts, b.pending_mask,
                   b.pending_cnts))


# ---------------------------------------------------------------------------
# shuffle_table (reference: Shuffle, table_api.cpp:214-297)
# ---------------------------------------------------------------------------

def _shuffle_by_pids(dt: DTable, pid: jax.Array, combine=None,
                     owner: "str | None" = None) -> DTable:
    """Exchange rows to their target shards; rebuild the DTable.
    ``combine``/``owner`` thread through to :func:`shuffle_leaves` (the
    partial-group fold spec and the byte-attribution tag).  The
    COLLECTIVE the exchange lowers to — single-shot all_to_all,
    chunked rounds, ring ppermute, allgather — is the costed chooser's
    per-execution decision (parallel/cost.py); every dist op routed
    through here inherits it without opting in."""
    if dt.pending_mask is not None:
        # ``pid`` was computed against THESE blocks — a deferred select
        # must have been folded into it (dropped-partition routing, via a
        # _cleared handle) or collapsed before the pid computation; seeing
        # one here is a caller bug, and collapsing now would desync shapes
        raise CylonError(Status(Code.ExecutionError,
            "internal: shuffle of a mask-carrying DTable (fold the "
            "pending mask into the partition ids or collapse first)"))
    if dt.ctx.get_world_size() == 1:
        return dt  # one shard: every row is already home; no collective
    leaves: List[jax.Array] = []
    slots: List[Tuple[int, bool]] = []  # (column index, is_validity)
    for i, c in enumerate(dt.columns):
        leaves.append(c.data)
        slots.append((i, False))
        if c.validity is not None:
            leaves.append(c.validity)
            slots.append((i, True))
    new_leaves, newcounts, outcap = shuffle_leaves(dt.ctx, pid, leaves,
                                                   combine, owner)
    # structural exchange metric (static host-side sizes — no sync):
    # total exchanged slot capacity across shards, summed over leaves
    trace.count("shuffle.capacity_rows",
                dt.ctx.get_world_size() * outcap)
    trace.count("shuffle.capacity_cells",
                dt.ctx.get_world_size() * outcap * len(leaves))
    # peak SINGLE exchange block (the sum above over-states transients
    # for staged plans like the streaming join, whose chunks free their
    # blocks before the next one allocates)
    trace.count_max("shuffle.capacity_cells_max",
                    dt.ctx.get_world_size() * outcap * len(leaves))
    data = {}
    validity = {}
    for leaf, (i, is_v) in zip(new_leaves, slots):
        (validity if is_v else data)[i] = leaf
    cols = [DColumn(c.name, c.dtype, data[i], validity.get(i),
                    c.dictionary, c.arrow_type)
            for i, c in enumerate(dt.columns)]
    return DTable(dt.ctx, cols, outcap, newcounts)


def _shuffle_masked(dt: DTable, pid: jax.Array) -> DTable:
    """Shuffle with any deferred-select mask folded into the routing:
    masked-out rows go to the dropped partition and never cross the wire
    (the same pushdown dist_groupby's ``where`` rides)."""
    if dt.pending_mask is not None:
        pid = jnp.where(dt.pending_mask, pid,
                        jnp.int32(dt.ctx.get_world_size()))
        dt = _cleared(dt)
    return _shuffle_by_pids(dt, pid)


@plan_check.instrument
def shuffle_table(dt: DTable, key_columns: Sequence[Union[int, str]]
                  ) -> DTable:
    """Hash-repartition rows so equal keys co-locate on one shard.

    reference: Shuffle (table_api.cpp:214-297) — HashPartition + split +
    ArrowAllToAll + concat collapsed into partition-ids + one two-phase
    all_to_all exchange.
    """
    plan_check.note("shuffle_table", dt, keys=tuple(key_columns),
                    decision="shuffle" if dt.ctx.get_world_size() > 1
                    else "local")
    dt._collapse_pending()
    key_ids = _resolve_ids(dt, key_columns)
    return _shuffle_by_pids(dt, _hash_pids(dt, key_ids))


# ---------------------------------------------------------------------------
# distributed join (reference: DistributedJoinTables, table_api.cpp:299-352)
# ---------------------------------------------------------------------------

@kernel_factory
def _join_phase1_fn(mesh, axis: str, how: str, alg: str, carried: bool):
    """Phase 1 per shard: the join "plan" + replicated output counts.

    ``hash``: dense ranks (the direct-address kernel's domain), plan =
    (l_rank, r_rank).  ``sort``: the fused single-sort plan; with
    ``carried`` the output leaves additionally ride the plan sorts
    (ops/join.py sort_join_plan_carried) so phase 2's output gathers fuse
    into the decode gathers.  Measured on a v5e at 4M+4M rows the carried
    variant wins ONLY when each side carries a single no-validity column
    (154 vs 212 ms) — every extra carried array rides the 8M merged sort,
    the build-order sort AND a widened run-heavy decode gather, and by two
    arrays per side the plain plan + per-side packed takes is ~20% faster
    (181 vs 216 ms at 2, 208 vs 237 at 3).  ``carried`` encodes that
    crossover (chosen by the caller from the leaf counts).
    """

    def kernel(l_cnt, r_cnt, lkeys, lvalids, rkeys, rvalids,
               l_leaves, r_leaves):
        if alg == "hash":
            lr, rr = ops_join.dense_ranks(lkeys, lvalids, rkeys, rvalids,
                                          l_count=l_cnt[0], r_count=r_cnt[0])
            state = (lr, rr)
            cnt = ops_hashjoin.hash_join_count(
                lr, rr, how, l_count=l_cnt[0], r_count=r_cnt[0])
        else:
            if carried:
                plan, psort, bsort = ops_join.sort_join_plan_carried(
                    lkeys, lvalids, rkeys, rvalids, how,
                    l_count=l_cnt[0], r_count=r_cnt[0],
                    l_leaves=l_leaves, r_leaves=r_leaves)
                state = (plan, psort, bsort)
            else:
                plan = ops_join.sort_join_plan(
                    lkeys, lvalids, rkeys, rvalids, how,
                    l_count=l_cnt[0], r_count=r_cnt[0])
                state = (plan,)
            cnt = ops_join.plan_total(plan, how, l_count=l_cnt[0],
                                      r_count=r_cnt[0])
        # counts replicated (all_gather of one int per shard) so any
        # controller process can device_get them under multi-host
        return state, jax.lax.all_gather(cnt.astype(jnp.int32), axis)

    spec = P(axis)
    # check_vma=False: the all_gathered counts are replicated, which
    # shard_map cannot statically infer
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 8,
                               out_specs=(spec, P()),
                               check_vma=False))


@kernel_factory
def _join_phase2_fn(mesh, axis: str, how: str, alg: str, capacity: int,
                    fill_left: bool, fill_right: bool, carried: bool):
    def kernel(l_cnt, r_cnt, state, l_leaves, r_leaves):
        if carried:
            plan, psort, bsort = state
            louts, routs, cnt = ops_join.plan_gather_carried(
                plan, psort, bsort, how, capacity,
                l_count=l_cnt[0], r_count=r_cnt[0])
            return tuple(louts), tuple(routs), cnt[None]
        if alg == "hash":
            li, ri, cnt = ops_hashjoin.hash_join_indices(
                state[0], state[1], how, capacity,
                l_count=l_cnt[0], r_count=r_cnt[0])
        else:
            (plan,) = state
            li, ri, cnt = ops_join.plan_indices(
                plan, how, capacity, l_count=l_cnt[0], r_count=r_cnt[0])
        louts = tuple(ops_gather.take_many(l_leaves, li,
                                           fill_null=fill_left))
        routs = tuple(ops_gather.take_many(r_leaves, ri,
                                           fill_null=fill_right))
        return louts, routs, cnt[None]

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 5, out_specs=(spec,) * 3))


@plan_check.instrument
def dist_join(left: DTable, right: DTable, config: JoinConfig,
              dense_key_range=None) -> DTable:
    """Distributed equi-join: co-partition both sides on the key, then a
    masked local join per shard.  Output columns are ``lt-…``/``rt-…`` like
    the local join (reference join_utils.cpp:23-95).

    Algorithm choice maps to the partitioning strategy (the reference keeps
    the same shuffle and varies only the local kernel, join_config.hpp:22-89):

      HASH  murmur3 hash-partition shuffle + direct-address local join;
      SORT  sampled-splitter range partition (distributed sample-sort) +
            local sort-merge join — shards are ordered by key ranges, so
            the join output is additionally globally key-ordered.

    Before either shuffle strategy runs, the planner considers a
    BROADCAST join (broadcast.py): when one side's global row count is
    provably under ``config.broadcast_threshold`` (None → the session
    knob ``config.broadcast_join_threshold()``, 0 → disabled), that
    side is all_gathered once into a replicated block — replica-cached
    across repeated joins of the same table — and the local kernel runs
    per shard against the UNMOVED other side; neither side is
    shuffled.  INNER may replicate either side, LEFT only the right;
    RIGHT/FULL always shuffle (a replicated side's unmatched rows would
    be emitted once per shard).  Like the dense fast path below, a
    broadcast join does not carry SORT's global key-ordering guarantee.

    ``dense_key_range=(lo, hi)``: caller hint that the RIGHT side's single
    join key is **unique, non-null and within [lo, hi]** — the FK → PK
    shape (fact table joining a base/dimension table on its primary key).
    Eligible joins (INNER/LEFT, single non-dictionary int key, slot space
    within budget) then skip both plan sorts and the run-length expansion
    entirely: one scatter builds a key→row map, one gather probes it
    (the direct-address idiom of the dense groupby/semi-join paths).  A
    LEFT join additionally keeps the probe side zero-copy — N:1 joins
    with referential integrity (every probe key present, the TPC-H
    fact→dimension joins) should prefer LEFT for that reason; with no
    unmatched probe rows the result equals INNER plus all-valid right
    columns.  Hint violations (duplicate / null / out-of-range right
    keys) fail loudly — they would silently drop matches.  world > 1
    co-partitions by the MODULO router, compressing the per-shard slot
    space to R/P exactly like the dense groupby.  NOTE: the fast path
    partitions by key residue, NOT by key range — the SORT algorithm's
    global key-ordering guarantee does not apply to a dense-hinted join
    (order an output that needs it with dist_sort, as the TPC-H plans
    do).
    """
    if left.is_spilled and config.join_type.value in ("inner", "left") \
            and not right.is_spilled:
        # out-of-core probe side (docs/out_of_core.md): stream the
        # spilled left through the morsel scan instead of faulting the
        # whole block in (INNER/LEFT only — the streaming restriction;
        # morsel_join falls back with a fault-in for the rest)
        from ..spill import morsel as spill_morsel
        return spill_morsel.morsel_join(left, right, config,
                                        dense_key_range=dense_key_range)
    node = plan_check.note("dist_join", left, right,
                           how=config.join_type.value,
                           alg=config.algorithm.value,
                           dense=dense_key_range is not None or None)
    if dense_key_range is not None:
        out = _try_fk_join(left, right, config, dense_key_range, node)
        if out is not None:
            return out
    out = _try_broadcast_join(left, right, config)
    if out is not None:
        return out
    left, right, li_keys, ri_keys, alg, splitters = _join_prologue(
        left, right, config)
    if left.ctx.get_world_size() > 1:
        trace.count("join.shuffle")
        plan_check.annotate(node, decision="shuffle",
                            reason=_shuffle_reason(node))
    else:
        plan_check.annotate(node, decision="local", reason="world=1")
    lsh = _copartition(left, li_keys, alg, splitters)
    rsh = _copartition(right, ri_keys, alg, splitters)
    return _join_copartitioned(lsh, rsh, li_keys, ri_keys,
                               config.join_type.value, alg)


@kernel_factory
def _fk_probe_fn(mesh, axis: str, cap_l: int, cap_r: int, lo: int, hi: int,
                 stride: int, has_lv: bool, has_rv: bool,
                 has_lmask: bool = False):
    """Dense-unique-key join probe: ONE scatter of the right rows into a
    key→row-index map over [lo, hi], ONE gather of the probe keys — the
    N:1 join plan with no sort at all.  Returns the per-probe-row build
    index (−1 = unmatched), the matched mask, and the replicated
    validation vector [matched, right_oob, right_dups, right_nulls] per
    shard (the last three are hint-contract violations: each silently
    loses matches, so callers raise on any non-zero).  ``stride`` = world
    size under modulo routing (one residue class per shard, slot space
    R/P)."""
    R = -(-(hi - lo + 1) // stride)

    def kernel(l_cnt, r_cnt, lk, lv, rk, rv, *maybe_lmask):
        lvalid = jnp.arange(cap_l) < l_cnt[0]
        if has_lmask:  # deferred-select fusion: filter rides the probe
            lvalid = lvalid & maybe_lmask[0]
        rvalid = jnp.arange(cap_r) < r_cnt[0]
        r_nonnull = rvalid & rv if has_rv else rvalid
        l_nonnull = lvalid & lv if has_lv else lvalid
        r_in = (rk >= lo) & (rk <= hi)
        l_in = (lk >= lo) & (lk <= hi)
        # subtract in the key dtype BEFORE narrowing: an int64 key past
        # 2^31 would wrap under astype(int32) and alias a valid slot
        # (in-range keys yield a base < R, which int32 always holds)
        r_base = (rk - lo).astype(jnp.int32)
        l_base = (lk - lo).astype(jnp.int32)
        if stride > 1:
            r_base = r_base // stride
            l_base = l_base // stride
        r_ok = r_nonnull & r_in
        slot = jnp.where(r_ok, r_base, jnp.int32(R))
        amap = jnp.full(R, -1, jnp.int32).at[slot].set(
            jnp.arange(cap_r, dtype=jnp.int32), mode="drop")
        oob = jnp.sum(r_nonnull & ~r_in).astype(jnp.int32)
        dups = (jnp.sum(r_ok) - jnp.sum(amap >= 0)).astype(jnp.int32)
        rnull = (jnp.sum(rvalid & ~rv).astype(jnp.int32) if has_rv
                 else jnp.zeros((), jnp.int32))
        m = jnp.take(amap, jnp.clip(l_base, 0, R - 1))
        matched = l_nonnull & l_in & (m >= 0)
        ri = jnp.where(matched, m, jnp.int32(-1))
        n = jnp.sum(matched).astype(jnp.int32)
        return matched, ri, jax.lax.all_gather(
            jnp.stack([n, oob, dups, rnull]), axis)

    spec = P(axis)
    nargs = 6 + int(has_lmask)
    # check_vma=False: the all_gathered counts are replicated
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * nargs,
                               out_specs=(spec, spec, P()), check_vma=False))


@kernel_factory
def _fk_rgather_fn(mesh, axis: str, nleaves: int, fill: bool):
    """Gather the build-side output columns at the per-output build index
    (−1 ⇒ null when ``fill``)."""

    def kernel(ri, r_leaves):
        return tuple(ops_gather.take_many(r_leaves, ri, fill_null=fill))

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec, spec),
                               out_specs=spec))


def _fk_violations(per_shard):
    per_shard = per_shard.reshape(-1, 4)
    oob, dups, rnull = (int(per_shard[:, 1].sum()),
                        int(per_shard[:, 2].sum()),
                        int(per_shard[:, 3].sum()))
    if oob or dups or rnull:
        raise CylonError(Status(Code.Invalid,
            "dist_join dense_key_range contract violated on the right "
            f"side: {oob} keys out of range, {dups} duplicate keys, "
            f"{rnull} null keys (the hint promises unique non-null keys "
            "within the range)"))
    return per_shard


def _try_fk_join(left: DTable, right: DTable, config: JoinConfig,
                 dense_key_range, node=None) -> "DTable | None":
    """Run the dense-unique-right-key join if eligible, else None (the
    general path handles every shape; the hint is advisory for dispatch
    but its CONTRACT — unique/non-null/in-range right keys — is enforced)."""
    how = config.join_type.value
    li_keys = _join_keys(left, config.left_column_idx)
    ri_keys = _join_keys(right, config.right_column_idx)
    if (how not in ("inner", "left")
            or len(li_keys) != 1 or len(ri_keys) != 1):
        return None
    lkc = left.columns[li_keys[0]]
    rkc = right.columns[ri_keys[0]]
    if (lkc.dtype.type != rkc.dtype.type
            or not jnp.issubdtype(lkc.data.dtype, jnp.integer)
            or is_dictionary_encoded(lkc.dtype.type)):
        return None
    lo, hi = int(dense_key_range[0]), int(dense_key_range[1])
    world = left.ctx.get_world_size()
    if hi < lo:
        return None
    # small BUILD side ⇒ replicate it instead of co-partitioning: the
    # probe (fact) side then never moves at all — the broadcast FK join.
    # stride stays 1 (every shard builds the full key→row map from its
    # replicated copy), so the slot budget is checked against the
    # replicated block's capacity bound.
    r_rows = (broadcast.rows_if_small(right, config.broadcast_threshold)
              if world > 1 else None)
    stride = 1 if (world == 1 or r_rows is not None) else world
    R = -(-(hi - lo + 1) // stride)
    bcap_bound = (ops_compact.next_bucket(max(r_rows, 1), minimum=8)
                  if r_rows is not None else right.cap)
    if R > 4 * max(left.cap, bcap_bound):
        return None  # same slot-space budget as the dense semi-join
    # a deferred select on the BUILD side would change which keys exist —
    # compact it (build sides are dimension-sized); the PROBE side's mask
    # fuses: INNER folds it into `matched` (one shared compaction), LEFT
    # keeps the zero-copy probe and passes the mask through to the output
    # the decision's evidence comes from the table AS THE PLANNER SAW it
    # — the collapse below may shrink cap and drop the ingest counts the
    # reason string reports (same ordering rule as _try_broadcast_join)
    r_reason = (broadcast.small_side_reason(right, r_rows)
                if r_rows is not None else None)
    right._collapse_pending()
    if world > 1:
        if r_rows is not None:
            trace.count("join.broadcast")
            plan_check.annotate(decision="fk-dense+broadcast",
                                reason=r_reason)
            right = broadcast.replicate_table(right)
        else:
            trace.count("join.shuffle")
            plan_check.annotate(node, decision="fk-dense+shuffle",
                                reason=_shuffle_reason(
                                    node, "build side not provably "
                                          "small; modulo co-partition"))
            with trace.span("join.shuffle"):
                left = _shuffle_masked(
                    left, _mod_pids(left, li_keys[0], lo, world))
                right = _shuffle_by_pids(
                    right, _mod_pids(right, ri_keys[0], lo, world))
            lkc = left.columns[li_keys[0]]
        rkc = right.columns[ri_keys[0]]
    else:
        plan_check.annotate(decision="fk-dense", reason="world=1")
    ctx = left.ctx
    mesh, axis = ctx.mesh, ctx.axis
    has_lm = how == "inner" and left.pending_mask is not None
    lm_args = (left.pending_mask,) if has_lm else ()
    with trace.span("join.count"):
        matched, ri, cnts = _fk_probe_fn(
            mesh, axis, left.cap, right.cap, lo, hi, stride,
            lkc.validity is not None, rkc.validity is not None, has_lm)(
            left.counts, right.counts, lkc.data, lkc.validity,
            rkc.data, rkc.validity, *lm_args)
    r_leaves = tuple((c.data, c.validity) for c in right.columns)

    from ..dtypes import Type
    if how == "left":
        # probe side zero-copy: every valid left row emits exactly once,
        # in place — no compaction, no count read (capacity is static)
        hint_key = ("fkleft", mesh, left.cap, right.cap, lo, hi, stride)
        _capacity_hints.setdefault(hint_key, ((1,), 0))

        def dispatch(sizes):
            with trace.span("join.gather"):
                return _fk_rgather_fn(mesh, axis, len(r_leaves), True)(
                    ri, r_leaves)

        def post(per_shard):
            _fk_violations(per_shard)
            return (1,)

        routs, _, _ = ops_compact.optimistic_dispatch(
            _capacity_hints, hint_key, dispatch, cnts, post)
        cols = [DColumn("lt-" + c.name, c.dtype, c.data, c.validity,
                        c.dictionary, c.arrow_type) for c in left.columns]
        cols += [DColumn("rt-" + c.name, c.dtype, d, v, c.dictionary,
                         c.arrow_type)
                 for c, (d, v) in zip(right.columns, routs)]
        # a deferred select on the probe side stays deferred: the attach
        # is zero-copy, so the mask keeps describing the output's rows
        return DTable(ctx, cols, left.cap, left.counts,
                      left.pending_mask, left.pending_cnts)

    # INNER: compact the matched probe rows (the shared row-filter tail),
    # carrying the build index as a rider column, then gather the build
    # outputs at the compacted capacity
    aug_cols = [DColumn("lt-" + c.name, c.dtype, c.data, c.validity,
                        c.dictionary, c.arrow_type) for c in left.columns]
    aug_cols.append(DColumn("__fk_ri", DataType(Type.INT32), ri, None))
    aug = DTable(ctx, aug_cols, left.cap, left.counts)

    def post(per_shard):
        per_shard = _fk_violations(per_shard)
        return (ops_compact.next_bucket(
            max(int(per_shard[:, 0].max(initial=0)), 1), minimum=8),)

    hint_key = ("fkinner", mesh, left.cap, right.cap, lo, hi, stride,
                len(aug_cols), has_lm)
    out = _compact_survivors(aug, matched, cnts, hint_key, "join.gather",
                             post=post)
    ri_c = out.columns[-1].data
    with trace.span("join.gather"):
        routs = _fk_rgather_fn(mesh, axis, len(r_leaves), False)(
            ri_c, r_leaves)
    cols = list(out.columns[:-1])
    cols += [DColumn("rt-" + c.name, c.dtype, d, v, c.dictionary,
                     c.arrow_type)
             for c, (d, v) in zip(right.columns, routs)]
    return DTable(ctx, cols, out.cap, out.counts)


def _join_keys(dt: DTable, spec) -> List[int]:
    """Key spec → column-index list: an int/str, or a tuple/list of them
    (composite keys; the kernels are multi-column throughout, the config
    merely carries the spec — reference join_config.hpp is single-column,
    composite keys are an intentional extension)."""
    if isinstance(spec, (tuple, list)):
        return [dt.column_index(c) for c in spec]
    return [dt.column_index(spec)]


def _join_setup(left: DTable, right: DTable, config: JoinConfig):
    """Key resolution + type check + dictionary unification — the setup
    every distributed-join strategy (shuffle, streaming, broadcast)
    shares."""
    # the general join's plan sorts want compacted inputs (a deferred
    # select's padding would ride every sort operand); only the dense
    # paths consume a pending mask in place
    left._collapse_pending()
    right._collapse_pending()
    li_keys = _join_keys(left, config.left_column_idx)
    ri_keys = _join_keys(right, config.right_column_idx)
    if len(li_keys) != len(ri_keys):
        raise CylonError(Status(Code.Invalid,
            f"join key arity mismatch: {len(li_keys)} vs {len(ri_keys)}"))
    for li, ri in zip(li_keys, ri_keys):
        lt_k = left.columns[li].dtype.type
        rt_k = right.columns[ri].dtype.type
        if lt_k != rt_k:
            raise CylonError(Status(Code.TypeError,
                f"join key type mismatch {lt_k.name} vs {rt_k.name}"))
    left, right = _unify_dtable_dicts(left, right, li_keys, ri_keys)
    return left, right, li_keys, ri_keys


def _try_broadcast_join(left: DTable, right: DTable, config: JoinConfig
                        ) -> "DTable | None":
    """Replicated-small-side join if eligible, else None (the shuffle
    path handles every shape).

    Eligibility = a side whose global row count is provably under the
    broadcast threshold (config knob / ``JoinConfig.broadcast_threshold``)
    AND whose unmatched rows need no emission: INNER can replicate
    either side, LEFT only the right side; RIGHT/FULL stay on the
    shuffle path (a replicated side's unmatched rows would be emitted
    once per shard — docs/tpu_perf_notes.md "broadcast vs shuffle
    joins").  The small side is all_gathered once into a replicated
    block (replica-cached across repeated joins) and the existing local
    kernel runs per shard against the UNMOVED large side, whose rows
    never cross the wire.  NOTE: like the dense FK fast path, a
    broadcast join does not carry the SORT algorithm's global
    key-ordering guarantee — the output stays in the large side's
    shard layout.
    """
    how = config.join_type.value
    if how not in ("inner", "left"):
        return None
    world = left.ctx.get_world_size()
    if world == 1:
        return None  # co-partitioning is already a no-op
    thr = config.broadcast_threshold
    r_rows = broadcast.rows_if_small(right, thr)
    l_rows = (broadcast.rows_if_small(left, thr)
              if how == "inner" else None)
    if r_rows is None and l_rows is None:
        return None
    # the decision's evidence comes from the tables AS THE PLANNER SAW
    # them — _join_setup may rebuild handles (collapse, dict unify) and
    # lose the ingest-count provenance the reason string reports
    take_right = r_rows is not None and (l_rows is None or r_rows <= l_rows)
    reason = (broadcast.small_side_reason(right, r_rows) if take_right
              else broadcast.small_side_reason(left, l_rows))
    left, right, li_keys, ri_keys = _join_setup(left, right, config)
    trace.count("join.broadcast")
    plan_check.annotate(decision="broadcast",
                        side="right" if take_right else "left",
                        reason=reason)
    if take_right:
        rrep = broadcast.replicate_table(right)
        return _join_copartitioned(left, rrep, li_keys, ri_keys, how,
                                   "sort")
    lrep = broadcast.replicate_table(left)
    return _join_copartitioned(lrep, right, li_keys, ri_keys, how, "sort")


def _join_prologue(left: DTable, right: DTable, config: JoinConfig):
    """Shared setup for the one-shot and streaming joins: key resolution,
    type check, dictionary unification, algorithm + sort splitters."""
    left, right, li_keys, ri_keys = _join_setup(left, right, config)
    alg = "sort" if config.algorithm == JoinAlgorithm.SORT else "hash"
    if alg == "hash" or left.ctx.get_world_size() == 1:
        splitters = None
    else:
        # range partition samples the PRIMARY key column; equal primary
        # values land on one shard, and equal composite keys share their
        # primary value, so composite keys still co-locate
        with trace.span("join.sample"):
            splitters = _sample_splitters(
                [(left, li_keys[0]), (right, ri_keys[0])], ascending=True)
    return left, right, li_keys, ri_keys, alg, splitters


def _copartition(dt: DTable, key_is: Sequence[int], alg: str,
                 splitters) -> DTable:
    """Route rows to their join shard (hash or range partitioning).

    Separated from the join tail so callers that join one side repeatedly
    (streaming.dist_join_streaming) shuffle it only once.
    """
    if dt.ctx.get_world_size() == 1:
        return dt  # one shard: co-partitioning is a no-op
    with trace.span_sync("join.partition") as sp:
        if alg == "sort":
            pid = _range_pids(dt, key_is[0], splitters, ascending=True)
        else:
            pid = _hash_pids(dt, key_is)
        sp.sync(pid)
    with trace.span("join.shuffle"):
        return _shuffle_by_pids(dt, pid)


# Last bucketed output capacity per join signature: lets the next identical
# join dispatch phase 2 optimistically BEFORE the host reads the counts, so
# the count sync overlaps device work instead of stalling dispatch (one
# host round trip per join in steady state).  Bounded: keyed by the
# size-class caps + join kind.
_capacity_hints: dict = {}

# Local kernel behind JoinAlgorithm.HASH.  Measured on the v5e
# (experiments/ab_join_kernels.json): the dense-ranks direct-address
# kernel costs 170.5 ms vs the fused single-sort plan's 138.6 at the
# 4M+4M bench shape (it pays dense_ranks' lexsort AND the probe passes),
# and a true no-sort open-addressing table loses 16x even at its
# best-case unique-build shape — random probe passes at ~6 ns/row cannot
# beat ~2 ns/row sorts.  The algorithm choice therefore governs the
# DISTRIBUTED strategy only (murmur hash partitioning vs range
# partitioning — the reference's split, where the shuffle varies and the
# local kernel is shared, arrow_hash_kernels.hpp vs join.cpp); both run
# the sort-plan local kernel.  Flip to "rank" to time the retired kernel.
HASH_LOCAL_KERNEL = "sort"


def _join_copartitioned(lsh: DTable, rsh: DTable, li_keys: Sequence[int],
                        ri_keys: Sequence[int], how: str, alg: str) -> DTable:
    """Masked local join of already co-partitioned sides (dist_join's tail)."""
    ctx = lsh.ctx
    mesh, axis = ctx.mesh, ctx.axis
    if alg == "hash" and HASH_LOCAL_KERNEL == "sort":
        alg = "sort"  # retired local kernel; see HASH_LOCAL_KERNEL
    lkcs = [lsh.columns[i] for i in li_keys]
    rkcs = [rsh.columns[i] for i in ri_keys]
    fill_left = how in ("right", "full_outer")
    fill_right = how in ("left", "full_outer")
    l_leaves = tuple((c.data, c.validity) for c in lsh.columns)
    r_leaves = tuple((c.data, c.validity) for c in rsh.columns)
    # measured crossover (see _join_phase1_fn): riding output leaves
    # through the plan sorts only pays when each side carries ONE array
    def _carry_width(leaves):
        return sum(1 + (v is not None) for _, v in leaves)
    carried = (alg == "sort" and _carry_width(l_leaves) <= 1
               and _carry_width(r_leaves) <= 1)
    with trace.span("join.count"):
        plan, cnts = _join_phase1_fn(mesh, axis, how, alg, carried)(
            lsh.counts, rsh.counts,
            tuple(c.data for c in lkcs), tuple(c.validity for c in lkcs),
            tuple(c.data for c in rkcs), tuple(c.validity for c in rkcs),
            l_leaves, r_leaves)

    hint_key = (mesh, lsh.cap, rsh.cap, how, alg)

    def dispatch(sizes):
        return _join_phase2_fn(mesh, axis, how, alg, sizes[0],
                               fill_left, fill_right, carried)(
            lsh.counts, rsh.counts, plan, l_leaves, r_leaves)

    def post(per_shard):
        return (ops_compact.next_bucket(
            max(int(per_shard.max(initial=0)), 1), minimum=8),)

    with trace.span_sync("join.gather") as sp:
        (louts, routs, counts), used, per_shard = \
            ops_compact.optimistic_dispatch(
                _capacity_hints, hint_key, dispatch, cnts, post)
        capacity = used[0]
        sp.sync((louts, routs))
    if per_shard is not None:  # None ⇒ deferred validation
        trace.count("join.out_rows", int(per_shard.sum()))
        from .. import logging as glog
        glog.vlog(1, "dist_join[%s/%s]: out=%d rows, shard max=%d, cap=%d",
                  how, alg, int(per_shard.sum()),
                  int(per_shard.max(initial=0)), capacity)

    cols = [DColumn("lt-" + c.name, c.dtype, d, v, c.dictionary, c.arrow_type)
            for c, (d, v) in zip(lsh.columns, louts)]
    cols += [DColumn("rt-" + c.name, c.dtype, d, v, c.dictionary, c.arrow_type)
             for c, (d, v) in zip(rsh.columns, routs)]
    return DTable(ctx, cols, capacity, counts)


# ---------------------------------------------------------------------------
# multiway (star) join: partition the fact once, probe every dimension
# ---------------------------------------------------------------------------

def _multiway_edges(edges) -> list:
    """Normalize + validate the per-dimension edge specs.  Each edge is
    ``(how, alg, fact_on, dim_on, dense_key_range, broadcast_threshold,
    rename)``: join kind ("inner"/"left" — the fact must be the
    preserved side), distributed algorithm, key NAMES on the running
    intermediate / the dimension, the optional dense-FK hint, the
    optional per-edge threshold override, and the (old, new) column
    rename applied to the probe output (the consumed ``rename`` node of
    the binary cascade this op replaces)."""
    out = []
    for e in edges:
        how, alg, fact_on, dim_on, dkr, thr, ren = e
        if how not in ("inner", "left"):
            raise CylonError(Status(Code.Invalid,
                f"dist_multiway_join: edge kind {how!r} unsupported — "
                "the fact side must be preserved (INNER, or LEFT with "
                "the fact on the left)"))
        if len(tuple(fact_on)) != len(tuple(dim_on)):
            raise CylonError(Status(Code.Invalid,
                "dist_multiway_join: edge key arity mismatch "
                f"{tuple(fact_on)} vs {tuple(dim_on)}"))
        out.append((how, alg, tuple(fact_on), tuple(dim_on),
                    None if dkr is None else (int(dkr[0]), int(dkr[1])),
                    thr, tuple((o, n) for o, n in ren)))
    return out


def _multiway_threshold(current: DTable, explicit, world: int) -> int:
    """Per-probe effective broadcast threshold — the partition-once
    economics (docs/tpu_perf_notes.md "partition-once / probe-N").

    Replicating a dimension of R rows costs R x (P-1) wire rows; the
    alternative — the per-dimension co-partitioning shuffle — must
    re-exchange the RUNNING intermediate (~I rows on the wire) plus the
    dimension.  Replication therefore pays whenever R < I / (P-1), no
    matter what the session threshold (tuned for binary joins, where
    the alternative only moves the two join sides) says.  ``I`` is the
    same sync-free evidence the broadcast planner reads: ingest-cached
    counts when the intermediate still carries them, else the P*cap
    capacity bound.  The PR-4 replica pricing
    (``broadcast.rows_if_small``'s budget veto, docs/robustness.md)
    keeps the last word on memory — the raised threshold can never
    admit a replica the budget refuses.  An explicit per-edge 0 (or a
    disabled session knob) disables broadcasting for the edge, same as
    ``JoinConfig.broadcast_threshold``."""
    from ..config import broadcast_join_threshold
    base = broadcast_join_threshold() if explicit is None else int(explicit)
    if base <= 0 or world <= 1:
        return base
    ch = current._counts_host
    if ch is not None and current.pending_mask is None:
        bound = int(np.asarray(ch).sum())
    else:
        bound = current.nparts * current.cap
    return max(base, bound // max(world - 1, 1))


def _multiway_rename(dt: DTable, ren) -> DTable:
    if not ren:
        return dt
    m = dict(ren)
    return dt.rename([m.get(n, n) for n in dt.column_names])


@plan_check.instrument
def dist_multiway_join(fact: DTable, dims: Sequence[DTable],
                       edges: Sequence) -> DTable:
    """Fused star join: probe ``fact`` against every dimension in one
    pass — partition-once/probe-N (arXiv:1905.13376) — instead of the
    binary cascade's re-exchange of the growing intermediate per join.

    Created by the logical planner's multiway-join rewrite
    (plan/rules.py; docs/query_planner.md has the detection conditions)
    from chains of equi-joins sharing a fact side; callable directly
    with the same edge specs (see :func:`_multiway_edges`).

    Per dimension, in order:

      * **replicate** when the dimension is provably under the edge's
        EFFECTIVE broadcast threshold — the session knob raised to the
        re-exchange crossover ``I/(P-1)`` (:func:`_multiway_threshold`)
        — and its replica fits the PR-4 memory budget
        (``broadcast.rows_if_small``, re-priced per dimension on EVERY
        execution, so a plan cached under a large budget degrades
        correctly when replayed under a smaller one).  The running
        intermediate then never moves: dense-FK edges probe it in
        place, general edges run the local sort-merge kernel per shard
        against the replica.
      * **fall back** to the ordinary co-partitioning shuffle for that
        edge otherwise (both sides exchange — the binary-equivalent
        degraded leg, ``join.multiway_dims_shuffled``).

    Each probe reuses the existing ops/join.py kernels through
    ``dist_join`` under the effective threshold, so key flavors (int /
    dictionary / null / composite), LEFT-fact null-filling, deferred
    select masks and the dense-FK contract behave byte-for-byte like
    the cascade they replace; EXPLAIN ANALYZE shows one nested node per
    probe with its row counts.  Counters: ``join.multiway``,
    ``join.multiway_probes``, ``join.multiway_dims_broadcast`` /
    ``_shuffled`` (observe catalogue)."""
    from ..config import JoinType
    edges = _multiway_edges(edges)
    if not edges or len(edges) != len(dims):
        raise CylonError(Status(Code.Invalid,
            f"dist_multiway_join: {len(dims)} dimension table(s) for "
            f"{len(edges)} edge spec(s)"))
    node = plan_check.note("dist_multiway_join", fact, *dims,
                           probes=len(edges))
    trace.count("join.multiway")
    world = fact.ctx.get_world_size()
    current = fact
    decisions = []
    for dim, (how, alg, fact_on, dim_on, dkr, thr, ren) in zip(dims, edges):
        trace.count("join.multiway_probes")
        eff = _multiway_threshold(current, thr, world)
        if world > 1:
            # advisory pre-check mirroring the probe's strategy order
            # (quiet: the authoritative re-check — veto counter and
            # annotation included — runs inside the probe); under an
            # installed FaultPlan the budget point may flip between the
            # two reads, skewing ONLY these counters
            label = None
            if broadcast.rows_if_small(dim, eff, quiet=True) is not None:
                label = "broadcast"
            elif how == "inner" and dkr is None \
                    and broadcast.rows_if_small(current, eff,
                                                quiet=True) is not None:
                # the general INNER path replicates a provably-small
                # LEFT (running) side instead — a replica probe, not a
                # co-partitioning exchange.  (A dense hint routes to
                # the FK path first, which never broadcasts the left
                # side; if the hint proves ineligible at probe time the
                # general path may still take this arm — the label is
                # advisory, the counters below stay directionally
                # honest: replica vs co-partition.)
                label = "broadcast-fact"
            if label is not None:
                trace.count("join.multiway_dims_broadcast")
                decisions.append(label)
            else:
                trace.count("join.multiway_dims_shuffled")
                decisions.append("shuffle")
        else:
            decisions.append("local")
        cfg = JoinConfig(JoinType(how), JoinAlgorithm(alg),
                         fact_on[0] if len(fact_on) == 1 else fact_on,
                         dim_on[0] if len(dim_on) == 1 else dim_on,
                         broadcast_threshold=eff)
        current = _multiway_rename(dist_join(current, dim, cfg, dkr), ren)
    plan_check.annotate(node, dims="/".join(decisions))
    return current


# ---------------------------------------------------------------------------
# distributed set ops (reference: DoDistributedSetOperation,
# table_api.cpp:904-975 — shuffle BOTH tables hashing on ALL columns)
# ---------------------------------------------------------------------------

@kernel_factory
def _setop_fn(mesh, axis: str, op: str, cap_a: int, cap_b: int,
              has_validity: Tuple[bool, ...]):
    capacity = cap_a + cap_b if op == ops_setops.UNION else cap_a

    def kernel(a_cnt, b_cnt, a_leaves, b_leaves):
        cols, vals = [], []
        for (ad, av), (bd, bv), has_v in zip(a_leaves, b_leaves, has_validity):
            cols.append(jnp.concatenate([ad, bd]))
            if has_v:
                va = av if av is not None else jnp.ones(ad.shape[0], bool)
                vb = bv if bv is not None else jnp.ones(bd.shape[0], bool)
                vals.append(jnp.concatenate([va, vb]))
            else:
                vals.append(None)
        valid_rows = jnp.concatenate([jnp.arange(cap_a) < a_cnt[0],
                                      jnp.arange(cap_b) < b_cnt[0]])
        idx, count = ops_setops.set_op_indices(tuple(cols), tuple(vals),
                                               cap_a, op, valid=valid_rows)
        outs = tuple(ops_gather.take_many(list(zip(cols, vals)), idx,
                                          fill_null=False))
        return outs, count[None]

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 4, out_specs=(spec, spec)))


def _dist_set_op(a: DTable, b: DTable, op: str) -> DTable:
    plan_check.note(f"dist_{op.lower()}", a, b,
                    decision="shuffle" if a.ctx.get_world_size() > 1
                    else "local")
    a._collapse_pending()
    b._collapse_pending()
    a.verify_same_schema(b)
    a, b = _unify_dtable_dicts(a, b, range(a.num_columns),
                               range(b.num_columns))
    if a.ctx.get_world_size() == 1:
        ash, bsh = a, b
    else:
        with trace.span("setop.shuffle"):
            ash = _shuffle_by_pids(a, _hash_pids(a, range(a.num_columns)))
            bsh = _shuffle_by_pids(b, _hash_pids(b, range(b.num_columns)))
    has_validity = tuple(
        ca.validity is not None or cb.validity is not None
        for ca, cb in zip(ash.columns, bsh.columns))
    a_leaves = tuple((c.data, c.validity) for c in ash.columns)
    b_leaves = tuple((c.data, c.validity) for c in bsh.columns)
    with trace.span_sync("setop.local") as sp:
        outs, counts = _setop_fn(a.ctx.mesh, a.ctx.axis, op, ash.cap, bsh.cap,
                                 has_validity)(
            ash.counts, bsh.counts, a_leaves, b_leaves)
        sp.sync(outs)
    capacity = ash.cap + bsh.cap if op == ops_setops.UNION else ash.cap
    cols = [DColumn(c.name, c.dtype, d, v, c.dictionary, c.arrow_type)
            for c, (d, v) in zip(ash.columns, outs)]
    return DTable(a.ctx, cols, capacity, counts)


@plan_check.instrument
def dist_union(a: DTable, b: DTable) -> DTable:
    return _dist_set_op(a, b, ops_setops.UNION)


@plan_check.instrument
def dist_intersect(a: DTable, b: DTable) -> DTable:
    return _dist_set_op(a, b, ops_setops.INTERSECT)


@plan_check.instrument
def dist_subtract(a: DTable, b: DTable) -> DTable:
    return _dist_set_op(a, b, ops_setops.SUBTRACT)


# ---------------------------------------------------------------------------
# distributed groupby-aggregate (BASELINE config 3; absent in reference v0)
# ---------------------------------------------------------------------------

@kernel_factory
def _groupby_phase1_fn(mesh, axis: str, cap: int, has_where: bool):
    """Group structure + replicated per-shard group counts (tiny).

    The value leaves ride the structure sort (``carry``), so phase 2 finds
    them already in sorted order — extra sort operands are ~free where a
    post-hoc n-row pack gather costs ~6 ns/row.

    The ``has_where=False`` variant takes no mask argument at all — the
    common path must not pay a [P*cap] bool ballast allocation."""

    def kernel(cnt, key_leaves, val_leaves, *maybe_mask):
        kcols = tuple(d for d, _ in key_leaves)
        kvals = tuple(v for _, v in key_leaves)
        row_valid = (maybe_mask[0] if has_where
                     else (jnp.arange(cap) < cnt[0]))
        carry = ops_groupby.carry_pack(
            tuple(d for d, _ in val_leaves),
            tuple(v for _, v in val_leaves))
        structure = ops_groupby.group_structure(kcols, kvals, row_valid,
                                                carry)
        ng = ops_groupby.num_groups_of(structure)
        return structure, row_valid, jax.lax.all_gather(ng, axis)

    spec = P(axis)
    nargs = 4 if has_where else 3
    # check_vma=False: the all_gathered counts are replicated
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * nargs,
                               out_specs=(spec, spec, P()),
                               check_vma=False))


@kernel_factory
def _groupby_phase2_fn(mesh, axis: str, aggs: Tuple[str, ...], out_cap: int,
                       slot_map: Tuple[int, ...]):
    """Aggregations + key gather into a bucketed [out_cap] block.

    ``val_leaves`` holds each distinct value column ONCE (phase 1 carried
    exactly those through the sort); ``slot_map[slot]`` expands them to
    the per-aggregation tuples — the expansion reuses one traced array per
    distinct column, so ``carry_unpack``'s identity replay inside
    ``groupby_aggregate`` matches phase 1's ``carry_pack`` walk."""

    def kernel(structure, row_valid, key_leaves, val_leaves):
        kcols = tuple(d for d, _ in key_leaves)
        kvals = tuple(v for _, v in key_leaves)
        # positional unpack of phase 1's carry (static layout: unique data
        # columns, then validity masks of the nullable ones), re-expanded
        # per aggregation slot
        ucols_s, uvals_s = ops_groupby.carry_unpack(
            structure[3], tuple(v for _, v in val_leaves))
        vcols = tuple(ucols_s[j] for j in slot_map)
        vcols_orig = tuple(val_leaves[j][0] for j in slot_map)
        vvals = tuple(uvals_s[j] for j in slot_map)
        key_idx, outs, out_valids, ngroups = ops_groupby.groupby_aggregate(
            kcols, kvals, vcols_orig,
            tuple(val_leaves[j][1] for j in slot_map), aggs,
            row_valid=row_valid, structure=structure, out_capacity=out_cap,
            sorted_values=(vcols, vvals))
        keys_out = tuple(ops_gather.take_many(key_leaves, key_idx,
                                              fill_null=False))
        return keys_out, outs, out_valids, ngroups[None]

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 4, out_specs=(spec,) * 4))


@kernel_factory
def _dense_phase1_fn(mesh, axis: str, cap: int, lo: int, hi: int,
                     has_kvalid: bool, has_where: bool, stride: int):
    """Dense-key phase 1: slot ids + slot counts + replicated
    [ngroups, overflow] per shard (overflow ⇒ the caller's range hint was
    violated — fails loudly in the count protocol's post()).  ``stride`` =
    world size under the modulo routing (per-shard slots = R/stride)."""

    def kernel(cnt, key_leaf, *maybe_mask):
        kd, kv = key_leaf
        row_valid = (maybe_mask[0] if has_where
                     else (jnp.arange(cap) < cnt[0]))
        slot, counts, ng, ov = ops_groupby.dense_group_structure(
            kd, kv if has_kvalid else None, row_valid, lo, hi,
            stride=stride)
        return slot, counts, jax.lax.all_gather(
            jnp.stack([ng, ov]), axis)

    spec = P(axis)
    nargs = 3 if has_where else 2
    # check_vma=False: the all_gathered counts are replicated
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * nargs,
                               out_specs=(spec, spec, P()), check_vma=False))


@kernel_factory
def _dense_phase2_fn(mesh, axis: str, aggs: Tuple[str, ...], out_cap: int,
                     lo: int, key_dtype_str: str, has_null_slot: bool,
                     slot_map: Tuple[int, ...], stride: int,
                     emit_empty: bool = False, hi: int = None):
    def kernel(slot, counts, val_leaves):
        import numpy as _np
        vcols = tuple(val_leaves[j][0] for j in slot_map)
        vvals = tuple(val_leaves[j][1] for j in slot_map)
        phase = (jax.lax.axis_index(axis).astype(jnp.int32)
                 if stride > 1 else 0)
        kd, kv, outs, ovals, ng = ops_groupby.dense_groupby_aggregate(
            slot, counts, vcols, vvals, aggs, out_cap, lo,
            _np.dtype(key_dtype_str), has_null_slot,
            stride=stride, phase=phase, emit_empty=emit_empty, hi=hi)
        return ((kd, kv), outs, ovals, ng[None])

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 3, out_specs=(spec,) * 4))


# Last bucketed group-count capacity per groupby signature (the optimistic
# dispatch pattern shared with join phase 2 / shuffle).  Bounded: the key
# includes the caller's `where` predicate object, so a fresh-lambda-per-call
# pattern would otherwise grow it (and pin the closures) forever.
_group_cap_hints: dict = {}
_GROUP_HINTS_MAX = 256


@plan_check.instrument
def dist_groupby(dt: DTable, key_columns: Sequence[Union[int, str]],
                 aggregations: Sequence[Tuple[Union[int, str], str]],
                 where=None, dense_key_range=None, pre_aggregate=None,
                 emit_empty: bool = False,
                 _local_only: bool = False) -> DTable:
    """Distributed groupby-aggregate: shuffle on key hash (equal keys
    co-locate ⇒ each group lives wholly on one shard), then the local
    segment-reduction kernel per shard.  Aggs: sum/count/mean/min/max.
    Output columns: keys, then ``{op}_{col}``.

    ``where`` is an optional row predicate (same env protocol + SQL null
    semantics as ``dist_select``) applied as FILTER PUSHDOWN: on a
    multi-shard mesh failing rows are dropped at the partition step (they
    never enter the shuffle), and locally they are masked out of the
    aggregation — either way the filter costs no extra memory pass,
    unlike select-then-groupby which materializes the filtered table.

    Output blocks are sized to a bucket of the per-shard GROUP count (the
    two-phase count protocol), not the input row capacity — a 4-group
    aggregate over millions of rows yields a tiny DTable, and every
    downstream op (sort/head/export) touches group-count-sized arrays.

    ``dense_key_range=(lo, hi)`` is a caller hint that the (single,
    integer, non-dictionary) group key densely covers [lo, hi] — TPC-H
    surrogate keys, row ids, enum codes.  The groupby then runs DIRECT-
    ADDRESS (two scatter passes, no sort — ops/groupby.py
    dense_group_structure).  A key outside the range fails loudly (never
    aliases); the hint is ignored when the slot space would exceed 4x the
    shard capacity (memory guard) or the key shape doesn't qualify.

    ``emit_empty=True`` (requires an engaged ``dense_key_range``) emits
    EVERY key in [lo, hi] as a group, zero-count keys included (count 0,
    sum 0, null min/max/mean) — the direct-address replacement for "LEFT
    join the key universe to keep its zero groups" (TPC-H Q13's
    zero-order customers).  Raises when the dense path cannot engage:
    the caller's plan depends on the zeros actually appearing.

    ``pre_aggregate`` (default: auto = on for world > 1): every supported
    aggregation is decomposable, so each shard aggregates its OWN rows
    first and only the per-shard group table crosses the wire — classic
    two-level aggregation.  Exchange volume drops from O(rows) to
    O(groups)/shard, and a hot key costs one partial row per shard
    instead of landing every duplicate on one receiver (the skew-cliff
    mitigation for grouped aggregation).  Pass ``False`` to force the
    raw-row shuffle (e.g. keys known near-unique, where the partial pass
    is pure overhead).
    """
    node = None
    if not _local_only:
        node = plan_check.note("dist_groupby", dt, keys=tuple(key_columns),
                               aggs=tuple(op for _, op in aggregations),
                               dense=dense_key_range is not None or None,
                               where=where is not None or None)
    key_ids = _resolve_ids(dt, key_columns)
    val_ids = [dt.column_index(c) for c, _ in aggregations]
    # distinct value columns enter the kernels ONCE (they ride phase 1's
    # sort); slot_map re-expands them per aggregation inside the kernels
    uniq_ids = list(dict.fromkeys(val_ids))
    slot_map = tuple(uniq_ids.index(i) for i in val_ids)
    aggs = tuple(op for _, op in aggregations)
    for op in aggs:
        if op not in ops_groupby.AGG_OPS:
            raise CylonError(Status(Code.Invalid, f"unknown aggregation {op!r}"))
    world = dt.ctx.get_world_size()
    # dense-key viability decides BOTH the partitioner (modulo routing at
    # world > 1: per-shard slot space = R / world) and the pre-aggregation
    # default (a key range wider than the shard capacity means near-unique
    # keys per shard — the partial pass would be pure overhead)
    dense = None
    if dense_key_range is not None and len(key_ids) == 1:
        kc0 = dt.columns[key_ids[0]]
        lo, hi = int(dense_key_range[0]), int(dense_key_range[1])
        stride = 1 if (world == 1 or _local_only) else world
        if (jnp.issubdtype(kc0.data.dtype, jnp.integer)
                and not is_dictionary_encoded(kc0.dtype.type)
                and 0 < hi - lo + 1
                and -(-(hi - lo + 1) // stride) <= 4 * dt.cap):
            dense = (lo, hi, stride)
    if emit_empty and dense is None:
        raise CylonError(Status(Code.Invalid,
            "emit_empty requires an engaged dense_key_range (integer "
            "non-dictionary single key, slot space within 4x capacity) — "
            "the zero-count groups only exist on the direct-address path"))
    near_unique = False
    if pre_aggregate is None:
        near_unique = (dense_key_range is not None and len(key_ids) == 1
                       and (int(dense_key_range[1])
                            - int(dense_key_range[0]) + 1) > dt.cap)
        explicit = False
        pre_aggregate = world > 1 and not _local_only and not near_unique
    else:
        explicit = True
    if node is not None:
        # decision AND reason: static EXPLAIN / ANALYZE show WHY a
        # groupby took its path, matching the join-strategy annotations
        # (docs/observability.md)
        if world > 1 and pre_aggregate:
            decision = "pre-aggregate"
            reason = ("explicit pre_aggregate=True" if explicit else
                      "decomposable aggs: per-shard partials replace "
                      "rows on the wire")
        elif world == 1:
            decision = "dense-local" if dense is not None else "local"
            reason = "world=1: every group is already local"
        else:
            decision = ("dense+shuffle" if dense is not None
                        else "shuffle")
            if near_unique:
                width = (int(dense_key_range[1])
                         - int(dense_key_range[0]) + 1)
                reason = (f"near_unique-skip: dense key range {width} > "
                          f"shard capacity {dt.cap} — the partial pass "
                          "could not shrink the exchange")
            else:
                reason = "explicit pre_aggregate=False"
        plan_check.annotate(node, decision=decision, reason=reason)
    if world > 1 and pre_aggregate and not _local_only:
        return _dist_groupby_preagg(dt, key_ids, aggregations, where,
                                    dense_key_range, emit_empty)
    pmask = _effective_mask(dt, where)
    if world == 1 or _local_only:
        sh = dt
    else:
        with trace.span("groupby.shuffle"):
            if dense is not None:
                pid = _mod_pids(dt, key_ids[0], dense[0], world)
            else:
                pid = _hash_pids(dt, key_ids)
            if pmask is not None:
                # filter pushdown: failing rows never enter the exchange
                pid = jnp.where(pmask, pid, jnp.int32(dt.ctx.get_world_size()))
                pmask = None  # rows arrive pre-filtered
            sh = _shuffle_by_pids(_cleared(dt), pid, owner="groupby")
    mesh, axis = dt.ctx.mesh, dt.ctx.axis
    key_leaves = tuple((sh.columns[i].data, sh.columns[i].validity)
                       for i in key_ids)
    val_leaves = tuple((sh.columns[i].data, sh.columns[i].validity)
                       for i in uniq_ids)

    if dense is not None:
        return _dist_groupby_dense(
            dt, sh, sh.columns[key_ids[0]], key_ids[0], val_leaves,
            uniq_ids, slot_map, aggs, aggregations, dense, pmask, where,
            emit_empty)

    with trace.span("groupby.count"):
        args = ((sh.counts, key_leaves, val_leaves)
                + (() if pmask is None else (pmask,)))
        structure, row_valid, ngs = _groupby_phase1_fn(
            mesh, axis, sh.cap, pmask is not None)(*args)

    # key-column identity and the filter decide the group count, so they
    # belong in the hint key — two different groupbys sharing one hint
    # would mis-hint each other into redundant redispatches/replays
    # (predicates are identity-hashable, same as _select_cache's key)
    hint_key = (mesh, sh.cap, aggs, tuple(key_ids), where,
                pmask is not None)
    while len(_group_cap_hints) > _GROUP_HINTS_MAX:
        _group_cap_hints.pop(next(iter(_group_cap_hints)))

    def dispatch(sizes):
        return _groupby_phase2_fn(mesh, axis, aggs, sizes[0], slot_map)(
            structure, row_valid, key_leaves, val_leaves)

    def post(per_shard):
        return (ops_compact.next_bucket(
            max(int(per_shard.max(initial=0)), 1), minimum=8),)

    with trace.span_sync("groupby.local") as sp:
        (keys_out, outs, out_valids, counts), used, per_shard = \
            ops_compact.optimistic_dispatch(
                _group_cap_hints, hint_key, dispatch, ngs, post)
        sp.sync(outs)
    out_cap = used[0]

    cols = []
    for i, (d, v) in zip(key_ids, keys_out):
        c = sh.columns[i]
        cols.append(DColumn(c.name, c.dtype, d, v, c.dictionary, c.arrow_type))
    from ..compute import _agg_output_type
    for (cref, op), arr, validity in zip(aggregations, outs, out_valids):
        base = sh.columns[dt.column_index(cref)]
        t_out = _agg_output_type(base.dtype.type, op)
        cols.append(DColumn(f"{op}_{base.name}", DataType(t_out), arr, validity))
    return DTable(dt.ctx, cols, out_cap, counts)


def _mod_pids(dt: DTable, key_id: int, lo: int, nparts: int) -> jax.Array:
    """Modulo partitioner for dense int keys: shard = (key − lo) mod P.
    Equal keys co-locate (like the hash partitioner) AND each shard's key
    set is one residue class, so the dense slot space compresses by P
    ((key − lo) // P is injective per shard).  Nulls and out-of-range
    keys route to shard 0 — overflow still fails loudly in phase 1."""
    kc = dt.columns[key_id]
    fn = _mod_pids_fn(dt.ctx.mesh, dt.ctx.axis, dt.cap, lo, nparts,
                      kc.validity is not None)
    return fn(dt.counts, kc.data, kc.validity)


@kernel_factory
def _mod_pids_fn(mesh, axis: str, cap: int, lo: int, nparts: int,
                 has_kv: bool):
    def kernel(cnt_blk, kd, kv):
        mask = jnp.arange(cap) < cnt_blk[0]
        # subtract in the key dtype BEFORE narrowing (the rule the dense
        # probes document): an int64 key past 2^31 would wrap under
        # astype(int32) and alias a residue class; in-range keys always
        # yield a base int32 holds
        base = (kd - lo).astype(jnp.int32)
        pid = jnp.where(base >= 0, base % nparts, 0)
        if has_kv:
            pid = jnp.where(kv, pid, 0)
        return jnp.where(mask, pid, jnp.int32(nparts))

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec))


def _dist_groupby_dense(dt: DTable, sh: DTable, kc: DColumn, key_id: int,
                        val_leaves, uniq_ids, slot_map, aggs, aggregations,
                        dense, pmask, where,
                        emit_empty: bool = False) -> DTable:
    """Direct-address tail of dist_groupby (dense_key_range hint)."""
    lo, hi, stride = dense
    mesh, axis = dt.ctx.mesh, dt.ctx.axis
    with trace.span("groupby.count"):
        args = ((sh.counts, (kc.data, kc.validity))
                + (() if pmask is None else (pmask,)))
        slot, counts, ngov = _dense_phase1_fn(
            mesh, axis, sh.cap, lo, hi, kc.validity is not None,
            pmask is not None, stride)(*args)

    hint_key = (mesh, sh.cap, aggs, ("dense", key_id, lo, hi, stride),
                where, pmask is not None, emit_empty)
    while len(_group_cap_hints) > _GROUP_HINTS_MAX:
        _group_cap_hints.pop(next(iter(_group_cap_hints)))
    floor = None
    if emit_empty:
        # group count is R/stride (+1 null) by construction — the first
        # dispatch can be sized exactly, no optimistic miss possible
        R_shard = -(-(hi - lo + 1) // stride)
        floor = ops_compact.next_bucket(R_shard + 1, minimum=8)
        _group_cap_hints.setdefault(hint_key, ((floor,), 0))

    def dispatch(sizes):
        return _dense_phase2_fn(mesh, axis, aggs, sizes[0], lo,
                                str(kc.data.dtype),
                                kc.validity is not None, slot_map,
                                stride, emit_empty, hi)(
            slot, counts, val_leaves)

    def post(per_shard):
        per_shard = per_shard.reshape(-1, 2)
        if int(per_shard[:, 1].sum()) > 0:
            raise CylonError(Status(Code.Invalid,
                f"dense_key_range ({lo}, {hi}) violated: "
                f"{int(per_shard[:, 1].sum())} rows carry keys outside it"))
        need = ops_compact.next_bucket(
            max(int(per_shard[:, 0].max(initial=0)), 1), minimum=8)
        if floor is not None:
            # emit_empty's out cap is STRUCTURAL (every slot in the range
            # emits, occupied or not), while per_shard counts only the
            # occupied groups.  Reporting the occupancy here would let
            # update_size_hint's shrink-slow policy walk the hint below
            # the slot count after shrink_after runs of the same query —
            # and an under-floor dispatch truncates the emitted range
            # SILENTLY, because the occupancy-based validation can never
            # exceed a cap-clamped kernel's output.  The floor is the
            # honest need.
            need = max(need, floor)
        return (need,)

    with trace.span_sync("groupby.local") as sp:
        ((kd, kv), outs, out_valids, counts_out), used, _ = \
            ops_compact.optimistic_dispatch(
                _group_cap_hints, hint_key, dispatch, ngov, post)
        sp.sync(outs)

    cols = [DColumn(kc.name, kc.dtype, kd, kv, kc.dictionary,
                    kc.arrow_type)]
    from ..compute import _agg_output_type
    for (cref, op), arr, validity in zip(aggregations, outs, out_valids):
        base = sh.columns[dt.column_index(cref)]
        t_out = _agg_output_type(base.dtype.type, op)
        cols.append(DColumn(f"{op}_{base.name}", DataType(t_out), arr,
                            validity))
    return DTable(dt.ctx, cols, used[0], counts_out)


# partial op → the aggregation that combines two partials of it
_COMBINE_OP = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


def _decompose_aggs(dt: DTable, aggregations):
    """Two-level decomposition of ``aggregations`` (arXiv:2010.14596):
    one partial slot per distinct (column, partial op) — avg → sum +
    count, count → sum-of-counts, min/max idempotent — plus the
    per-output recomposition plan ``(op, partial ref[, count ref for
    mean])``.  Shared by the runtime pre-aggregate tail and the
    planner-lowered fused operator so the two can never drift."""
    partial: List[Tuple[int, str]] = []
    ppos: dict = {}

    def _p(ci: int, op: str) -> int:
        k = (ci, op)
        if k not in ppos:
            ppos[k] = len(partial)
            partial.append((ci, op))
        return ppos[k]

    plan = []
    for cref, op in aggregations:
        ci = dt.column_index(cref)
        if op == "mean":
            plan.append((op, _p(ci, "sum"), _p(ci, "count")))
        elif op == "count":
            plan.append((op, _p(ci, "count")))
        else:
            plan.append((op, _p(ci, op)))
    return partial, plan


def _recompose_partials(dt: DTable, aggregations, plan, comb: DTable,
                        K: int) -> DTable:
    """Final columns from a combined partial table: mean = Σsum/Σcount,
    everything else forwards its combined partial.  Column plumbing is
    positional — partial column j sits at index K+j of ``comb``."""
    from ..compute import _agg_output_type
    fdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    cols = list(comb.columns[:K])
    for (cref, op), spec in zip(aggregations, plan):
        base = dt.columns[dt.column_index(cref)]
        t_out = _agg_output_type(base.dtype.type, op)
        name = f"{op}_{base.name}"
        if op == "mean":
            s, c = comb.columns[K + spec[1]], comb.columns[K + spec[2]]
            data = s.data.astype(fdt) / jnp.maximum(c.data, 1).astype(fdt)
            cols.append(DColumn(name, DataType(t_out), data, c.data > 0))
        else:
            src = comb.columns[K + spec[1]]
            cols.append(DColumn(name, DataType(t_out), src.data,
                                src.validity))
    return DTable(dt.ctx, cols, comb.cap, comb.counts)


# ---------------------------------------------------------------------------
# aggregation-state capture + merge (serve/matview.py "incremental
# maintenance"): every mergeable aggregation tail holds a combined
# partial-group table (plain combine specs) or a merged sketch-state
# table right before recomposition.  Under collect_agg_state() that
# state is handed to a thread-local sink at zero extra device cost —
# it already exists — so a materialized view can later fold an
# appended delta's state into it (arXiv:2010.14596's mergeable-
# summary contract) and re-finalize WITHOUT touching the base table.
# ---------------------------------------------------------------------------

_matview_tls = threading.local()


class AggState:
    """One captured mergeable aggregation state.

    ``kind``   — ``"plain"`` (combine-spec partials: sum/count/min/max
                 slots, mean = Σsum/Σcount) or ``"sketch"`` (HLL /
                 bottom-k lanes).
    ``state``  — the partial DTable: ``K`` key columns then partial /
                 sketch-lane columns, positional (the
                 ``_recompose_partials`` contract).
    ``base_meta`` — per aggregation ``(base column name, base
                 DataType, op)``: everything finalize needs from the
                 base table, captured as metadata so re-finalizing
                 never faults a spilled base back in.
    """

    __slots__ = ("kind", "state", "K", "partial", "plan", "base_meta",
                 "dense_key_range", "kinds", "qs")

    def __init__(self, kind: str, state: DTable, K: int, *,
                 partial=None, plan=None, base_meta=None,
                 dense_key_range=None, kinds=None, qs=None) -> None:
        self.kind = kind
        self.state = state
        self.K = K
        self.partial = partial
        self.plan = plan
        self.base_meta = base_meta
        self.dense_key_range = dense_key_range
        self.kinds = kinds
        self.qs = qs


@contextmanager
def collect_agg_state():
    """Collect every mergeable aggregation state produced on THIS
    thread while the context is open (yields the sink list).  Nestable;
    the inner collector wins, restoring the outer one on exit."""
    prev = getattr(_matview_tls, "sink", None)
    sink: List[AggState] = []
    _matview_tls.sink = sink
    try:
        yield sink
    finally:
        _matview_tls.sink = prev


def _collecting() -> bool:
    return getattr(_matview_tls, "sink", None) is not None


def _note_plain_state(dt: DTable, aggregations, partial, plan,
                      comb: DTable, K: int, dense_key_range) -> None:
    sink = getattr(_matview_tls, "sink", None)
    if sink is None:
        return
    base_meta = []
    for cref, op in aggregations:
        c = dt._columns[dt.column_index(cref)]
        base_meta.append((c.name, c.dtype, op))
    sink.append(AggState("plain", comb, K, partial=list(partial),
                         plan=list(plan), base_meta=base_meta,
                         dense_key_range=dense_key_range))


def _note_sketch_state(dt: DTable, aggregations, sh: DTable, K: int,
                       kinds, qs) -> None:
    sink = getattr(_matview_tls, "sink", None)
    if sink is None:
        return
    base_meta = []
    for cref, op in aggregations:
        c = dt._columns[dt.column_index(cref)]
        base_meta.append((c.name, c.dtype, op))
    # the shuffled partial table co-locates same-group rows; one local
    # merge collapses it to the global one-row-per-group state
    state = _sketch_merge_local(sh, K, kinds, qs)
    sink.append(AggState("sketch", state, K, base_meta=base_meta,
                         kinds=tuple(kinds), qs=tuple(qs)))


def merge_agg_state(a: AggState, b: AggState) -> AggState:
    """Merge two captured states of the SAME aggregation tail (base ∪
    delta) into one — the O(delta) fold.  Key dictionaries are unified
    first (an append can grow a dictionary, which re-encodes codes);
    plain partials re-combine through the standard combining groupby,
    sketch lanes through the sketch merge kernel."""
    from .streaming import _concat_compact
    K = a.K
    sa, sb = _unify_dtable_dicts(a.state, b.state, list(range(K)),
                                 list(range(K)))
    cc = _concat_compact([sa, sb])
    if a.kind == "sketch":
        sh = _shuffle_by_pids(cc, _hash_pids(cc, list(range(K))),
                              owner="groupby")
        merged = _sketch_merge_local(sh, K, a.kinds, a.qs)
        return AggState("sketch", merged, K, base_meta=a.base_meta,
                        kinds=a.kinds, qs=a.qs)
    comb_aggs = [(K + j, _COMBINE_OP[op])
                 for j, (_, op) in enumerate(a.partial)]
    merged = dist_groupby(cc, list(range(K)), comb_aggs,
                          dense_key_range=a.dense_key_range,
                          pre_aggregate=False)
    return AggState("plain", merged, K, partial=a.partial, plan=a.plan,
                    base_meta=a.base_meta,
                    dense_key_range=a.dense_key_range)


def finalize_agg_state(st: AggState) -> DTable:
    """The final result table from a (merged) captured state — local
    arithmetic only for plain partials, shuffle + sketch collapse for
    sketches; never reads a base table (``base_meta`` carries the
    output naming/typing)."""
    from ..compute import _agg_output_type
    from ..dtypes import Type
    comb, K = st.state, st.K
    if st.kind == "plain":
        fdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        cols = list(comb.columns[:K])
        for (name, dtype, _), spec in zip(st.base_meta, st.plan):
            op = spec[0]
            t_out = _agg_output_type(dtype.type, op)
            if op == "mean":
                s, c = comb.columns[K + spec[1]], comb.columns[K + spec[2]]
                data = (s.data.astype(fdt)
                        / jnp.maximum(c.data, 1).astype(fdt))
                cols.append(DColumn(f"{op}_{name}", DataType(t_out),
                                    data, c.data > 0))
            else:
                src = comb.columns[K + spec[1]]
                cols.append(DColumn(f"{op}_{name}", DataType(t_out),
                                    src.data, src.validity))
        return DTable(comb.ctx, cols, comb.cap, comb.counts)
    # sketch: co-locate groups, then the finalizing combine
    sh = _shuffle_by_pids(comb, _hash_pids(comb, list(range(K))),
                          owner="groupby")
    key_leaves = tuple((sh.columns[i].data, sh.columns[i].validity)
                       for i in range(K))
    fn = _sketch_combine_fn(
        sh.ctx.mesh, sh.ctx.axis, sh.cap,
        tuple(sh.columns[i].validity is not None for i in range(K)),
        st.kinds, st.qs, sh.cap, True)
    keys_out, outs, counts = fn(sh.counts, key_leaves,
                                _sketch_state_groups(sh, K, st.kinds))
    cols = []
    for meta, (kd, kv) in zip(sh.columns[:K], keys_out):
        cols.append(DColumn(meta.name, meta.dtype, kd, kv,
                            meta.dictionary, meta.arrow_type))
    idt = Type.INT64 if jax.config.jax_enable_x64 else Type.INT32
    for (name, _, op), (est, valid), kind in zip(st.base_meta, outs,
                                                 st.kinds):
        out_name = sketch_output_name(name, op)
        if kind == "distinct":
            cols.append(DColumn(out_name, DataType(idt),
                                est.astype(jnp.int64
                                           if jax.config.jax_enable_x64
                                           else jnp.int32), None))
        else:
            cols.append(DColumn(out_name, DataType(Type.FLOAT), est,
                                valid))
    return DTable(comb.ctx, cols, sh.cap, counts)


def _dist_groupby_preagg(dt: DTable, key_ids: List[int], aggregations,
                         where, dense_key_range,
                         emit_empty: bool = False) -> DTable:
    """Two-level aggregation tail of dist_groupby (``pre_aggregate``):
    local per-shard groupby (no exchange) → shuffle the tiny partial-group
    table → combining groupby (sum of sums, sum of counts, min of mins,
    max of maxes; mean = Σsum/Σcount)."""
    K = len(key_ids)
    partial, plan = _decompose_aggs(dt, aggregations)
    # emit_empty rides the LOCAL pass only: with every shard emitting the
    # full key range, every key reaches the combine as ≥1 partial row, so
    # the zero groups survive it without a second emit-empty pass
    part = dist_groupby(dt, key_ids, partial, where=where,
                        dense_key_range=dense_key_range,
                        pre_aggregate=False, _local_only=True,
                        emit_empty=emit_empty)
    comb_aggs = [(K + j, _COMBINE_OP[op])
                 for j, (_, op) in enumerate(partial)]
    if broadcast.rows_if_small(part, None) is not None:
        # small partial table: replace the combine SHUFFLE with one
        # all_gather — every shard receives all partial rows, shard 0
        # alone owns them (HEAD counts), and the local combining groupby
        # produces the full result there.  One collective instead of
        # partition + two-phase exchange; the result lands on one shard,
        # which is where a few-group aggregate ends up anyway.  (The
        # planner-lowered fused path prefers the partial SHUFFLE: the
        # gather replicates every shard's padded partial block P-1
        # times, strictly more wire bytes — docs/tpu_perf_notes.md
        # "aggregation below the exchange".)
        trace.count("groupby.broadcast_combine")
        part_rep = broadcast.replicate_table(
            part, mode=broadcast.HEAD,
            span_name="groupby.broadcast_gather", cache=False)
        comb = dist_groupby(part_rep, list(range(K)), comb_aggs,
                            dense_key_range=dense_key_range,
                            pre_aggregate=False, _local_only=True)
    else:
        comb = dist_groupby(part, list(range(K)), comb_aggs,
                            dense_key_range=dense_key_range,
                            pre_aggregate=False)
    _note_plain_state(dt, aggregations, partial, plan, comb, K,
                      dense_key_range)
    return _recompose_partials(dt, aggregations, plan, comb, K)


def _combine_leaf_spec(part: DTable, K: int, partial_ops) -> Tuple:
    """Static leaf-layout combiner spec of a partial-group table for the
    chunked shuffle's fold-by-key (shuffle._fold_combine_fn): maps the
    wire leaf positions (data + optional validity per column, in
    _shuffle_by_pids order) to key slots and value slots with their
    combine ops."""
    idx = 0
    key_slots, val_slots = [], []
    for i, c in enumerate(part.columns):
        d = idx
        idx += 1
        v = None
        if c.validity is not None:
            v = idx
            idx += 1
        if i < K:
            key_slots.append((d, v))
        else:
            val_slots.append((d, v, _COMBINE_OP[partial_ops[i - K]]))
    return (tuple(key_slots), tuple(val_slots))


# plan-known dense slot spaces up to this size combine as ONE all-reduce
# (docs/tpu_perf_notes.md derives the crossover: the psum's wire cost is
# R x (P-1) lane-bytes regardless of occupancy, so a sparse domain must
# stay small to beat the partial exchange's true-rows pricing)
_PSUM_SLOT_CAP = 4096


@kernel_factory
def _psum_combine_fn(mesh, axis: str, cap: int, domains: Tuple,
                     lanes: Tuple[str, ...], out_cap: int,
                     has_where: bool):
    """Fused groupby over a plan-known dense composite key space: per
    shard, scatter-add every partial lane into the [R+1] slot array (R
    real slots + 1 dropped), combine ALL shards with ONE ``psum`` — the
    aggregation runs inside the collective (arXiv:2106.15565), with no
    count protocol, no sort, and no host read anywhere — then decode the
    present slots into an output block every shard computes identically
    (shard 0 owns the rows, the HEAD-counts form).

    ``domains`` is ``((size, nullable), ...)`` per key column, ``size``
    INCLUDING the null code (= size-1) when nullable — composite null
    keys compose correctly because each column contributes its own null
    code.  ``lanes`` is one of "count"/"isum"/"fsum" per partial slot,
    preceded by the implicit row-count lane deciding group presence."""

    R = 1
    for size, _ in domains:
        R *= size

    def kernel(cnt, key_leaves, val_leaves, *maybe_mask):
        idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        fdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        row_valid = (maybe_mask[0] if has_where
                     else (jnp.arange(cap) < cnt[0]))
        slot = jnp.zeros(cap, jnp.int32)
        for (kd, kv), (size, nullable) in zip(key_leaves, domains):
            code = kd.astype(jnp.int32)
            if nullable:
                code = jnp.where(kv, code, jnp.int32(size - 1))
            slot = slot * size + code
        slot = jnp.where(row_valid, slot, jnp.int32(R))
        ilanes = [row_valid.astype(idt)]   # lane 0: rows per group
        flanes = []
        fpos, ipos = [], [None]
        for (vd, vv), kind in zip(val_leaves, lanes):
            vmask = row_valid if vv is None else (row_valid & vv)
            if kind == "count":
                ipos.append(len(ilanes))
                ilanes.append(vmask.astype(idt))
            elif kind == "isum":
                ipos.append(len(ilanes))
                ilanes.append(jnp.where(vmask, vd,
                                        jnp.zeros((), vd.dtype))
                              .astype(idt))
            else:
                ipos.append(None)
                fpos.append(len(flanes))
                flanes.append(jnp.where(vmask, vd,
                                        jnp.zeros((), vd.dtype))
                              .astype(fdt))
        ipack = jnp.zeros((R + 1, len(ilanes)), idt).at[slot].add(
            jnp.stack(ilanes, axis=1), mode="drop")
        packs = [ipack]
        if flanes:
            packs.append(jnp.zeros((R + 1, len(flanes)), fdt).at[slot]
                         .add(jnp.stack(flanes, axis=1), mode="drop"))
        packs = jax.lax.psum(tuple(packs), axis)  # the combine
        itot = packs[0][:R]
        ftot = packs[1][:R] if flanes else None
        present = itot[:, 0] > 0
        starts = ops_compact.compact_indices(present, out_cap, fill=-1)
        ngroups = jnp.sum(present).astype(jnp.int32)
        safe = jnp.clip(starts, 0, R - 1)
        keys_out = []
        rem = safe
        for (kd, kv), (size, nullable) in reversed(
                list(zip(key_leaves, domains))):
            code = rem % size
            rem = rem // size
            valid = None
            if nullable:
                valid = code != (size - 1)
                code = jnp.where(valid, code, 0)
            keys_out.append((code.astype(kd.dtype), valid))
        keys_out.reverse()
        vals_out = []
        fi = 0
        for j, kind in enumerate(lanes):
            if ipos[j + 1] is not None:
                lane = jnp.take(itot[:, ipos[j + 1]], safe)
            else:
                lane = jnp.take(ftot[:, fpos[fi]], safe)
                fi += 1
            vals_out.append(lane)
        me = jax.lax.axis_index(axis)
        cnt_out = jnp.where(me == 0, ngroups, jnp.int32(0))
        return tuple(keys_out), tuple(vals_out), cnt_out[None]

    spec = P(axis)
    nargs = 4 if has_where else 3
    # check_vma=False: the psum'd packs are replicated; every shard
    # emits the identical decoded block as its own P(axis) slice (the
    # replicate_table idiom)
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * nargs,
                               out_specs=(spec, spec, spec),
                               check_vma=False))


def _fused_psum_groupby(dt: DTable, key_ids: List[int], aggregations,
                        where, node, reason) -> "DTable | None":
    """The "combine during the collective" arm of dist_groupby_fused, if
    eligible at execution time, else None.  Eligibility re-checks what
    the plan decided from schema stats: every key dictionary-encoded
    (codes are structurally in-range — no overflow validation, hence no
    host read, is needed), the composite domain within _PSUM_SLOT_CAP,
    and every aggregation sum/count/mean-decomposable (min/max have no
    SUM all-reduce; some backends lower only SUM — see _scalar_agg_fn)."""
    world = dt.ctx.get_world_size()
    if world <= 1:
        return None
    domains = []
    for i in key_ids:
        c = dt.columns[i]
        if (c.dictionary is None or len(c.dictionary) == 0
                or not jnp.issubdtype(c.data.dtype, jnp.integer)):
            return None
        domains.append((len(c.dictionary) + (1 if c.validity is not None
                                             else 0),
                        c.validity is not None))
    R = 1
    for size, _ in domains:
        R *= size
    if not 0 < R + 1 <= _PSUM_SLOT_CAP:
        return None
    if any(op not in ("sum", "count", "mean") for _, op in aggregations):
        return None
    partial, plan = _decompose_aggs(dt, aggregations)
    lanes = []
    for ci, op in partial:
        if op == "count":
            lanes.append("count")
        elif jnp.issubdtype(dt.columns[ci].data.dtype, jnp.floating):
            lanes.append("fsum")
        else:
            lanes.append("isum")
    trace.count("groupby.psum_combine")
    plan_check.annotate(node, decision="psum-combine", reason=reason)
    pmask = _effective_mask(dt, where)
    out_cap = ops_compact.next_bucket(R, minimum=8)
    key_leaves = tuple((dt.columns[i].data, dt.columns[i].validity)
                      for i in key_ids)
    val_leaves = tuple((dt.columns[ci].data, dt.columns[ci].validity)
                       for ci, _ in partial)
    from ..analysis._abstract import is_abstract
    if not any(is_abstract(d) for d, _ in key_leaves) \
            and trace_state_clean():
        # wire accounting: the all-reduce combines the [R+1, lanes]
        # packs across shards — priced as R+1 slot-rows replicated to
        # the other P-1 shards (the broadcast family; abstract plan
        # runs move zero bytes, like every other exchange path)
        idt_w = 8 if jax.config.jax_enable_x64 else 4
        lane_bytes = (1 + len(lanes)) * idt_w
        moved = (R + 1) * (world - 1)
        trace.count("broadcast.rows_sent", moved)
        trace.count("broadcast.bytes_sent", moved * lane_bytes)
        trace.count("groupby.bytes_moved", moved * lane_bytes)
    args = ((dt.counts, key_leaves, val_leaves)
            + (() if pmask is None else (pmask,)))
    with trace.span_sync("groupby.psum_combine") as sp:
        keys_out, vals_out, counts_out = _psum_combine_fn(
            dt.ctx.mesh, dt.ctx.axis, dt.cap, tuple(domains),
            tuple(lanes), out_cap, pmask is not None)(*args)
        sp.sync(vals_out)
    idt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    cols = []
    for i, (kd, kv) in zip(key_ids, keys_out):
        c = dt.columns[i]
        cols.append(DColumn(c.name, c.dtype, kd, kv, c.dictionary,
                            c.arrow_type))
    # rebuild a partial-table view so the shared recompose applies: the
    # lanes ARE the combined partials (float sums cast back to the
    # column dtype, the groupby kernels' convention)
    pcols = list(cols)
    from ..compute import _agg_output_type
    for (ci, op), lane in zip(partial, vals_out):
        base = dt.columns[ci]
        if op == "sum" and jnp.issubdtype(base.data.dtype, jnp.floating):
            lane = lane.astype(base.data.dtype)
        pcols.append(DColumn(f"{op}_{base.name}",
                             DataType(_agg_output_type(base.dtype.type,
                                                       op)),
                             lane, None))
    comb = DTable(dt.ctx, pcols, out_cap, counts_out)
    _note_plain_state(dt, aggregations, partial, plan, comb,
                      len(key_ids), None)
    return _recompose_partials(dt, aggregations, plan, comb,
                               len(key_ids))


@plan_check.instrument
def dist_groupby_fused(dt: DTable, key_columns: Sequence[Union[int, str]],
                       aggregations: Sequence[Tuple[Union[int, str], str]],
                       where=None, dense_key_range=None,
                       emit_empty: bool = False,
                       mode: str = "pre-aggregate",
                       reason: "str | None" = None) -> DTable:
    """Planner-lowered fused aggregation exchange: per-shard partial
    aggregation → exchange of the partial-group table → combining
    aggregation, with the decomposition (avg → sum+count, count →
    sum-of-counts, min/max idempotent) and the strategy decided at PLAN
    time (plan/rules.py "groupby-pushdown"; callable directly with the
    same semantics as :func:`dist_groupby`).

    ``mode`` is the plan's strategy, ``reason`` its recorded evidence:

      * ``"psum"`` — every key is dictionary-encoded with a small
        plan-known domain and every agg is sum/count/mean: the combine
        runs INSIDE one all-reduce over the dense slot space
        (arXiv:2106.15565's combine-during-the-collective) — no count
        protocol, no sort, no host read; re-checked at execution and
        degraded to ``pre-aggregate`` if the rebound table disagrees.
      * ``"pre-aggregate"`` — local partials, then a hash shuffle of
        the partial table carrying a combiner spec: the single-shot
        exchange moves each partial row once (strictly fewer bytes than
        the eager tail's replicate-everywhere combine gather), and the
        over-budget chunked path folds rounds together BY GROUP KEY so
        ``shuffle.exchange_bytes_peak`` scales with distinct groups,
        not rows (shuffle._fold_combine_fn).  On a non-trivial
        (slow, fast) mesh split the chooser may further lower this
        exchange HIERARCHICALLY (``exchange=hierarchical-combine``):
        the same combiner spec drives a fast-axis-local pre-combine so
        only per-group partials cross the slow axis
        (shuffle._hierarchical_exchange; ``groupby.axis_precombine*``
        counters, docs/tpu_perf_notes.md "Hierarchical collectives").
      * ``"shuffle"`` — plan-proven near-unique keys: the partial pass
        cannot shrink the exchange, so raw rows move once and aggregate
        in place (identical to ``pre_aggregate=False``).

    Counters: ``groupby.pushdown``, ``groupby.partials_rows``,
    ``groupby.psum_combine``, ``shuffle.fold_combined`` (observe
    catalogue; docs/tpu_perf_notes.md "aggregation below the
    exchange")."""
    if mode not in ("psum", "pre-aggregate", "shuffle"):
        raise CylonError(Status(Code.Invalid,
            f"dist_groupby_fused: unknown mode {mode!r}"))
    if dt.is_spilled and not emit_empty:
        # out-of-core input (docs/out_of_core.md): the leaves live in
        # the host-tier spill pool — stream them through the
        # morsel-partitioned scan instead of faulting the whole block
        # in.  Row-identical to the resident path, psum mode included
        # (psum is a performance lowering; the morsel fold is the
        # generic one).  emit_empty faults in transparently below: the
        # dense hint may not engage at morsel width.
        from ..spill import morsel as spill_morsel
        return spill_morsel.morsel_groupby(
            dt, list(key_columns), list(aggregations), where=where,
            dense_key_range=dense_key_range, emit_empty=emit_empty,
            reason=reason)
    node = plan_check.note("dist_groupby_fused", dt,
                           keys=tuple(key_columns),
                           aggs=tuple(op for _, op in aggregations),
                           mode=mode,
                           where=where is not None or None)
    trace.count("groupby.pushdown")
    key_ids = _resolve_ids(dt, key_columns)
    world = dt.ctx.get_world_size()
    for _, op in aggregations:
        if op not in ops_groupby.AGG_OPS:
            raise CylonError(Status(Code.Invalid,
                                    f"unknown aggregation {op!r}"))
    if mode == "psum" and not emit_empty:
        out = _fused_psum_groupby(dt, key_ids, aggregations, where,
                                  node, reason)
        if out is not None:
            return out
        mode = "pre-aggregate"
        reason = "psum re-check failed at execution; partial exchange"
    if world <= 1 or mode == "shuffle":
        plan_check.annotate(node, decision=("local" if world <= 1
                                            else "shuffle"),
                            reason=reason)
        return dist_groupby(dt, key_ids, list(aggregations), where=where,
                            dense_key_range=dense_key_range,
                            pre_aggregate=False, emit_empty=emit_empty)
    plan_check.annotate(node, decision="pre-aggregate", reason=reason)
    K = len(key_ids)
    partial, plan = _decompose_aggs(dt, aggregations)
    part = dist_groupby(dt, key_ids, partial, where=where,
                        dense_key_range=dense_key_range,
                        pre_aggregate=False, _local_only=True,
                        emit_empty=emit_empty)
    comb_aggs = [(K + j, _COMBINE_OP[op])
                 for j, (_, op) in enumerate(partial)]
    spec = _combine_leaf_spec(part, K, [op for _, op in partial])
    with trace.span("groupby.shuffle"):
        sh = _shuffle_by_pids(part, _hash_pids(part, list(range(K))),
                              combine=spec, owner="groupby")
    comb = dist_groupby(sh, list(range(K)), comb_aggs,
                        dense_key_range=dense_key_range,
                        pre_aggregate=False, _local_only=True)
    _note_plain_state(dt, aggregations, partial, plan, comb, K,
                      dense_key_range)
    return _recompose_partials(dt, aggregations, plan, comb, K)


# ---------------------------------------------------------------------------
# sketch-based approximate aggregation (docs/out_of_core.md "sketches";
# arXiv:2010.14596): per-group mergeable sketches ARE the partials, so
# the combine exchange moves constant bytes per group regardless of rows
# ---------------------------------------------------------------------------

def _parse_sketch_op(op: str) -> Tuple[str, "float | None"]:
    """``approx_distinct`` | ``approx_quantile:<q>`` (default q 0.5) →
    ``(kind, q)``; anything else raises."""
    if op == "approx_distinct":
        return "distinct", None
    if op == "approx_quantile" or op.startswith("approx_quantile:"):
        q = 0.5
        if ":" in op:
            try:
                q = float(op.split(":", 1)[1])
            except ValueError:
                raise CylonError(Status(Code.Invalid,
                    f"bad quantile in sketch op {op!r}")) from None
        if not 0.0 <= q <= 1.0:
            raise CylonError(Status(Code.Invalid,
                f"quantile must be in [0, 1], got {q} ({op!r})"))
        return "quantile", q
    raise CylonError(Status(Code.Invalid,
        f"unknown sketch aggregation {op!r} (expected approx_distinct "
        "or approx_quantile:<q>)"))


def sketch_output_name(col_name: str, op: str) -> str:
    kind, q = _parse_sketch_op(op)
    if kind == "distinct":
        return f"approx_distinct_{col_name}"
    return f"p{int(round(q * 100))}_{col_name}"


@kernel_factory
def _sketch_partial_fn(mesh, axis: str, cap: int, total_cap: int,
                       key_hasv: Tuple[bool, ...],
                       val_hasv: Tuple[bool, ...],
                       kinds: Tuple[str, ...], out_cap: int,
                       has_where: bool):
    """Phase A (per shard, no exchange): sort-group the rows and build
    one fixed-size sketch per (group, aggregation) — HLL registers or
    bottom-k sample lanes (ops/sketch.py).  Returns the per-shard
    partial block: group keys + [out_cap, M/K] sketch leaves + group
    counts.  ``off`` (traced) is the morsel row offset, so the per-row
    sample priorities stay globally unique across staged morsels with
    ONE compiled program."""
    from ..ops import sketch as ops_sketch

    def kernel(cnt, off, key_leaves, val_leaves, *maybe_mask):
        row_valid = jnp.arange(cap) < cnt[0]
        if has_where:
            row_valid = row_valid & maybe_mask[0]
        me = jax.lax.axis_index(axis)
        gidx = (me.astype(jnp.uint32) * jnp.uint32(total_cap)
                + off[0].astype(jnp.uint32)
                + jnp.arange(cap, dtype=jnp.uint32))
        carry = [d for d, _ in val_leaves]
        carry += [v for _, v in val_leaves if v is not None]
        carry.append(gidx)
        structure = ops_groupby.group_structure(
            tuple(d for d, _ in key_leaves),
            tuple(v for _, v in key_leaves), row_valid,
            carry=tuple(carry))
        idxS, is_first, rvS, carried = structure
        nv = len(val_leaves)
        vals_s = carried[:nv]
        it = iter(carried[nv:-1])
        valids_s = tuple(next(it) if hv else None for hv in val_hasv)
        gidx_s = carried[-1]
        slot, keep_first = ops_sketch.sorted_slots(is_first, rvS,
                                                   out_cap)
        ngroups = jnp.sum(keep_first).astype(jnp.int32)
        starts = ops_compact.compact_indices(keep_first, out_cap,
                                             fill=-1)
        key_idx = jnp.where(
            starts >= 0,
            jnp.take(idxS, jnp.clip(starts, 0, cap - 1)),
            jnp.int32(-1))
        keys_out = ops_gather.take_many(key_leaves, key_idx,
                                        fill_null=False)
        outs = []
        for col_s, valid_s, kind in zip(vals_s, valids_s, kinds):
            vmask = rvS if valid_s is None else (rvS & valid_s)
            bits = ops_sketch.value_bits32(col_s)
            if kind == "distinct":
                outs.append((ops_sketch.hll_build(slot, out_cap, bits,
                                                  vmask),))
            else:
                sv, sp = ops_sketch.bottomk_build(
                    slot, out_cap, col_s.astype(jnp.float32), bits,
                    gidx_s, vmask)
                outs.append((sv, sp))
        return tuple(keys_out), tuple(outs), ngroups[None]

    spec = P(axis)
    return named_jit(shard_map(
          kernel, mesh=mesh,
          in_specs=(spec,) * (5 if has_where else 4),
          out_specs=(spec, spec, spec)))


@kernel_factory
def _sketch_combine_fn(mesh, axis: str, cap: int,
                       key_hasv: Tuple[bool, ...],
                       kinds: Tuple[str, ...], qs: Tuple,
                       out_cap: int, finalize: bool = True):
    """Phase B (per shard): re-group the partial rows by key and MERGE
    each group's sketches (register max / bottom-k of the union).
    With ``finalize`` (after the partial exchange) the merged sketches
    collapse to result lanes — HLL harmonic estimate, empirical sample
    quantile; without it (the per-morsel fold of a spilled scan) the
    MERGED SKETCH STATE comes back instead, in the partial-table lane
    layout, so the accumulator stays one row per group seen so far.
    Returns keys + one lane tuple per aggregation + group counts."""
    from ..ops import sketch as ops_sketch

    def kernel(cnt, key_leaves, sk_leaves):
        row_valid = jnp.arange(cap) < cnt[0]
        # sketch state is 2-D ([n, M/K] lanes): it cannot ride the
        # lax.sort carry (operand shapes must match the keys), so the
        # rows are gathered into sorted order explicitly instead
        structure = ops_groupby.group_structure(
            tuple(d for d, _ in key_leaves),
            tuple(v for _, v in key_leaves), row_valid)
        idxS, is_first, rvS, _ = structure
        carried = []
        for leaves in sk_leaves:
            for lf in leaves:
                carried.append(jnp.take(lf, idxS, axis=0))
        slot, keep_first = ops_sketch.sorted_slots(is_first, rvS,
                                                   out_cap)
        ngroups = jnp.sum(keep_first).astype(jnp.int32)
        starts = ops_compact.compact_indices(keep_first, out_cap,
                                             fill=-1)
        key_idx = jnp.where(
            starts >= 0,
            jnp.take(idxS, jnp.clip(starts, 0, cap - 1)),
            jnp.int32(-1))
        keys_out = ops_gather.take_many(key_leaves, key_idx,
                                        fill_null=False)
        outs = []
        ci = 0
        for kind, q in zip(kinds, qs):
            if kind == "distinct":
                regs_rows = carried[ci]
                ci += 1
                regs = ops_sketch.hll_merge_rows(slot, out_cap,
                                                 regs_rows, rvS)
                if finalize:
                    outs.append((ops_sketch.hll_estimate(regs), None))
                else:
                    outs.append((regs,))
            else:
                vals_rows, prio_rows = carried[ci], carried[ci + 1]
                ci += 2
                mv, mp = ops_sketch.bottomk_merge_rows(
                    slot, out_cap, vals_rows, prio_rows, rvS)
                if finalize:
                    est, nonempty = ops_sketch.bottomk_quantile(mv, mp,
                                                                q)
                    outs.append((est, nonempty))
                else:
                    outs.append((mv, mp))
        return tuple(keys_out), tuple(outs), ngroups[None]

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=(spec, spec, spec)))


def _sketch_state_table(ctx, key_meta_cols, keys_out, sk_outs, kinds,
                        cap: int, counts) -> DTable:
    """Assemble a sketch PARTIAL-state DTable (keys + trailing-dim
    sketch lanes) — shared by the per-shard build, the per-morsel fold
    and nothing else, so the lane layout cannot drift between them."""
    from ..dtypes import Type
    cols = []
    for meta, (kd, kv) in zip(key_meta_cols, keys_out):
        cols.append(DColumn(meta.name, meta.dtype, kd, kv,
                            meta.dictionary, meta.arrow_type))
    for j, (leaves, kind) in enumerate(zip(sk_outs, kinds)):
        if kind == "distinct":
            cols.append(DColumn(f"__hll{j}", DataType(Type.INT32),
                                leaves[0]))
        else:
            cols.append(DColumn(f"__bkv{j}", DataType(Type.FLOAT),
                                leaves[0]))
            cols.append(DColumn(f"__bkp{j}", DataType(Type.UINT32),
                                leaves[1]))
    return DTable(ctx, cols, cap, counts)


def _sketch_state_groups(part: DTable, K: int, kinds) -> Tuple:
    """The sketch-lane leaves of a partial-state table, grouped per
    aggregation in the `_sketch_state_table` layout."""
    groups = []
    ci = K
    for kind in kinds:
        if kind == "distinct":
            groups.append((part.columns[ci].data,))
            ci += 1
        else:
            groups.append((part.columns[ci].data,
                           part.columns[ci + 1].data))
            ci += 2
    return tuple(groups)


def _sketch_merge_local(part: DTable, K: int, kinds, qs) -> DTable:
    """Merge same-group rows of a partial-state table IN PLACE (no
    exchange): the per-morsel fold of a spilled sketch scan — the
    accumulator stays one row per group seen so far instead of growing
    with morsels."""
    key_leaves = tuple((part.columns[i].data, part.columns[i].validity)
                       for i in range(K))
    fn = _sketch_combine_fn(
        part.ctx.mesh, part.ctx.axis, part.cap,
        tuple(part.columns[i].validity is not None for i in range(K)),
        kinds, qs, part.cap, False)
    keys_out, outs, counts = fn(part.counts, key_leaves,
                                _sketch_state_groups(part, K, kinds))
    return _sketch_state_table(part.ctx, part.columns[:K], keys_out,
                               outs, kinds, part.cap, counts)


def _sketch_partial_table(dt: DTable, key_ids, val_ids, kinds, where,
                          off: int, total_cap: int) -> DTable:
    """One table's (or morsel's) per-shard sketch partials as a DTable:
    key columns + sketch-state columns with trailing dims (the
    exchange's per-leaf path moves those natively)."""
    pmask = _effective_mask(dt, where)
    key_leaves = tuple((dt.columns[i].data, dt.columns[i].validity)
                       for i in key_ids)
    val_leaves = tuple((dt.columns[i].data, dt.columns[i].validity)
                       for i in val_ids)
    out_cap = dt.cap   # groups <= rows/shard; partial blocks are
    #                    input-capacity-bounded (the exchange's receive
    #                    blocks size to ACTUAL groups via the counts)
    fn = _sketch_partial_fn(
        dt.ctx.mesh, dt.ctx.axis, dt.cap, total_cap,
        tuple(dt.columns[i].validity is not None for i in key_ids),
        tuple(dt.columns[i].validity is not None for i in val_ids),
        kinds, out_cap, pmask is not None)
    offs = jax.device_put(np.full(dt.nparts, off, np.int32),
                          dt.ctx.sharding())
    args = (dt.counts, offs, key_leaves, val_leaves) \
        + (() if pmask is None else (pmask,))
    keys_out, sk_outs, counts = fn(*args)
    return _sketch_state_table(dt.ctx, [dt.columns[i] for i in key_ids],
                               keys_out, sk_outs, kinds, out_cap,
                               counts)


@plan_check.instrument
def dist_groupby_sketch(dt: DTable,
                        key_columns: Sequence[Union[int, str]],
                        aggregations: Sequence[Tuple[Union[int, str],
                                                     str]],
                        where=None) -> DTable:
    """Sketch-based approximate groupby (docs/out_of_core.md
    "sketches"): per group, ``approx_distinct`` estimates the distinct
    count of a column via HLL registers and ``approx_quantile:<q>``
    estimates its q-quantile from a bottom-k uniform sample — both
    within the advertised error bounds (ops/sketch.py
    ``HLL_ERROR_BOUND`` / ``QUANTILE_RANK_ERROR_BOUND``), both
    decomposed through the partial → exchange → combine path with the
    SKETCHES as the partials: the combine exchange moves one
    fixed-size summary per (group, shard) no matter how many rows fed
    it — the constant-per-group wire contract that makes these the
    cheap high-QPS answer over larger-than-memory data (the serving
    tier submits them like any other plan, and a SPILLED input streams
    through the morsel scan, merging per-morsel sketches).

    Output columns: keys, then ``approx_distinct_{col}`` (int) /
    ``p{q*100}_{col}`` (float32, null for all-null groups) in
    aggregation order."""
    from ..dtypes import Type
    node = plan_check.note("dist_groupby_sketch", dt,
                           keys=tuple(key_columns),
                           aggs=tuple(op for _, op in aggregations))
    trace.count("sketch.groupbys")
    key_ids = _resolve_ids(dt, key_columns)
    K = len(key_ids)
    val_ids = [dt.column_index(c) for c, _ in aggregations]
    parsed = [_parse_sketch_op(op) for _, op in aggregations]
    kinds = tuple(kind for kind, _ in parsed)
    qs = tuple(-1.0 if q is None else q for _, q in parsed)
    if dt.is_spilled:
        # out-of-core input: per-morsel partials from staged slices,
        # FOLDED incrementally — sketches merge, so the accumulator
        # holds one state row per group seen so far (retaining all K
        # morsel partials would scale device memory with the input ×
        # the sketch width, defeating the budget the scan honors).
        # Stage-in of morsel k+1 overlaps device compute of morsel k
        # through the HostPipeline, the morsel-scan invariant.
        from contextlib import closing

        from ..resilience import exchange_budget
        from ..spill import morsel as spill_morsel
        from ..spill import pool as spill_pool
        from .streaming import _concat_compact
        entry = spill_pool.get_pool().pin_for_scan(dt)
        cap = entry.cap
        k, w, per = spill_morsel.plan_morsels(
            dt.nparts, cap, spill_morsel._spilled_rbytes(dt),
            exchange_budget())
        plan_check.annotate(node, decision="morsel-scan",
                            reason=f"{k} morsels x {w} rows/shard "
                                   f"({per} B/morsel)")
        acc = None
        with closing(spill_morsel.iter_morsels(dt, entry, k, w,
                                               cap)) as scan:
            for m, sl in enumerate(scan):
                part_m = _sketch_partial_table(
                    sl, key_ids, val_ids, kinds, where, m * w, cap)
                if acc is None:
                    acc = part_m
                else:
                    acc = _sketch_merge_local(
                        _concat_compact([acc, part_m]), K, kinds, qs)
        part = acc
    else:
        part = _sketch_partial_table(dt, key_ids, val_ids, kinds,
                                     where, 0, dt.cap)
    pcnt = part.counts_host()
    prows = int(np.asarray(pcnt).sum())
    trace.count("sketch.partial_rows", prows)
    from .. import observe
    sk_leaves = [lf for c in part.columns[K:]
                 for lf in (c.data, c.validity) if lf is not None]
    trace.count("sketch.register_bytes",
                prows * max(observe.row_bytes(sk_leaves), 1))
    with trace.span("sketch.shuffle"):
        sh = _shuffle_by_pids(part, _hash_pids(part, list(range(K))),
                              owner="groupby")
    key_leaves = tuple((sh.columns[i].data, sh.columns[i].validity)
                       for i in range(K))
    fn = _sketch_combine_fn(
        sh.ctx.mesh, sh.ctx.axis, sh.cap,
        tuple(sh.columns[i].validity is not None for i in range(K)),
        kinds, qs, sh.cap, True)
    with trace.span_sync("sketch.combine") as sp:
        keys_out, outs, counts = fn(
            sh.counts, key_leaves, _sketch_state_groups(sh, K, kinds))
        sp.sync(outs)
    _note_sketch_state(dt, aggregations, sh, K, kinds, qs)
    cols = []
    for i, (kd, kv) in zip(key_ids, keys_out):
        c = dt._columns[i]
        cols.append(DColumn(c.name, c.dtype, kd, kv, c.dictionary,
                            c.arrow_type))
    idt = Type.INT64 if jax.config.jax_enable_x64 else Type.INT32
    for (cref, op), (est, valid), kind in zip(aggregations, outs,
                                              kinds):
        base = dt._columns[dt.column_index(cref)]
        name = sketch_output_name(base.name, op)
        if kind == "distinct":
            cols.append(DColumn(name, DataType(idt),
                                est.astype(jnp.int64
                                           if jax.config.jax_enable_x64
                                           else jnp.int32), None))
        else:
            cols.append(DColumn(name, DataType(Type.FLOAT), est,
                                valid))
    return DTable(dt.ctx, cols, sh.cap, counts)


@kernel_factory
def _scalar_agg_fn(mesh, axis: str, cap: int, aggs: Tuple[str, ...],
                   has_where: bool):
    """Whole-table reductions: per-shard masked fold + one psum each —
    no sort, no groups.  The constant-key groupby a scalar aggregate would
    otherwise ride sorts the entire padded block (measured 2.6 s for a
    SF-10 Q6 at 67M cap; this path is ~30 ms device)."""

    def kernel(cnt, val_leaves, *maybe_mask):
        base = (maybe_mask[0] if has_where
                else (jnp.arange(cap) < cnt[0]))
        outs = []
        nonempty = []  # SQL: min/max/mean over zero rows are NULL
        for (d, v), op in zip(val_leaves, aggs):
            m = base if v is None else (base & v)
            c = jax.lax.psum(jnp.sum(m).astype(jnp.int32), axis)
            nonempty.append(c > 0)
            if op in ("sum", "mean"):
                # integer sums accumulate in int64 when x64 is on; with x64
                # off (TPU default) the accumulator stays int32 and a
                # whole-table SUM over values averaging > 2^31/rows can
                # wrap — same documented limit as the groupby int path
                acc = d
                if (jnp.issubdtype(d.dtype, jnp.integer)
                        and jax.config.jax_enable_x64):
                    acc = d.astype(jnp.int64)
                s = jax.lax.psum(jnp.where(m, acc, 0).sum(), axis)
            if op == "sum":
                outs.append(s)
            elif op == "count":
                outs.append(c)
            elif op == "mean":
                outs.append(s / jnp.maximum(c, 1).astype(d.dtype))
            elif op in ("min", "max"):
                from ..dtypes import extreme_value
                fill = extreme_value(d.dtype, largest=(op == "min"))
                folded = jnp.where(m, d, fill)
                local = folded.min() if op == "min" else folded.max()
                # all_gather + local fold instead of pmin/pmax: some XLA
                # backends have lowered only SUM all-reduces; the gather
                # of one scalar per shard costs the same wire bytes
                g = jax.lax.all_gather(local, axis)
                outs.append(g.min() if op == "min" else g.max())
            else:
                raise ValueError(f"unknown aggregation {op!r}")
        return tuple(outs), tuple(nonempty)

    spec = P(axis)
    nargs = 3 if has_where else 2
    # check_vma=False: psum outputs are replicated
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * nargs,
                               out_specs=((P(),) * len(aggs),) * 2,
                               check_vma=False))


@plan_check.instrument
def dist_aggregate(dt: DTable,
                   aggregations: Sequence[Tuple[Union[int, str], str]],
                   where=None) -> "Table":
    """Whole-table (scalar) aggregate — the GROUP BY-less SELECT SUM(…)
    shape.  Returns a ONE-row local Table with columns ``{op}_{col}``.

    ``where`` follows the same predicate protocol (and SQL null semantics)
    as ``dist_select``/``dist_groupby``; it rides the reduction mask, so a
    filtered scalar aggregate is one fused device pass + one host read.
    """
    plan_check.note("dist_aggregate", dt,
                    aggs=tuple(op for _, op in aggregations),
                    where=where is not None or None)
    val_ids = [dt.column_index(c) for c, _ in aggregations]
    aggs = tuple(op for _, op in aggregations)
    pmask = _effective_mask(dt, where)
    val_leaves = tuple((dt.columns[i].data, dt.columns[i].validity)
                       for i in val_ids)
    args = (dt.counts, val_leaves) + (() if pmask is None else (pmask,))
    with trace.span_sync("aggregate.scalar") as sp:
        outs, nonempty = _scalar_agg_fn(dt.ctx.mesh, dt.ctx.axis, dt.cap,
                                        aggs, pmask is not None)(*args)
        sp.sync(outs)
    from ..compute import _agg_output_type
    from ..dtypes import DataType, Type, device_dtype
    from ..table import Column, Table
    cols = []
    for (cref, op), val, ne in zip(aggregations, outs, nonempty):
        base = dt.columns[dt.column_index(cref)]
        t_out = _agg_output_type(base.dtype.type, op)
        if not jax.config.jax_enable_x64:
            # declared type must match device storage (same logical-type
            # downgrade as ingest / dist_with_column)
            t_out = {Type.INT64: Type.INT32, Type.UINT64: Type.UINT32,
                     Type.DOUBLE: Type.FLOAT}.get(t_out, t_out)
        # Empty-input semantics are pandas-style, matching the oracle the
        # whole test-suite verifies against: SUM and COUNT over zero rows
        # are 0 (strict SQL would make SUM NULL); MIN/MAX/AVG are NULL.
        validity = (None if op in ("sum", "count")
                    else jnp.asarray(ne)[None])
        cols.append(Column(f"{op}_{base.name}", DataType(t_out),
                           jnp.asarray(val, device_dtype(t_out))[None],
                           validity))
    return Table(dt.ctx, cols)


# ---------------------------------------------------------------------------
# distributed sample-sort (BASELINE config 4; absent in reference v0)
# ---------------------------------------------------------------------------

@kernel_factory
def _sample_fn(mesh, axis: str, cap: int, nsamples: int, ascending: bool):
    """Per shard: nsamples evenly-spaced order statistics of the non-null
    valid rows + a per-sample validity flag."""

    def kernel(cnt, col, validity):
        order = ops_sort.sort_indices_masked(col, validity, cnt[0], ascending)
        n_null = (jnp.int32(0) if validity is None else
                  jnp.sum((~validity) & (jnp.arange(cap) < cnt[0]))
                  .astype(jnp.int32))
        nn = cnt[0] - n_null           # non-null rows sort to the front
        q = ((jnp.arange(nsamples, dtype=jnp.int32) * jnp.maximum(nn, 1))
             // nsamples)
        vals = jnp.take(col, jnp.take(order, jnp.clip(q, 0, cap - 1)))
        ok = jnp.arange(nsamples) < nn  # crude but safe: ≤ nn samples
        return vals, ok

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 3, out_specs=(spec, spec)))


@kernel_factory
def _pool_splitters_fn(mesh, axis: str, nsides: int, nparts: int,
                       ascending: bool):
    """Pool every side's per-shard samples (all_gather), sort the pool on
    device, and pick P−1 evenly-spaced pivots — replicated, never touching
    the host.  With zero valid samples the pivots collapse to the dtype's
    extreme so every row routes to shard 0 (degenerate but correct)."""

    def kernel(*flat):
        vals, oks = flat[:nsides], flat[nsides:]
        pv = jnp.concatenate([jax.lax.all_gather(v, axis, tiled=True)
                              for v in vals])
        po = jnp.concatenate([jax.lax.all_gather(o, axis, tiled=True)
                              for o in oks])
        key = pv if ascending else ops_sort._invert(pv)
        _, _, sv = jax.lax.sort((~po, key, pv), num_keys=2)  # invalids last
        m = jnp.sum(po).astype(jnp.int32)
        total = pv.shape[0]
        pos = jnp.clip((jnp.arange(1, nparts) * m) // nparts, 0, total - 1)
        sp = jnp.take(sv, pos)
        from ..dtypes import extreme_value
        return jnp.where(m > 0, sp, extreme_value(pv.dtype,
                                                  largest=ascending))

    spec = P(axis)
    # check_vma=False: the pooled splitters are replicated
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * (2 * nsides), out_specs=P(),
                               check_vma=False))


def _sample_splitters(sides: Sequence[Tuple[DTable, int]], ascending: bool
                      ) -> jax.Array:
    """Pool per-shard samples from every (table, key column) side and pick
    P−1 splitters — the sample-sort pivot selection.  Entirely on device
    (the former host pooling cost one blocking round trip per sort/join)."""
    ctx = sides[0][0].ctx
    nparts = ctx.get_world_size()
    flat = []
    for dt, key_i in sides:
        c = dt.columns[key_i]
        flat.append(_sample_fn(dt.ctx.mesh, dt.ctx.axis, dt.cap,
                               _SAMPLES_PER_SHARD, ascending)(
            dt.counts, c.data, c.validity))
    vals = [v for v, _ in flat]
    oks = [o for _, o in flat]
    return _pool_splitters_fn(ctx.mesh, ctx.axis, len(sides), nparts,
                              ascending)(*vals, *oks)


@functools.partial(named_jit, name="dist_ops._range_pids_kernel")
def _range_pids_kernel(col, validity, mask, splitters, nparts_arr, last_arr):
    pid = jnp.searchsorted(splitters, col, side="right").astype(jnp.int32)
    if validity is not None:
        pid = jnp.where(validity, pid, last_arr)  # nulls last
    return jnp.where(mask, pid, nparts_arr)


@functools.partial(named_jit, name="dist_ops._range_pids_desc_kernel")
def _range_pids_desc_kernel(col, validity, mask, splitters, nparts_arr,
                            last_arr):
    # splitters descend; a row's partition is the count of splitters > value
    pid = jnp.sum(splitters[None, :] > col[:, None], axis=1).astype(jnp.int32)
    if validity is not None:
        pid = jnp.where(validity, pid, last_arr)
    return jnp.where(mask, pid, nparts_arr)


def _range_pids(dt: DTable, key_i: int, splitters: jax.Array,
                ascending: bool) -> jax.Array:
    c = dt.columns[key_i]
    nparts = dt.ctx.get_world_size()
    mask = _row_mask(dt)
    if splitters.shape[0] == 0:
        return jnp.where(mask, jnp.int32(0), jnp.int32(nparts))
    sp = splitters.astype(c.data.dtype)
    fn = _range_pids_kernel if ascending else _range_pids_desc_kernel
    # host scalars: a jnp.int32(...) here would be an eager device
    # program of its own per call
    return fn(c.data, c.validity, mask, sp, np.int32(nparts),
              np.int32(nparts - 1))


# ---------------------------------------------------------------------------
# embarrassingly-parallel ops: select / project / derived columns / head.
# No shuffle — each shard transforms its own rows (reference local paths:
# Select table_api.cpp:977-1005, Project table_api.cpp:1007-1029).
# ---------------------------------------------------------------------------

# Keyed on the predicate/function object itself: pass a stable callable
# (module-level fn or a reused closure) to avoid re-tracing in loops.
# Bounded FIFO so fresh-lambda callers can't grow it without limit (each
# entry pins the closure + its compiled executable).
_SELECT_CACHE_MAX = 256
_select_cache: dict = {}


def _cache_put(key, fn):
    if len(_select_cache) >= _SELECT_CACHE_MAX:
        _select_cache.pop(next(iter(_select_cache)))
    _select_cache[key] = fn
    return fn


class _RecordingEnv(dict):
    """Column-name → data-array env that records which columns the predicate
    reads (at trace time), so nulls in exactly those columns can veto rows.

    This matches SQL three-valued logic for conjunctive predicates (a NULL
    comparand makes the conjunction non-TRUE ⇒ row dropped).  For predicates
    where a NULL column must NOT veto the row — disjunctions over nullable
    columns, IS NULL tests — read ``env.valid(name)`` and combine it
    explicitly; doing so waives the automatic veto for that column."""

    def __init__(self, items, validities):
        super().__init__(items)
        self._validities = validities
        self.accessed = set()
        self.null_handled = set()

    def __getitem__(self, k):
        self.accessed.add(k)
        return super().__getitem__(k)

    # every other read path records too, so no spelling of a predicate can
    # silently bypass the null veto
    def get(self, k, default=None):
        if k in self:
            return self[k]
        return default

    def items(self):
        self.accessed.update(self.keys())
        return [(k, super(_RecordingEnv, self).__getitem__(k))
                for k in self.keys()]

    def values(self):
        self.accessed.update(self.keys())
        return [super(_RecordingEnv, self).__getitem__(k)
                for k in self.keys()]

    def valid(self, k):
        """Per-row validity of column ``k`` (all-True when it has no nulls).
        Reading it transfers NULL handling for ``k`` to the predicate."""
        self.null_handled.add(k)
        v = self._validities[k]
        return jnp.ones(super().__getitem__(k).shape[0], bool) if v is None \
            else v


def _env(columns: Sequence[DColumn]) -> dict:
    return {c.name: c.data for c in columns}


def _masked_predicate(names, predicate, base_mask, leaves, params=()):
    """The ONE definition of predicate evaluation semantics: the recording
    env (so nulls in exactly the columns the predicate read veto the row —
    SQL three-valued logic, waived per column via ``env.valid``), AND'ed
    with ``base_mask``.  Shared by dist_select and every filter-pushdown
    path so the semantics cannot diverge.

    ``params`` are extra traced arguments handed to the predicate after
    the env — DEVICE-RESIDENT comparands (e.g. a scalar aggregate feeding
    a threshold).  They enter the jit as arguments, never as baked-in
    constants, so a data-dependent threshold costs no host round trip and
    downstream dispatch overlaps the upstream compute producing it."""
    env = _RecordingEnv({n: d for n, (d, _) in zip(names, leaves)},
                        {n: v for n, (_, v) in zip(names, leaves)})
    mask = predicate(env, *params) & base_mask
    for n, (_, v) in zip(names, leaves):
        if n in env.accessed - env.null_handled and v is not None:
            mask = mask & v
    return mask


def _predicate_mask(dt: DTable, predicate) -> jax.Array:
    """Row mask [P*cap] for ``predicate``, AND'ed with the valid-row mask
    (and any deferred-select mask the table carries).  Pure elementwise —
    XLA propagates the mesh sharding; used by the filter-pushdown paths
    (dist_groupby ``where``)."""
    names = tuple(c.name for c in dt.columns)
    key = ("pmask", dt.cap, names, predicate)
    fn = _select_cache.get(key)
    if fn is None:
        def kernel(base_mask, leaves):
            return _masked_predicate(names, predicate, base_mask, leaves)

        fn = _cache_put(key, named_jit(kernel,
                                       name="dist_ops._predicate_mask"))
    leaves = tuple((c.data, c.validity) for c in dt.columns)
    base = _row_mask(dt) if dt.pending_mask is None else dt.pending_mask
    return fn(base, leaves)


def _effective_mask(dt: DTable, where) -> "jax.Array | None":
    """The fused row filter a mask-aware consumer should apply: the
    ``where`` predicate (if any) AND the table's deferred-select mask (if
    any); None when neither exists (the cheap no-ballast path)."""
    if where is not None:
        return _predicate_mask(dt, where)  # folds pending itself
    return dt.pending_mask


# Last bucketed output capacity per select signature (optimistic dispatch,
# same pattern as join phase 2): a selective filter must SHRINK the block —
# leaving survivors in the input-sized capacity makes every downstream op
# (join sorts especially) pay for the dead padding.  Measured at TPC-H
# SF-10: a month filter on lineitem leaves 748k rows in a 67M block, and
# the following part join took 6.8 s; with compaction it is ~100 ms.
_select_cap_hints: dict = {}


def _compact_survivors(dt: DTable, mask: jax.Array, cnts, hint_key,
                       span_name: str, post=None) -> DTable:
    """Shared tail of every row-filter-shaped op (select, semi/anti join):
    compact the rows ``mask`` keeps into a size-class block bucketed to the
    max per-shard survivor count, via the optimistic-dispatch protocol.
    ``cnts`` is the replicated per-shard survivor-count array; a custom
    ``post`` may validate extra per-shard fields riding it (the dense
    semi-join's overflow counter)."""
    mesh, axis, cap = dt.ctx.mesh, dt.ctx.axis, dt.cap
    leaves = tuple((c.data, c.validity) for c in dt.columns)
    nleaves = len(leaves)

    def dispatch(sizes):
        outcap = sizes[0]
        key2 = ("selgather", mesh, axis, cap, outcap, nleaves)
        p2 = _select_cache.get(key2)
        if p2 is None:
            def gather_kernel(mask, leaves):
                idx, count = ops_compact.mask_to_indices(mask, outcap)
                outs = tuple(ops_gather.take_many(leaves, idx,
                                                  fill_null=False))
                return outs, count[None].astype(jnp.int32)

            spec = P(axis)
            p2 = _cache_put(key2, named_jit(shard_map(
                gather_kernel, mesh=mesh, in_specs=(spec, spec),
                out_specs=(spec, spec)), name="dist_ops._compact_survivors"))
        return p2(mask, leaves)

    if post is None:
        def post(per_shard):
            return (ops_compact.next_bucket(
                max(int(per_shard.max(initial=0)), 1), minimum=8),)

    while len(_select_cap_hints) > _GROUP_HINTS_MAX:  # predicate keys pin closures
        _select_cap_hints.pop(next(iter(_select_cap_hints)))
    with trace.span_sync(span_name) as sp:
        (outs, counts), used, _ = ops_compact.optimistic_dispatch(
            _select_cap_hints, hint_key, dispatch, cnts, post)
        sp.sync(outs)
    cols = [DColumn(c.name, c.dtype, d, v, c.dictionary, c.arrow_type)
            for c, (d, v) in zip(dt.columns, outs)]
    return DTable(dt.ctx, cols, used[0], counts)


@plan_check.instrument
def dist_select(dt: DTable, predicate, params=(), compact: bool = True
                ) -> DTable:
    """Distributed row filter: ``predicate`` maps {column name: sharded data
    array} → bool mask; surviving rows compact into a size-class block
    bucketed to the max per-shard survivor count.  Purely local compute —
    the reference's Select is too (table_api.cpp:977-1005, per-row lambda →
    arrow Filter) — plus the tiny replicated count all_gather every
    two-phase op shares.

    ``params``: device-resident extra predicate arguments (replicated
    scalars/small arrays), passed ``predicate(env, *params)``.  A
    threshold computed by ``dist_aggregate`` can feed a select WITHOUT a
    host read — the dependency stays on device and the pipeline never
    stalls on it (TPC-H Q11/Q15/Q22's correlated-scalar shape).

    ``compact=False`` defers the compaction: the result carries the row
    mask (``DTable.pending_mask``) and keeps the input blocks.  Consumers
    that fold row masks — groupby/aggregate, the dense semi/anti and
    FK-join probes, further selects — then skip the standalone ~6 ns/row
    compaction scatter entirely; any other consumer compacts on first
    touch.  Rule of thumb (docs/tpu_perf_notes.md): defer when the
    SURVIVOR fraction is large (the compaction's output gathers dominate)
    or the consumer is mask-aware end-to-end; compact when the filter is
    highly selective and the consumer re-traverses the block per pass.
    """
    plan_check.note("dist_select", dt, deferred=(not compact) or None)
    mesh, axis, cap = dt.ctx.mesh, dt.ctx.axis, dt.cap
    names = tuple(c.name for c in dt.columns)
    has_pm = dt.pending_mask is not None
    key1 = ("selmask", mesh, axis, cap, names, predicate, len(params),
            has_pm)
    p1 = _select_cache.get(key1)
    if p1 is None:
        def mask_kernel(cnt, leaves, params, *maybe_pm):
            base = maybe_pm[0] if has_pm else (jnp.arange(cap) < cnt[0])
            mask = _masked_predicate(names, predicate, base, leaves,
                                     params)
            n = jnp.sum(mask).astype(jnp.int32)
            return mask, jax.lax.all_gather(n, axis)

        spec = P(axis)
        # check_vma=False: the all_gathered counts are replicated (and so
        # are the params)
        p1 = _cache_put(key1, named_jit(shard_map(
            mask_kernel, mesh=mesh,
            in_specs=(spec, spec, P()) + ((spec,) if has_pm else ()),
            out_specs=(spec, P()), check_vma=False),
            name="dist_ops.dist_select"))
    leaves = tuple((c.data, c.validity) for c in dt.columns)
    args = (dt.counts, leaves, tuple(params)) + (
        (dt.pending_mask,) if has_pm else ())
    mask, cnts = p1(*args)
    if not compact:
        return DTable(dt.ctx, dt.columns, dt.cap, dt.counts,
                      pending_mask=mask, pending_cnts=cnts)
    return _compact_survivors(dt, mask, cnts,
                              ("sel", mesh, cap, names, predicate),
                              "select.gather")


@kernel_factory
def _semi_mask_dense_fn(mesh, axis: str, cap_l: int, cap_r: int,
                        lo: int, hi: int, anti: bool,
                        has_lv: bool, has_rv: bool, stride: int = 1,
                        has_lmask: bool = False, has_rmask: bool = False):
    """Dense-key semi/anti probe: presence bits over the key range [lo,
    hi] (ONE scatter of the right keys) + ONE gather probe of the left
    keys — no sort at all.  The big⋈tiny filter-join shape (probe 60M
    lineitem rows against 13k filtered parts) drops from a 60M-row merged
    sort to two O(n) passes.  Out-of-range keys on EITHER side fail
    loudly via the overflow counter (they could silently miss matches).
    Null == null like the sort kernel: a null left key matches iff the
    right side has any null key.  ``stride`` = world size under modulo
    routing (both sides see one residue class, slots compress by P)."""
    R = -(-(hi - lo + 1) // stride)

    def kernel(l_cnt, r_cnt, lk, lv, rk, rv, *masks):
        rvalid = jnp.arange(cap_r) < r_cnt[0]
        lvalid = jnp.arange(cap_l) < l_cnt[0]
        # deferred-select masks fold straight into row validity: the
        # "table" each side presents is its filtered rows
        mi = 0
        if has_lmask:
            lvalid = lvalid & masks[mi]
            mi += 1
        if has_rmask:
            rvalid = rvalid & masks[mi]
        r_nonnull = rvalid & rv if has_rv else rvalid
        l_nonnull = lvalid & lv if has_lv else lvalid
        r_in = (rk >= lo) & (rk <= hi)
        l_in = (lk >= lo) & (lk <= hi)
        overflow = (jnp.sum(r_nonnull & ~r_in)
                    + jnp.sum(l_nonnull & ~l_in)).astype(jnp.int32)
        # subtract in the key dtype BEFORE narrowing (int64 keys past 2^31
        # would wrap under astype(int32) and alias a valid slot)
        r_base = (rk - lo).astype(jnp.int32)
        l_base = (lk - lo).astype(jnp.int32)
        if stride > 1:
            r_base = r_base // stride
            l_base = l_base // stride
        slot = jnp.where(r_nonnull & r_in, r_base, jnp.int32(R))
        present = jnp.zeros(R, bool).at[slot].set(True, mode="drop")
        hit = l_nonnull & l_in & jnp.take(
            present, jnp.clip(l_base, 0, R - 1))
        if has_lv or has_rv:
            r_has_null = (jnp.any(rvalid & ~rv) if has_rv
                          else jnp.zeros((), bool))
            l_null = lvalid & ~lv if has_lv else jnp.zeros(cap_l, bool)
            hit = hit | (l_null & r_has_null)
        keep = (lvalid & ~hit) if anti else hit
        n = jnp.sum(keep).astype(jnp.int32)
        return keep, jax.lax.all_gather(jnp.stack([n, overflow]), axis)

    spec = P(axis)
    nargs = 6 + int(has_lmask) + int(has_rmask)
    # check_vma=False: the all_gathered counts are replicated
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * nargs,
                               out_specs=(spec, P()), check_vma=False))


@kernel_factory
def _semi_mask_fn(mesh, axis: str, cap_l: int, cap_r: int, anti: bool):
    """Keep-mask for semi/anti join + replicated survivor counts."""

    def kernel(l_cnt, r_cnt, lkeys, lvalids, rkeys, rvalids):
        present = ops_join.semi_mask(lkeys, lvalids, rkeys, rvalids,
                                     l_count=l_cnt[0], r_count=r_cnt[0])
        if anti:
            keep = (jnp.arange(cap_l) < l_cnt[0]) & ~present
        else:
            keep = present  # semi_mask is already False on padding rows
        n = jnp.sum(keep).astype(jnp.int32)
        return keep, jax.lax.all_gather(n, axis)

    spec = P(axis)
    # check_vma=False: the all_gathered counts are replicated
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 6,
                               out_specs=(spec, P()), check_vma=False))


def _dist_semi_or_anti(left: DTable, right: DTable, left_on, right_on,
                       anti: bool, dense_key_range=None,
                       broadcast_threshold=None) -> DTable:
    node = plan_check.note("dist_anti_join" if anti else "dist_semi_join",
                           left, right,
                           dense=dense_key_range is not None or None)
    li_keys = _join_keys(left, left_on)
    ri_keys = _join_keys(right, right_on)
    if len(li_keys) != len(ri_keys):
        raise CylonError(Status(Code.Invalid,
            f"join key arity mismatch: {len(li_keys)} vs {len(ri_keys)}"))
    for li, ri in zip(li_keys, ri_keys):
        if left.columns[li].dtype.type != right.columns[ri].dtype.type:
            raise CylonError(Status(Code.TypeError,
                "semi-join key type mismatch "
                f"{left.columns[li].dtype.type.name} vs "
                f"{right.columns[ri].dtype.type.name}"))
    left, right = _unify_dtable_dicts(left, right, li_keys, ri_keys)
    # the probe only ever reads the right side's KEY columns — drop the
    # rest before the exchange so non-key payload never crosses the wire
    right = dist_project(right, ri_keys)
    ri_keys = list(range(len(ri_keys)))
    world = left.ctx.get_world_size()
    # small build side ⇒ replicate its keys to every shard and probe the
    # UNMOVED left block locally — the big⋈tiny filter-join shape with
    # no exchange on either side (semi/anti emit left rows only, so a
    # replicated right is always sound)
    use_bcast = False
    r_rows = (broadcast.rows_if_small(right, broadcast_threshold)
              if world > 1 else None)
    if r_rows is not None:
        use_bcast = True
        trace.count("join.broadcast")
        plan_check.annotate(
            node, decision="broadcast",
            reason=broadcast.small_side_reason(right, r_rows))
        right._collapse_pending()
        right = broadcast.replicate_table(right)
    elif world > 1:
        plan_check.annotate(node, decision="shuffle",
                            reason=_shuffle_reason(
                                node, "build-side keys not provably "
                                      "under the broadcast threshold"))
    else:
        plan_check.annotate(node, decision="local", reason="world=1")
    # presence bits cost R/stride BYTES per shard — gate against the
    # larger side's capacity (a 1.5M-key range is nothing next to a
    # 15M-row probe side, even when the filtered LEFT block is small)
    kc0 = left.columns[li_keys[0]]
    stride = 1 if (world == 1 or use_bcast) else world
    use_dense = (dense_key_range is not None and len(li_keys) == 1
                 and jnp.issubdtype(kc0.data.dtype, jnp.integer)
                 and not is_dictionary_encoded(kc0.dtype.type)
                 and 0 < (int(dense_key_range[1])
                          - int(dense_key_range[0]) + 1)
                 and -(-(int(dense_key_range[1])
                         - int(dense_key_range[0]) + 1) // stride)
                 <= 4 * max(left.cap, right.cap))
    if world > 1 and not use_bcast:
        trace.count("join.shuffle")
        # deferred-select masks fold into the routing: masked rows go to
        # the dropped partition, so the kernels below see cleared tables
        with trace.span("semijoin.shuffle"):
            if use_dense:
                lo0 = int(dense_key_range[0])
                left = _shuffle_masked(
                    left, _mod_pids(left, li_keys[0], lo0, world))
                right = _shuffle_masked(
                    right, _mod_pids(right, ri_keys[0], lo0, world))
            else:
                left = _shuffle_masked(left, _hash_pids(left, li_keys))
                right = _shuffle_masked(right, _hash_pids(right, ri_keys))
    if not use_dense:
        # the sort-path presence kernel has no mask operand — compact any
        # deferred select first (world > 1 already folded it above)
        left._collapse_pending()
        right._collapse_pending()
    mesh, axis = left.ctx.mesh, left.ctx.axis
    lkcs = [left.columns[i] for i in li_keys]
    rkcs = [right.columns[i] for i in ri_keys]
    kc = lkcs[0]
    if use_dense:
        lo, hi = int(dense_key_range[0]), int(dense_key_range[1])
        rc = rkcs[0]
        has_lm = left.pending_mask is not None
        has_rm = right.pending_mask is not None
        mask_args = (() if not has_lm else (left.pending_mask,)) + \
            (() if not has_rm else (right.pending_mask,))
        with trace.span("semijoin.mask"):
            mask, cnts = _semi_mask_dense_fn(
                mesh, axis, left.cap, right.cap, lo, hi, anti,
                kc.validity is not None, rc.validity is not None,
                stride, has_lm, has_rm)(
                left.counts, right.counts, kc.data, kc.validity,
                rc.data, rc.validity, *mask_args)

        hint_key = ("semid", mesh, left.cap, right.cap, lo, hi, anti,
                    stride, has_lm, has_rm)

        def post(per_shard):
            per_shard = per_shard.reshape(-1, 2)
            if int(per_shard[:, 1].sum()) > 0:
                raise CylonError(Status(Code.Invalid,
                    f"semi-join dense_key_range ({lo}, {hi}) violated: "
                    f"{int(per_shard[:, 1].sum())} keys outside it"))
            return (ops_compact.next_bucket(
                max(int(per_shard[:, 0].max(initial=0)), 1), minimum=8),)

        return _compact_survivors(left, mask, cnts, hint_key,
                                  "semijoin.gather", post=post)
    with trace.span("semijoin.mask"):
        mask, cnts = _semi_mask_fn(mesh, axis, left.cap, right.cap, anti)(
            left.counts, right.counts,
            tuple(c.data for c in lkcs), tuple(c.validity for c in lkcs),
            tuple(c.data for c in rkcs), tuple(c.validity for c in rkcs))
    hint_key = ("semi", mesh, left.cap, right.cap, tuple(li_keys), anti)
    return _compact_survivors(left, mask, cnts, hint_key, "semijoin.gather")


@plan_check.instrument
def dist_semi_join(left: DTable, right: DTable, left_on, right_on,
                   dense_key_range=None, broadcast_threshold=None) -> DTable:
    """Distributed LEFT SEMI join: the rows of ``left`` whose key has at
    least one match in ``right`` — each such row emitted ONCE regardless of
    match multiplicity (SQL EXISTS / IN).  Output schema = left's schema.

    Co-partition both sides on the key hash, then the one-sort presence
    kernel (ops/join.py semi_mask) + survivor compaction per shard.  The
    reference spells EXISTS as inner join + dedup (no semi-join operator in
    table_api.cpp); that shape explodes with match multiplicity and pays a
    near-table-cardinality groupby — this primitive replaces it.  Null
    keys follow the join kernels' convention (null == null).

    ``dense_key_range=(lo, hi)``: single-int-key hint (same contract as
    ``dist_groupby``'s) switching the probe to presence bits over the
    range — one scatter + one gather instead of the merged sort.

    ``broadcast_threshold``: per-call override of the broadcast small-
    side row threshold (None → the session knob, 0 → never broadcast);
    below it the right side's keys replicate to every shard and the
    probe runs against the UNMOVED left block — no exchange at all.
    """
    return _dist_semi_or_anti(left, right, left_on, right_on, anti=False,
                              dense_key_range=dense_key_range,
                              broadcast_threshold=broadcast_threshold)


@plan_check.instrument
def dist_anti_join(left: DTable, right: DTable, left_on, right_on,
                   dense_key_range=None, broadcast_threshold=None) -> DTable:
    """Distributed LEFT ANTI join: the rows of ``left`` whose key has NO
    match in ``right`` (SQL NOT EXISTS).  Complement of ``dist_semi_join``
    over the valid left rows: a null left key equals a null right key, so
    with any null right key present, null-keyed left rows are dropped.
    ``broadcast_threshold`` as in ``dist_semi_join``."""
    return _dist_semi_or_anti(left, right, left_on, right_on, anti=True,
                              dense_key_range=dense_key_range,
                              broadcast_threshold=broadcast_threshold)


@plan_check.instrument
def dist_project(dt: DTable, columns: Sequence[Union[int, str]]) -> DTable:
    """Column subset — zero-copy, like the local Project
    (reference table_api.cpp:1007-1029).  A deferred-select mask rides
    along (projection commutes with row filtering)."""
    plan_check.note("dist_project", dt, keep=len(columns))
    ids = _resolve_ids(dt, columns)
    out = DTable(dt.ctx, [dt.columns[i] for i in ids], dt.cap, dt.counts,
                 dt.pending_mask, dt.pending_cnts)
    # projection never changes row counts — keep the host copy so the
    # broadcast planner's sync-free threshold check stays exact for
    # projected base tables (the semi/anti path projects to keys first)
    out._counts_host = dt._counts_host
    return out


@plan_check.instrument
def dist_with_column(dt: DTable, name: str, fn, out_type,
                     validity_from: Sequence[str] = ()) -> DTable:
    """Append a derived column ``name = fn({col name: data array})``.

    Pure elementwise compute on the already-sharded arrays — no shard_map
    needed; XLA propagates the mesh sharding through the expression.
    ``validity_from`` names input columns whose nulls null the output.
    """
    plan_check.note("dist_with_column", dt, name=name)
    from ..dtypes import DataType as _DT, Type, device_dtype
    if not jax.config.jax_enable_x64:
        # the same logical-type downgrade ingest applies (table._narrow_host):
        # declared type must match what the device actually stores
        out_type = {Type.INT64: Type.INT32, Type.UINT64: Type.UINT32,
                    Type.DOUBLE: Type.FLOAT}.get(out_type, out_type)
    dtype = device_dtype(out_type)
    key = ("withcol", fn, dtype)
    jfn = _select_cache.get(key)
    if jfn is None:
        def with_column(env):
            # the cast rides the program, not an eager device op of its own
            return fn(env).astype(dtype)

        jfn = _cache_put(key, named_jit(with_column,
                                        name="dist_ops.dist_with_column"))
    out = jfn(_env(dt.columns))
    validity = None
    for n in validity_from:
        v = dt.column(n).validity
        if v is not None:
            validity = v if validity is None else (validity & v)
    cols = list(dt.columns) + [DColumn(name, _DT(out_type), out, validity)]
    # a deferred-select mask rides along: the derived column computes
    # garbage on masked-out rows, which stay masked
    return DTable(dt.ctx, cols, dt.cap, dt.counts, dt.pending_mask,
                  dt.pending_cnts)


@plan_check.instrument
def dist_head(dt: DTable, n: int) -> "Table":
    """First ``n`` global rows (shard-major order) as a local Table — the
    small-result gather after a dist_sort (ORDER BY … LIMIT n).  Rows are
    compacted on device first, so the transfer is O(n), not O(P·cap)."""
    plan_check.note("dist_head", dt, n=n)
    return dt.head(n)


@kernel_factory
def _local_sort_multi_fn(mesh, axis: str, cap: int, nkeys: int,
                         ascending: Tuple[bool, ...]):
    def kernel(cnt, key_leaves, leaves):
        order = ops_sort.lexsort_indices_masked(
            tuple(d for d, _ in key_leaves),
            tuple(v for _, v in key_leaves), cnt[0], list(ascending))
        return tuple(ops_gather.take_many(leaves, order, fill_null=False))

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 3, out_specs=spec))


@plan_check.instrument
def dist_sort_multi(dt: DTable, sort_columns: Sequence[Union[int, str]],
                    ascending=True) -> DTable:
    """Distributed multi-key ORDER BY: range-partition on the PRIMARY
    column (equal primary values co-locate, so cross-shard lexicographic
    order holds), then a per-shard masked lexsort over all keys.  One
    shuffle regardless of key count — the scalable spelling of the
    host-side ``compute.sort_multi`` tail every small query uses.
    ``ascending``: one bool or a per-column sequence."""
    plan_check.note("dist_sort_multi", dt, keys=tuple(sort_columns),
                    decision="shuffle" if dt.ctx.get_world_size() > 1
                    else "local")
    dt._collapse_pending()
    key_ids = _resolve_ids(dt, sort_columns)
    asc = ([ascending] * len(key_ids) if isinstance(ascending, bool)
           else list(ascending))
    if dt.ctx.get_world_size() == 1:
        sh = dt
    else:
        with trace.span("sort.sample"):
            splitters = _sample_splitters([(dt, key_ids[0])], asc[0])
        with trace.span("sort.shuffle"):
            sh = _shuffle_by_pids(
                dt, _range_pids(dt, key_ids[0], splitters, asc[0]))
    key_leaves = tuple((sh.columns[i].data, sh.columns[i].validity)
                       for i in key_ids)
    leaves = tuple((c.data, c.validity) for c in sh.columns)
    with trace.span_sync("sort.local") as sp:
        outs = _local_sort_multi_fn(dt.ctx.mesh, dt.ctx.axis, sh.cap,
                                    len(key_ids), tuple(asc))(
            sh.counts, key_leaves, leaves)
        sp.sync(outs)
    cols = [DColumn(c.name, c.dtype, d, v, c.dictionary, c.arrow_type)
            for c, (d, v) in zip(sh.columns, outs)]
    return DTable(dt.ctx, cols, sh.cap, sh.counts)


@kernel_factory
def _local_sort_fn(mesh, axis: str, cap: int, ascending: bool):
    def kernel(cnt, key_leaf, leaves):
        col, validity = key_leaf
        order = ops_sort.sort_indices_masked(col, validity, cnt[0], ascending)
        return tuple(ops_gather.take_many(leaves, order, fill_null=False))

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec,) * 3, out_specs=spec))


@plan_check.instrument
def dist_sort(dt: DTable, sort_column: Union[int, str],
              ascending: bool = True) -> DTable:
    """Distributed sample-sort: sample splitters → range-partition shuffle →
    local sort per shard.  Shard *i*'s rows all precede shard *i+1*'s in the
    requested order, and rows within a shard are sorted (nulls last
    globally), so concatenating shards in mesh order is the sorted table.
    """
    plan_check.note("dist_sort", dt, key=sort_column,
                    decision="shuffle" if dt.ctx.get_world_size() > 1
                    else "local")
    dt._collapse_pending()
    key_i = dt.column_index(sort_column)
    if dt.ctx.get_world_size() == 1:
        sh = dt  # one shard: a local sort is already globally ordered
    else:
        with trace.span("sort.sample"):
            splitters = _sample_splitters([(dt, key_i)], ascending)
        with trace.span("sort.shuffle"):
            sh = _shuffle_by_pids(
                dt, _range_pids(dt, key_i, splitters, ascending))
    kc = sh.columns[key_i]
    leaves = tuple((c.data, c.validity) for c in sh.columns)
    with trace.span_sync("sort.local") as sp:
        outs = _local_sort_fn(dt.ctx.mesh, dt.ctx.axis, sh.cap, ascending)(
            sh.counts, (kc.data, kc.validity), leaves)
        sp.sync(outs)
    cols = [DColumn(c.name, c.dtype, d, v, c.dictionary, c.arrow_type)
            for c, (d, v) in zip(sh.columns, outs)]
    return DTable(dt.ctx, cols, sh.cap, sh.counts)
