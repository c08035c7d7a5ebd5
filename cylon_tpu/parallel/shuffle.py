"""Two-phase static-shape shuffle: the ICI replacement for cylon::net.

The reference moves rows with a user-space progress engine — per-peer
rendezvous state machines over ``MPI_Isend/Irecv`` polled by ``MPI_Test``
(reference: cpp/src/cylon/net/mpi/mpi_channel.cpp:27-243), a queueing
AllToAll with FIN bookkeeping (net/ops/all_to_all.cpp:26-177), and an Arrow
buffer walker on top (arrow/arrow_all_to_all.cpp:80-221).  None of that
machinery exists here: XLA compiles ONE collective per column buffer and the
ICI network does the rest (SURVEY.md §2.4).

Variable-length sends meet XLA's static shapes with the two-phase plan:

  phase 1 (counts)    per-shard count of target ids, one fused
                      compare-and-sum per target (``_count_targets``, no
                      scatter) → ``[P, P]`` matrix on host (a tiny transfer
                      — the analogue of the reference's 8-int header
                      messages).
  phase 2 (exchange)  rows grouped by target via one argsort, padded to a
                      size-class block ``M = bucket(max count)``, one
                      ``lax.all_to_all`` per column leaf, then receiver-side
                      compaction to ``bucket(max rows received)``.

Bucketing both shapes to quarter-step size classes (2^k·{4,5,6,7}/4,
ops/compact.next_bucket) bounds recompilation at ≤25% padding overhead
(SURVEY.md §7 hard part 1).  Peak extra memory is ``P*M`` rows per column —
the padded send buffer; the FIN protocol, backpressure caps and spin loops
of the reference (table_api.cpp:260-261) have no equivalent because the
collective is one program.

Phase 2's COLLECTIVE is a costed decision, not a constant
(docs/tpu_perf_notes.md "Choosing the collective"): the single-shot
``lax.all_to_all`` above is the fast path, but every sized exchange is
priced through the shared cost model (parallel/cost.py) against the
live memory budget, and the chooser may lower it instead as K bounded
chunked rounds, a P−1-round staged ring ``lax.ppermute``, or a
replicate-and-filter ``lax.all_gather`` — identical rows out, choice +
reason annotated on the plan and tallied in ``shuffle.strategy.*``.
"""
from __future__ import annotations

import threading
import time
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from .._jax_compat import shard_map
from jax.sharding import PartitionSpec as P

from .. import trace
from ..observe.compile import kernel_factory, named_jit
from ..observe.locks import OrderedLock
from ..ops import compact as ops_compact
from ..ops import gather as ops_gather
from . import cost


def _bcast(mask: jax.Array, like: jax.Array) -> jax.Array:
    return mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim))


def _count_targets(ids: jax.Array, length: int) -> jax.Array:
    """``jnp.bincount(ids, length=length)`` as int32, without a scatter.

    ``bincount`` lowers to a combining scatter-add: at 4M ids into a
    handful of bins every update collides in the same few slots and the
    chip serializes them (≈ 37 ms, docs/tpu_perf_notes.md).  The
    exchange only counts over mesh extents (``length`` ≤ P + 1), so one
    compare-and-sum per bin costs ``cap × length`` compares, and XLA
    fuses the sibling sums into one pass over the ids.  Same answer for
    every input: negative ids count in bin 0 (bincount clips them), ids
    ≥ ``length`` count nowhere."""
    return jnp.stack([jnp.sum(ids <= 0 if t == 0 else ids == t,
                              dtype=jnp.int32) for t in range(length)])


# Last (send block, receive capacity) per shuffle signature — lets the next
# same-shaped shuffle dispatch the exchange before the host has read the
# count matrix (the count sync then overlaps device work).  Validated after
# the fact; undersized hints re-run with correct sizes.
_block_hints: dict = {}

# The costed chooser's degraded-signature state (docs/robustness.md;
# the chooser itself is parallel/cost.py): shuffle signatures whose
# last sized exchange chose a NON-single-shot lowering.  These skip the
# optimistic dispatch entirely — blocking on the count matrix is the
# price of not allocating an over-budget exchange — and re-run the
# chooser per call until single-shot prices back under budget (then
# they self-promote).  Lock-guarded: the serve layer runs exchanges
# from a dispatcher thread while clients submit (the same hazard class
# as the replica-cache/warn_once races fixed in PR 9); membership reads
# stay lock-free (a stale read only costs one optimistic dispatch or
# one count block, never correctness).
_chunked_keys: set = set()
_chunk_lock = OrderedLock("shuffle.chunk_state")

# The lint contract (graftlint shared-state-unguarded): this module's
# writes to the chooser's signature state hold _chunk_lock.  The hint
# UPDATE inside ops_compact.optimistic_dispatch (update_size_hint on
# the dict we pass it) is deliberately lock-free: a lost grow/shrink
# race costs at most one redone dispatch — hints are validated against
# the true counts every call — and serializing it would put a lock
# acquisition on the optimistic hot path for no correctness gain.
GUARDED_STATE = {"_chunked_keys": "_chunk_lock",
                 "_block_hints": "_chunk_lock"}


def clear_chunk_state() -> None:
    """Forget which signatures are degraded (test isolation)."""
    with _chunk_lock:
        _chunked_keys.clear()


def _mark_degraded(hint_key) -> None:
    with _chunk_lock:
        _chunked_keys.add(hint_key)


def _mark_promoted(hint_key, reseed=None) -> None:
    """Lift a signature's degrade; ``reseed`` re-records its
    single-shot size hint under the same lock hold."""
    with _chunk_lock:
        _chunked_keys.discard(hint_key)
        if reseed is not None:
            _block_hints[hint_key] = (reseed, 0)


class _OverBudget(Exception):
    """Raised by the count-protocol post() when the chooser picks a
    non-single-shot lowering — carries the (already-read) count matrix
    and the priced choice so shuffle_leaves can run the degraded
    strategy without a second host read or a re-choose.  Internal
    control flow, never user-visible."""

    def __init__(self, counts, need, choice, reason):
        super().__init__(f"exchange degraded to {choice.strategy}")
        self.counts = counts
        self.need = need
        self.choice = choice
        self.reason = reason


# The single pricing rule behind the budget comparison, the
# ``shuffle.exchange_bytes_peak`` watermark and admission's
# worst-exchange price now lives in the shared cost model
# (cost.single_shot_bytes); the chunk math is cost.chunk_plan.
_priced_bytes = cost.single_shot_bytes


def _watchdog_dispatch(point: str, thunk):
    """Bounded-timeout guard around one collective dispatch
    (docs/robustness.md "Elasticity").  A collective whose peer died
    mid-flight does not fail on every backend — it can WEDGE, and a
    wedged exchange hangs the serve dispatcher (and every queued
    result()) forever.  With ``CYLON_EXCHANGE_TIMEOUT_MS`` /
    ``config.set_exchange_timeout_ms`` configured, the dispatch (and
    its completion wait) runs on a helper thread bounded by the
    timeout; a breach raises a classified
    :class:`faults.TransientFault` naming the fault point — the
    escalation ladder's transient/topology machinery takes it from
    there — and bumps ``shuffle.watchdog_timeouts``.  The wedged
    helper thread is deliberately LEAKED (daemon): there is no sound
    way to interrupt a stuck collective from the host, and a leaked
    waiter is strictly better than a hung dispatcher.  Disabled
    (``None``, the default) this is one knob read + a direct call."""
    from ..config import exchange_timeout_ms
    timeout_ms = exchange_timeout_ms()
    if not timeout_ms:
        return thunk()
    box: dict = {}
    done = threading.Event()

    def run():
        try:
            out = thunk()
            jax.block_until_ready(out)
            box["out"] = out
        except BaseException as e:  # graftlint: ok[broad-except] — the
            box["err"] = e          # waiter re-raises it on its thread
        finally:
            done.set()

    th = threading.Thread(target=run, name="cylon-exchange-watchdog",
                          daemon=True)
    th.start()
    if not done.wait(timeout_ms / 1e3):
        from .. import faults
        trace.count("shuffle.watchdog_timeouts")
        raise faults.TransientFault(point, detail=(
            f"exchange watchdog: collective dispatch at {point!r} "
            f"exceeded CYLON_EXCHANGE_TIMEOUT_MS={timeout_ms} ms — "
            "treating the exchange as wedged (transient class; the "
            "recovery ladder retries or re-meshes)"))
    if "err" in box:
        raise box["err"]
    return box["out"]


def _account(counts: np.ndarray, rbytes: int, combine=None,
             owner: "str | None" = None, split=None) -> None:
    """Exchange-volume accounting shared by the single-shot post() and
    the chunked path (docs/observability.md).  Counts what ACTUALLY
    crosses the wire: for a partial-group exchange (``combine`` set)
    that is the partial rows, never the pre-aggregation input rows —
    the count matrix here was computed over the partial table, so the
    off-diagonal IS the partials moved.  ``owner`` attributes the bytes
    to a subsystem (``groupby.bytes_moved`` feeds bench's
    ``tpch_*_groupby_bytes_saved`` column).

    With a non-trivial ``(slow, fast)`` ``split``, the rows whose
    sender and receiver sit in DIFFERENT slow groups additionally tally
    ``shuffle.rows_sent_slow`` — the expensive-edge traffic the
    hierarchical lowerings exist to shrink.  Combine-spec exchanges
    skip this here: their slow-axis crossing depends on the executed
    lowering (the hierarchical pre-combine collapses it), so the
    dispatch path tallies the exact post-combine number instead."""
    moved = int(counts.sum() - np.trace(counts))
    trace.count("shuffle.rows_sent", moved)
    trace.count("shuffle.bytes_sent", moved * rbytes)
    if owner == "groupby":
        trace.count("groupby.bytes_moved", moved * rbytes)
    if combine is not None:
        # every partial row entering the combine exchange (diagonal
        # included: rows staying home are still partials produced)
        trace.count("groupby.partials_rows", int(counts.sum()))
    elif split is not None:
        slow, fast = split
        c = np.asarray(counts)
        if slow > 1 and fast > 1 and c.shape[0] == slow * fast:
            slow_of = np.arange(slow * fast) // fast
            cross = slow_of[:, None] != slow_of[None, :]
            trace.count("shuffle.rows_sent_slow", int(c[cross].sum()))


def _axis_split_of(ctx):
    """``topology.axis_split(ctx)`` reduced to the chooser's contract:
    the (slow, fast) pair when it is NON-trivial and tiles the live
    world, else None (flat mesh — no hierarchy to price)."""
    from .. import topology
    slow, fast = topology.axis_split(ctx)
    if slow > 1 and fast > 1 and slow * fast == ctx.get_world_size():
        return (slow, fast)
    return None


# THE sizing rule for a single-shot exchange, shared by the optimistic
# post(), the degraded steady-state branch and every candidate price —
# owned by the cost model so no two paths can dispatch different size
# classes for the same counts.
_sizes_from_counts = cost.exchange_sizes


def _warn_skew(Pn: int, hint_key, per_recv: np.ndarray,
               outcap: int) -> None:
    """The hot-key-skew warning, rate-limited to ONCE per shuffle
    signature per session (a skewed query in a loop used to log one line
    per call).  See docs/tpu_perf_notes.md 'hot-key skew'."""
    mean_recv = max(float(per_recv.mean()), 1.0)
    # the 64k floor keeps toy tables (where count noise looks like
    # skew) quiet; below that size the blowup is bytes, not a hazard
    if not (Pn > 1 and outcap >= 65536 and outcap > 4 * mean_recv):
        return
    from .. import logging as glog
    glog.warn_once(
        ("shuffle.skew", hint_key),
        "skewed exchange: hottest receiver gets %d rows "
        "(%.1fx the %.0f mean); every shard's receive block is "
        "bucketed to %d — peak memory ~%.1fx the data. "
        "See docs/tpu_perf_notes.md 'hot-key skew'. "
        "(warned once per shuffle signature per session)",
        int(per_recv.max(initial=0)), per_recv.max() / mean_recv,
        mean_recv, outcap, outcap / mean_recv)


@kernel_factory
def _counts_fn(mesh, axis: str, nparts: int):
    """pid [P*cap] → counts [P, P]; counts[s, t] = rows sender s has for t.

    The matrix comes back replicated (an all_gather of P ints per shard)
    so every controller process can ``device_get`` it — a sharded count
    output would span non-addressable devices under multi-host."""

    def kernel(pid_blk):
        cnt = _count_targets(pid_blk, nparts + 1)[:nparts]
        return jax.lax.all_gather(cnt, axis)

    # check_vma=False: the all_gather makes the output replicated, which
    # shard_map cannot statically infer
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=P(axis), out_specs=P(),
                               check_vma=False))


@kernel_factory
def _exchange_fn(mesh, axis: str, nparts: int, block: int, outcap: int,
                 spec_axes=None):
    """The exchange program: group-by-target, all_to_all, compact.

    Returns a jitted fn ``(pid, leaves_tuple) -> (counts[P], new_leaves)``
    reused across calls with the same (mesh, block, outcap); differing leaf
    structures hit jit's own cache.

    ``spec_axes`` (the 2-level lowering, docs/tpu_perf_notes.md
    "Hierarchical collectives"): when set — e.g. ``(MESH_SLOW_AXIS,
    MESH_FAST_AXIS)`` on a ``ctx.mesh2d`` mesh — the leaves shard over
    BOTH axes while the collective itself runs only over ``axis``
    (``nparts`` = that axis's extent): the fast stage of the
    hierarchical shuffle is exactly this kernel restricted to the fast
    axis."""
    spec = P(spec_axes if spec_axes is not None else axis)

    def kernel(pid_blk, leaves):
        cap = pid_blk.shape[0]
        order = jnp.argsort(pid_blk, stable=True)     # rows grouped by target
        cnt = _count_targets(pid_blk, nparts + 1)[:nparts]
        offs = jnp.concatenate([jnp.zeros((1,), cnt.dtype),
                                jnp.cumsum(cnt)])[:-1]
        jj = jnp.arange(block, dtype=jnp.int32)[None, :]
        gather_pos = jnp.clip(offs[:, None] + jj, 0, cap - 1)
        send_idx = jnp.take(order, gather_pos)        # [P, block]
        valid_send = jj < cnt[:, None]

        # the 8-int header of mpi_channel.cpp, as one int exchange
        rcnt = jax.lax.all_to_all(cnt, axis, 0, 0, tiled=True)  # [P]
        recv_valid = (jnp.arange(block, dtype=jnp.int32)[None, :]
                      < rcnt[:, None]).reshape(-1)    # [P*block]
        vidx = ops_compact.compact_indices(recv_valid, outcap, fill=0)
        newcount = jnp.sum(rcnt).astype(jnp.int32)
        keep = jnp.arange(outcap, dtype=jnp.int32) < newcount

        outs = [None] * len(leaves)
        if all(lf.ndim == 1 for lf in leaves):
            # width-classed wide path: one gather + ONE all_to_all + one
            # compaction per byte-width group instead of per column
            for M, positions, dtypes in ops_gather.pack_columns(leaves):
                S = jnp.take(M, send_idx, axis=0)       # [P, block, C]
                S = jnp.where(valid_send[:, :, None], S,
                              jnp.zeros((), S.dtype))
                R = jax.lax.all_to_all(S, axis, 0, 0, tiled=True)
                flat = R.reshape((nparts * block, R.shape[2]))
                C = jnp.take(flat, vidx, axis=0)
                C = jnp.where(keep[:, None], C, jnp.zeros((), C.dtype))
                for col, pos in zip(ops_gather.unpack_columns(C, dtypes),
                                    positions):
                    outs[pos] = col
        else:  # trailing-dim leaves: per-leaf path
            for pos, leaf in enumerate(leaves):
                as_bool = leaf.dtype == jnp.bool_
                x = leaf.astype(jnp.uint8) if as_bool else leaf
                S = jnp.take(x, send_idx, axis=0)       # [P, block, ...]
                S = jnp.where(_bcast(valid_send, S), S,
                              jnp.zeros((), S.dtype))
                R = jax.lax.all_to_all(S, axis, 0, 0, tiled=True)
                flat = R.reshape((nparts * block,) + R.shape[2:])
                C = jnp.take(flat, vidx, axis=0)
                C = jnp.where(_bcast(keep, C), C, jnp.zeros((), C.dtype))
                outs[pos] = C.astype(jnp.bool_) if as_bool else C
        return newcount[None], tuple(outs)

    f = shard_map(kernel, mesh=mesh,
                  in_specs=(spec, spec),
                  out_specs=(spec, spec))
    return named_jit(f)


# ---------------------------------------------------------------------------
# staged lowerings (docs/tpu_perf_notes.md "Choosing the collective"):
# the two catalogue entries beyond the all_to_all pair.  Both produce
# the same [P*outcap] result block as the single-shot exchange — the
# ring up to intra-shard row order (arrival order is me, me-1, … not
# sender order; no consumer depends on intra-shard order after a
# shuffle), the allgather byte-identical (gathered order IS sender
# order).
# ---------------------------------------------------------------------------

@kernel_factory
def _ring_exchange_fn(mesh, axis: str, nparts: int, block: int,
                      outcap: int, spec_axes=None):
    """Staged ring exchange: P−1 ``lax.ppermute`` rounds, round r moving
    each shard's whole (me → me+r) cell as ONE [block] buffer — the
    collective-permute decomposition of arXiv:2112.01075.  Only one
    send + one receive block live per round (vs the all_to_all's
    [P, block] pair), so the transient is ``2·block`` rows — the shape
    the cost model prices as ``ring``.  Received rows scatter straight
    into the result block at the running offset; own rows land first.

    ``spec_axes``: as in :func:`_exchange_fn` — shard over the full
    2-level mesh, permute only over ``axis`` (``nparts`` = that axis's
    extent); the slow stage of the hierarchical shuffle is this ring
    restricted to the slow axis, fed pids already rewritten to
    slow-axis coordinates."""
    spec = P(spec_axes if spec_axes is not None else axis)

    def kernel(pid_blk, leaves):
        me = jax.lax.axis_index(axis)
        iota = jnp.arange(block, dtype=jnp.int32)
        sel0 = pid_blk == me
        vidx = ops_compact.compact_indices(sel0, outcap, fill=0)
        total = jnp.sum(sel0).astype(jnp.int32)
        keep0 = jnp.arange(outcap, dtype=jnp.int32) < total
        wide = all(lf.ndim == 1 for lf in leaves)
        if wide:
            # width-classed wide path: one ppermute per byte-width group
            # per round instead of per column (same packing as the
            # single-shot kernel — the cost model's round count stays an
            # honest dispatch count on wide tables)
            groups = ops_gather.pack_columns(leaves)
            srcs = [M for M, _, _ in groups]
        else:  # trailing-dim leaves: per-leaf path
            srcs = [lf.astype(jnp.uint8) if lf.dtype == jnp.bool_ else lf
                    for lf in leaves]
        # round 0 (no wire): own rows compact straight into the result
        accs = []
        for x in srcs:
            c0 = jnp.take(x, vidx, axis=0)
            accs.append(jnp.where(_bcast(keep0, c0), c0,
                                  jnp.zeros((), c0.dtype)))
        # rounds 1..P-1: each round's routing state (send index,
        # receive slots, validity lanes) is computed INSIDE the loop so
        # only one round's worth is live next to the payload buffers —
        # the _RING_ROUTING_BYTES term price_ring charges
        for r in range(1, nparts):
            sel = pid_blk == ((me + r) % nparts)
            idx = ops_compact.compact_indices(sel, block, fill=0)
            cnt = jnp.sum(sel).astype(jnp.int32)
            valid = iota < cnt
            perm = [(i, (i + r) % nparts) for i in range(nparts)]
            rcnt = jax.lax.ppermute(cnt[None], axis, perm)[0]
            rvalid = iota < rcnt
            slots = jnp.where(rvalid, total + iota, jnp.int32(outcap))
            for j, x in enumerate(srcs):
                S = jnp.take(x, idx, axis=0)
                S = jnp.where(_bcast(valid, S), S, jnp.zeros((), S.dtype))
                R = jax.lax.ppermute(S, axis, perm)
                R = jnp.where(_bcast(rvalid, R), R, jnp.zeros((), R.dtype))
                accs[j] = accs[j].at[slots].set(R, mode="drop")
            total = total + rcnt
        outs = [None] * len(leaves)
        if wide:
            for (_, positions, dtypes), A in zip(groups, accs):
                for col, pos in zip(ops_gather.unpack_columns(A, dtypes),
                                    positions):
                    outs[pos] = col
        else:
            for pos, (lf, A) in enumerate(zip(leaves, accs)):
                outs[pos] = (A.astype(jnp.bool_)
                             if lf.dtype == jnp.bool_ else A)
        return total[None], tuple(outs)

    f = shard_map(kernel, mesh=mesh,
                  in_specs=(spec, spec),
                  out_specs=(spec, spec))
    return named_jit(f)


@kernel_factory
def _allgather_exchange_fn(mesh, axis: str, nparts: int, outcap: int):
    """Replicate-and-filter exchange: one ``lax.all_gather`` per leaf
    (plus the pid lane), each shard keeping the gathered rows targeted
    at it.  1 round; the gathered [P·cap] intermediates are the price —
    cheaper than the all_to_all's 2·P·block pair exactly when one
    sender-side cell dominates (block > cap/2, the hot-target shape).
    Output rows land in gathered order == sender order, byte-identical
    to the single-shot exchange."""

    def kernel(pid_blk, leaves):
        me = jax.lax.axis_index(axis)
        gpid = jax.lax.all_gather(pid_blk, axis, tiled=True)   # [P*cap]
        sel = gpid == me
        vidx = ops_compact.compact_indices(sel, outcap, fill=0)
        total = jnp.sum(sel).astype(jnp.int32)
        keep = jnp.arange(outcap, dtype=jnp.int32) < total

        def filter_own(x):
            g = jax.lax.all_gather(x, axis, tiled=True)
            C = jnp.take(g, vidx, axis=0)
            return jnp.where(_bcast(keep, C), C, jnp.zeros((), C.dtype))

        outs = [None] * len(leaves)
        if all(lf.ndim == 1 for lf in leaves):
            # width-classed wide path: one all_gather per byte-width
            # group instead of per column (the single-shot kernel's
            # packing, shared here so the 1-round latency claim holds
            # on wide tables too)
            for M, positions, dtypes in ops_gather.pack_columns(leaves):
                A = filter_own(M)
                for col, pos in zip(ops_gather.unpack_columns(A, dtypes),
                                    positions):
                    outs[pos] = col
        else:  # trailing-dim leaves: per-leaf path
            for pos, leaf in enumerate(leaves):
                as_bool = leaf.dtype == jnp.bool_
                A = filter_own(leaf.astype(jnp.uint8) if as_bool else leaf)
                outs[pos] = A.astype(jnp.bool_) if as_bool else A
        return total[None], tuple(outs)

    # check_vma=False: the all_gathered intermediates are replicated,
    # which shard_map cannot statically infer (same note as broadcast.py)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(P(axis), P(axis)),
                               out_specs=(P(axis), P(axis)),
                               check_vma=False))


def _staged_exchange(ctx, pid, leaves, choice, outcap_total: int):
    """Dispatch one ring/allgather exchange (the chooser already sized
    it: ``choice.sizes`` carries (block|cap, outcap)).  Returns the same
    ``(leaves, counts, outcap)`` contract as the single-shot dispatch."""
    mesh, axis, Pn = ctx.mesh, ctx.axis, ctx.get_world_size()
    trace.count_max("shuffle.exchange_bytes_peak", choice.peak_bytes)
    dm0 = _devmem_before(ctx)
    t0 = time.perf_counter()
    with trace.span_sync("shuffle.exchange") as sp:
        if choice.strategy == cost.RING:
            block = choice.sizes[0]
            newcounts, outs = _watchdog_dispatch(
                "shuffle.exchange",
                lambda: _ring_exchange_fn(mesh, axis, Pn, block,
                                          outcap_total)(pid,
                                                        tuple(leaves)))
        else:
            newcounts, outs = _watchdog_dispatch(
                "shuffle.exchange",
                lambda: _allgather_exchange_fn(
                    mesh, axis, Pn, outcap_total)(pid, tuple(leaves)))
        sp.sync(outs)
    _note_exchange_ms(ctx, choice, t0, dm0)
    return list(outs), newcounts, outcap_total


def _note_choice(choice, reason: str, nparts=None) -> None:
    """Record one chooser decision: the per-strategy tally counter +
    the plan annotation (static EXPLAIN and ANALYZE both render it —
    docs/query_planner.md "annotation surface").  Annotations APPEND:
    an op that runs several exchanges (a shuffle join co-partitions
    both sides under one node) keeps every choice, not just the
    last.  When the chooser priced a (slow, fast) split the choice
    carries a per-device ``slow_wire_bytes``; with ``nparts`` that
    tallies the mesh-wide ``shuffle.bytes_sent_slow`` — the number the
    hierarchy smoke and the scaling bench compare across lowerings."""
    from ..analysis import plan_check
    from ..resilience import note_strategy_choice
    trace.count(cost.strategy_counter(choice.strategy))
    if nparts is not None and choice.slow_wire_bytes:
        trace.count("shuffle.bytes_sent_slow",
                    int(choice.slow_wire_bytes) * int(nparts))
    # the recovery driver's per-attempt record: a resource-classed
    # failure demotes the chooser off whatever was picked here
    note_strategy_choice(choice.strategy)
    if choice.strategy != cost.SINGLE_SHOT:
        trace.count("shuffle.strategy.downgrades")
        # a downgrade is exactly the decision a post-mortem wants to
        # see in context — one bounded ring event, not a log line
        from ..observe import flightrec
        flightrec.note("exchange_choice", strategy=choice.strategy,
                       reason=reason[:200])
    plan_check.annotate_append("exchange", f"{choice.strategy}: {reason}")


def _mesh_device(ctx):
    """First device of the context's mesh — the device whose allocator
    the devmem sampler reads (single-controller: one device's watermark
    is representative; every shard runs the same program)."""
    try:
        return next(iter(ctx.mesh.devices.flat))
    except Exception:  # graftlint: ok[broad-except] — device layout
        return None     # varies by jax version; None = default device


def _devmem_before(ctx):
    """Pre-exchange device-memory snapshot (observe.devmem) — taken
    ONLY under an active plan capture: ``memory_stats`` is a runtime
    call and the live-buffer walk is O(live arrays), so production
    dispatch pays one thread-local read and nothing else."""
    from ..analysis import plan_check
    if not plan_check.capturing():
        return None
    from ..observe import devmem
    try:
        return devmem.snapshot(_mesh_device(ctx))
    except Exception:  # graftlint: ok[broad-except] — the sample is
        return None     # telemetry; the exchange must run regardless


def _note_exchange_ms(ctx, choice, t0: float, dm0=None) -> None:
    """Annotate one completed exchange with its predicted-vs-observed
    measurements — BOTH audit columns of the cost model:

      * ``exchange_ms`` — predicted from the meshprobe-fitted
        coefficients of THIS mesh (cost.predicted_ms) vs wall-clock
        from ``t0`` — under ANALYZE the span sync makes the observation
        completion-honest, under plain async dispatch it is
        dispatch-side only.  Silent without a probed profile: the
        annotation reports measurements, it never invents them.
      * ``peak`` — the strategy's priced ``peak_bytes`` vs the
        device-truth transient between the ``dm0`` snapshot and now
        (observe.devmem; allocator watermark where the backend has one,
        live-buffer delta — a documented lower bound — on CPU).  Also
        watermarked as ``devmem.peak_bytes``, the measured twin of
        ``shuffle.exchange_bytes_peak``.

    Early-exits outside a plan capture (annotate_append would be a
    no-op anyway) so plain production dispatch pays one thread-local
    read, not a profile lookup or an allocator read."""
    from ..analysis import plan_check
    if not plan_check.capturing():
        return
    from . import meshprobe
    profile = meshprobe.get_profile(ctx)
    if profile is not None:
        pred = cost.predicted_ms(choice, profile)
        if pred is not None:
            observed = (time.perf_counter() - t0) * 1e3
            plan_check.annotate_append(
                "exchange_ms",
                f"{choice.strategy}: predicted {pred:.2f} / observed "
                f"{observed:.2f} ms")
    if dm0 is not None:
        from ..observe import devmem
        try:
            after = devmem.snapshot(_mesh_device(ctx))
        except Exception:  # graftlint: ok[broad-except] — telemetry
            after = None
        obs = devmem.observed_exchange_bytes(dm0, after)
        if obs is not None:
            trace.count_max("devmem.peak_bytes", obs)
            plan_check.annotate_append(
                "peak",
                f"{choice.strategy}: predicted {choice.peak_bytes} / "
                f"observed {obs} bytes ({after.source})")



# ---------------------------------------------------------------------------
# chunked degraded exchange (docs/robustness.md): when the chooser picks
# the chunked lowering, the rows of every (sender, target) cell are
# split into K contiguous rank-slices and moved by K bounded all_to_all
# rounds reusing _exchange_fn, each round's compacted output folded into
# the final block receiver-side.  The rounds share ONE (block, outcap)
# size class, so the whole degraded path costs at most three extra
# compiles (rank, slice, fold) + one exchange shape.
# ---------------------------------------------------------------------------

@kernel_factory
def _rank_fn(mesh, axis: str, nparts: int):
    """pid [P*cap] → per-row rank within its (shard, target) cell.

    rank[i] = |{j < i in the same shard block : pid[j] == pid[i]}| —
    the stable intra-cell position that round k's slice [k·C, (k+1)·C)
    selects on.  One argsort, same cost shape as the counts phase."""

    def kernel(pid_blk):
        cap = pid_blk.shape[0]
        order = jnp.argsort(pid_blk, stable=True)
        cnt = _count_targets(pid_blk, nparts + 1)
        offs = jnp.concatenate([jnp.zeros((1,), cnt.dtype),
                                jnp.cumsum(cnt)])[:-1]      # [nparts+1]
        sorted_pid = jnp.take(pid_blk, order)
        rank_sorted = (jnp.arange(cap, dtype=jnp.int32)
                       - jnp.take(offs, sorted_pid).astype(jnp.int32))
        return jnp.zeros((cap,), jnp.int32).at[order].set(rank_sorted)

    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=P(axis), out_specs=P(axis)))


@kernel_factory
def _slice_pids_fn(nparts: int):
    """(pid, rank, lo, hi) → pid with rows outside the [lo, hi) rank
    slice retargeted to P (dropped by the exchange).  lo/hi are traced
    operands, so every round of every chunked shuffle shares one
    compiled program per world size."""

    def f(pid, rank, lo, hi):
        keep = (rank >= lo) & (rank < hi) & (pid < nparts)
        return jnp.where(keep, pid, jnp.int32(nparts))

    return named_jit(f)


@kernel_factory
def _fold_fn(mesh, axis: str, incap: int, outcap: int, fresh: bool):
    """Receiver-side concatenation of one round's compacted output into
    the final block: per shard, scatter the round's ``rcnt`` valid rows
    at offset ``acc_cnt`` (rounds land back-to-back — the final block is
    exactly what the single-shot exchange would have produced, up to
    intra-shard row order).  ``fresh`` builds the zeroed accumulator for
    round 0 instead of taking one as input."""

    def scatter(acc, leaf, tgt, keep):
        x = jnp.where(_bcast(keep, leaf), leaf, jnp.zeros((), leaf.dtype))
        return acc.at[tgt].set(x, mode="drop")

    if fresh:
        def kernel(rcnt_blk, rleaves):
            idx = jnp.arange(incap, dtype=jnp.int32)
            keep = idx < rcnt_blk[0]
            tgt = jnp.where(keep, idx, jnp.int32(outcap))
            outs = tuple(
                scatter(jnp.zeros((outcap,) + lf.shape[1:], lf.dtype),
                        lf, tgt, keep) for lf in rleaves)
            return rcnt_blk, outs

        f = shard_map(kernel, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=(P(axis), P(axis)))
    else:
        def kernel(acc_cnt_blk, rcnt_blk, acc_leaves, rleaves):
            idx = jnp.arange(incap, dtype=jnp.int32)
            keep = idx < rcnt_blk[0]
            tgt = jnp.where(keep, acc_cnt_blk[0] + idx, jnp.int32(outcap))
            outs = tuple(scatter(acc, lf, tgt, keep)
                         for acc, lf in zip(acc_leaves, rleaves))
            return acc_cnt_blk + rcnt_blk, outs

        f = shard_map(kernel, mesh=mesh,
                      in_specs=(P(axis),) * 4, out_specs=(P(axis), P(axis)))
    return named_jit(f)


@kernel_factory
def _fold_combine_fn(mesh, axis: str, spec, incap: int, acc_cap: int,
                     out_cap: int, fresh: bool):
    """Receiver-side fold of one chunk round that COMBINES partial-group
    rows by key instead of concatenating them — the hierarchical
    variant of the fused aggregation exchange (docs/tpu_perf_notes.md
    "aggregation below the exchange").

    ``spec`` is the static leaf-layout combiner: ``(key_slots,
    val_slots)`` with ``key_slots = ((data_idx, validity_idx|None), …)``
    and ``val_slots = ((data_idx, validity_idx|None, comb_op), …)`` over
    the wire leaf positions.  Each fold runs the local groupby kernel
    over ``concat(accumulator, round)`` with the COMBINE ops (sum of
    sums / sum of counts / min of mins / max of maxes), so the
    accumulator holds one row per distinct group seen so far and its
    capacity scales with groups, not received rows.  Output dtypes are
    cast back to the wire dtypes: the block feeds further folds and
    finally the DTable whose column dtypes the sender declared.  Rows
    past the returned group count are unspecified, masked by the next
    fold's row validity / the DTable counts — the standard contract."""
    from ..ops import gather as ops_gather
    from ..ops import groupby as ops_groupby
    key_slots, val_slots = spec

    def combine(leaves, row_valid):
        kpairs = tuple((leaves[d], None if v is None else leaves[v])
                       for d, v in key_slots)
        key_idx, outs, out_valids, ng = ops_groupby.groupby_aggregate(
            tuple(d for d, _ in kpairs), tuple(v for _, v in kpairs),
            tuple(leaves[d] for d, _v, _op in val_slots),
            tuple(None if v is None else leaves[v]
                  for _d, v, _op in val_slots),
            tuple(op for _d, _v, op in val_slots),
            row_valid=row_valid, out_capacity=out_cap)
        keys_out = ops_gather.take_many(kpairs, key_idx, fill_null=False)
        folded = [None] * len(leaves)
        for (d, v), (kd, kv) in zip(key_slots, keys_out):
            folded[d] = kd
            if v is not None:
                folded[v] = kv
        for (d, v, _op), arr, av in zip(val_slots, outs, out_valids):
            folded[d] = arr.astype(leaves[d].dtype)
            if v is not None:
                folded[v] = (av if av is not None
                             else jnp.ones(out_cap, bool))
        return tuple(folded), ng

    if fresh:
        def kernel(rcnt_blk, rleaves):
            row_valid = jnp.arange(incap) < rcnt_blk[0]
            outs, ng = combine(rleaves, row_valid)
            return ng[None], outs

        f = shard_map(kernel, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=(P(axis), P(axis)))
    else:
        def kernel(acc_cnt_blk, rcnt_blk, acc_leaves, rleaves):
            merged = tuple(jnp.concatenate([a, r])
                           for a, r in zip(acc_leaves, rleaves))
            row_valid = jnp.concatenate(
                [jnp.arange(acc_cap) < acc_cnt_blk[0],
                 jnp.arange(incap) < rcnt_blk[0]])
            outs, ng = combine(merged, row_valid)
            return ng[None], outs

        f = shard_map(kernel, mesh=mesh,
                      in_specs=(P(axis),) * 4, out_specs=(P(axis), P(axis)))
    return named_jit(f)


# ---------------------------------------------------------------------------
# hierarchical lowerings (docs/tpu_perf_notes.md "Hierarchical
# collectives"): the two-level decomposition of one redistribution over
# a (slow, fast) mesh split.  Stage 1 is the single-shot all_to_all
# kernel restricted to the FAST axis (every row moves to the device in
# its own slow group whose fast coordinate matches the target's), so
# the slow edge then carries each row AT MOST ONCE — stage 2 is the
# ring restricted to the SLOW axis.  The fused-aggregation variant
# folds stage 1's landing by (keys, target pid) BEFORE the slow stage,
# so only per-group partials ever cross the expensive edge.
# ---------------------------------------------------------------------------

@kernel_factory
def _fast_targets_fn(nparts: int, fast: int):
    """pid → stage-1 target: the FAST coordinate of the final owner.
    Padding rows (pid == nparts) map to the drop lane ``fast`` — NOT
    ``nparts % fast``, which would alias a real fast coordinate and
    ship padding over the wire.  Elementwise, so plain jit over the
    sharded pid lane (no collective, no axis name)."""

    def kernel(pid):
        return jnp.where(pid < nparts, pid % fast,
                         jnp.int32(fast)).astype(jnp.int32)

    return named_jit(kernel)


@kernel_factory
def _stage2_pids_fn(mesh, spec_axes, nparts: int, fast: int, nslow: int,
                    incap: int):
    """Stage-1 landing pids → stage-2 targets (the SLOW coordinate of
    the final owner).  Rows past the landing count and padding pids map
    to the drop lane ``nslow``."""
    spec = P(spec_axes)

    def kernel(cnt_blk, pid_blk):
        valid = jnp.arange(incap, dtype=jnp.int32) < cnt_blk[0]
        return jnp.where(valid & (pid_blk < nparts), pid_blk // fast,
                         jnp.int32(nslow)).astype(jnp.int32)

    f = shard_map(kernel, mesh=mesh, in_specs=(spec, spec),
                  out_specs=spec)
    return named_jit(f)


@kernel_factory
def _slow_counts_fn(mesh, spec_axes, slow_axis: str, fast_axis: str,
                    nparts: int, fast: int, nslow: int, incap: int):
    """Per-device histogram of stage-2 targets, replicated — the count
    protocol of the slow stage.  Gathered over (slow, fast) in that
    order so the flattened leading dim IS the flat device id
    (p = s·F + f); the host reads one [P, S] matrix and sizes every
    ring round exactly."""
    spec = P(spec_axes)

    def kernel(cnt_blk, pid_blk):
        valid = jnp.arange(incap, dtype=jnp.int32) < cnt_blk[0]
        ts = jnp.where(valid & (pid_blk < nparts), pid_blk // fast,
                       jnp.int32(nslow))
        c = _count_targets(ts, nslow + 1)[:nslow]
        return jax.lax.all_gather(c, (slow_axis, fast_axis))

    # check_vma=False: the all_gather replicates the output, which
    # shard_map cannot statically infer (same note as _counts_fn)
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec, spec),
                               out_specs=P(), check_vma=False))


@kernel_factory
def _slow_cell_fn(mesh, spec_axes, slow_axis: str, nparts: int, fast: int,
                  nslow: int, r: int, block: int, incap: int):
    """One slow-ring round of the hierarchical COMBINE path: each
    device selects its post-combine rows destined to slow group
    (me + r) % S, compacts them into a [block] cell, and (r > 0)
    ppermutes the cell r hops around the slow axis.  Round 0 moves
    nothing over the wire — own-group rows feed the first fold
    directly, which is why the cross-only ``block`` prices the wire.
    Returns (received count, received leaves) for the receiver-side
    fold; one send + one receive cell live at a time."""
    spec = P(spec_axes)

    def kernel(cnt_blk, pid_blk, leaves):
        me = jax.lax.axis_index(slow_axis)
        valid_row = jnp.arange(incap, dtype=jnp.int32) < cnt_blk[0]
        ts = jnp.where(valid_row & (pid_blk < nparts), pid_blk // fast,
                       jnp.int32(nslow))
        sel = ts == ((me + r) % nslow)
        idx = ops_compact.compact_indices(sel, block, fill=0)
        cnt = jnp.sum(sel).astype(jnp.int32)
        valid = jnp.arange(block, dtype=jnp.int32) < cnt
        outs = []
        if r == 0:
            rcnt = cnt
            for lf in leaves:
                C = jnp.take(lf, idx, axis=0)
                outs.append(jnp.where(_bcast(valid, C), C,
                                      jnp.zeros((), C.dtype)))
        else:
            perm = [(i, (i + r) % nslow) for i in range(nslow)]
            rcnt = jax.lax.ppermute(cnt[None], slow_axis, perm)[0]
            rvalid = jnp.arange(block, dtype=jnp.int32) < rcnt
            for lf in leaves:
                as_bool = lf.dtype == jnp.bool_
                x = lf.astype(jnp.uint8) if as_bool else lf
                S = jnp.take(x, idx, axis=0)
                S = jnp.where(_bcast(valid, S), S, jnp.zeros((), S.dtype))
                R = jax.lax.ppermute(S, slow_axis, perm)
                R = jnp.where(_bcast(rvalid, R), R, jnp.zeros((), R.dtype))
                outs.append(R.astype(jnp.bool_) if as_bool else R)
        return rcnt[None], tuple(outs)

    f = shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                  out_specs=(spec, spec))
    return named_jit(f)


def _hierarchical_exchange(ctx, pid, leaves, counts: np.ndarray,
                           rbytes: int, outcap_total: int, choice,
                           combine=None):
    """Dispatch one two-level exchange (strategy ``hierarchical`` /
    ``hierarchical-combine``; priced by cost.price_hierarchical /
    price_hier_combine, sized by cost.hier_plan from the SAME count
    matrix).  Same ``(leaves, counts, outcap)`` contract as every other
    lowering; rows come back identical up to intra-shard order.

    Plain path: fast-stage all_to_all (pid rides as an extra int32
    lane), then a slow-stage ring keyed on ``pid // F``.  Combine path
    (``combine`` = the fold spec): stage 1's landing is folded by
    (keys, pid) BEFORE the slow axis — the pid is a hash of the keys,
    so adding it as a key slot changes nothing about the grouping — and
    each slow-ring round's received cell folds into the accumulator, so
    the slow edge only ever carries per-group partials
    (``groupby.axis_precombine_rows`` is the exact row count)."""
    from ..context import MESH_FAST_AXIS, MESH_SLOW_AXIS
    Pn = ctx.get_world_size()
    S, F, block1, outcap1, block2, _outcap_ss = choice.sizes
    mesh2 = ctx.mesh2d((S, F))
    axes = (MESH_SLOW_AXIS, MESH_FAST_AXIS)
    trace.count_max("shuffle.exchange_bytes_peak", choice.peak_bytes)
    dm0 = _devmem_before(ctx)
    t0 = time.perf_counter()
    try:
        with trace.span_sync("shuffle.exchange") as sp:
            tf = _fast_targets_fn(Pn, F)(pid)
            cnt1, outs1 = _watchdog_dispatch(
                "shuffle.exchange",
                lambda: _exchange_fn(mesh2, MESH_FAST_AXIS, F, block1,
                                     outcap1, axes)(
                    tf, tuple(leaves) + (pid,)))
            pid_idx = len(leaves)
            if combine is None:
                pid2 = _stage2_pids_fn(mesh2, axes, Pn, F, S,
                                       outcap1)(cnt1, outs1[pid_idx])
                cnt2, outs2 = _watchdog_dispatch(
                    "shuffle.exchange",
                    lambda: _ring_exchange_fn(mesh2, MESH_SLOW_AXIS, S,
                                              block2, outcap_total,
                                              axes)(pid2, outs1))
                sp.sync(outs2)
                return list(outs2[:pid_idx]), cnt2, outcap_total
            # combine path: axis-local pre-combine, then per-round folds
            trace.count("groupby.axis_precombine")
            key_slots, val_slots = combine
            spec2 = (tuple(key_slots) + ((pid_idx, None),),
                     tuple(val_slots))
            ngc, comb = _fold_combine_fn(mesh2, axes, spec2, outcap1,
                                         0, outcap1, True)(cnt1, outs1)
            trace.count("shuffle.fold_combined")
            c2c = np.asarray(ops_compact._read_counts(
                _slow_counts_fn(mesh2, axes, MESH_SLOW_AXIS,
                                MESH_FAST_AXIS, Pn, F, S,
                                outcap1)(ngc, comb[pid_idx])))
            c2c = c2c.reshape(Pn, S)
            slow_of = np.arange(Pn) // F
            fast_of = np.arange(Pn) % F
            cross = c2c.copy()
            cross[np.arange(Pn), slow_of] = 0
            moved_slow = int(cross.sum())
            trace.count("shuffle.rows_sent_slow", moved_slow)
            trace.count("groupby.axis_precombine_rows", moved_slow)
            block_own = ops_compact.next_bucket(
                max(int(c2c[np.arange(Pn), slow_of].max(initial=0)), 1),
                minimum=8)
            block_x = ops_compact.next_bucket(
                max(int(cross.max(initial=0)), 1), minimum=8)
            per_recv = np.zeros((Pn,), np.int64)
            acc = None
            acc_cnt = None
            acc_cap = 0
            for r in range(S):
                blk_r = block_own if r == 0 else block_x
                src = ((slow_of - r) % S) * F + fast_of
                per_recv += c2c[src, slow_of]
                out_cap = ops_compact.next_bucket(
                    max(int(per_recv.max(initial=0)), 1), minimum=8)
                rcnt, cells = _watchdog_dispatch(
                    "shuffle.exchange",
                    lambda blk=blk_r, rr=r: _slow_cell_fn(
                        mesh2, axes, MESH_SLOW_AXIS, Pn, F, S, rr, blk,
                        outcap1)(ngc, comb[pid_idx], tuple(comb)))
                if r == 0:
                    acc_cnt, acc = _fold_combine_fn(
                        mesh2, axes, spec2, blk_r, 0, out_cap,
                        True)(rcnt, cells)
                else:
                    acc_cnt, acc = _fold_combine_fn(
                        mesh2, axes, spec2, blk_r, acc_cap, out_cap,
                        False)(acc_cnt, rcnt, acc, cells)
                trace.count("shuffle.fold_combined")
                trace.count_max("shuffle.exchange_bytes_peak",
                                choice.peak_bytes
                                + (acc_cap + out_cap) * rbytes)
                acc_cap = out_cap
            sp.sync(acc)
            return list(acc[:pid_idx]), acc_cnt, acc_cap
    finally:
        _note_exchange_ms(ctx, choice, t0, dm0)


# The chunk math (rounds, C, block, outcap_round) lives in the shared
# cost model so the chooser prices the SAME plan the degraded path runs.
_chunk_sizes = cost.chunk_plan


def _staged_spill_exchange(ctx, pid, leaves, counts: np.ndarray,
                           rbytes: int, budget: int, outcap_total: int,
                           choice, combine=None):
    """The host-tier lowering (docs/out_of_core.md): stage the payload
    OUT to the spill pool, then stream it back in ``rounds``
    rank-sliced morsels — each a [P, bucket(C)]-shaped bounded
    all_to_all over a MORSEL-sized staged block — folded receiver-side
    exactly like the chunked rounds (plain concat, or fold-by-key under
    a ``combine`` spec).  Unlike the chunked path, the full-size input
    block is not needed on device while the rounds run; morsel k+1's
    host assembly + async ``device_put`` overlaps morsel k's device
    compute through the HostPipeline.  Identical rows out, same
    ``(block, outcap)`` size classes as the chunked plan, so the extra
    compile cost is zero."""
    from ..spill import pool as spill_pool
    from .streaming import HostPipeline
    mesh, axis, Pn = ctx.mesh, ctx.axis, ctx.get_world_size()
    rounds, C, block, outcap_k = choice.sizes
    trace.count("spill.exchanges")
    trace.count_max("shuffle.exchange_bytes_peak", choice.peak_bytes)
    from ..analysis import plan_check
    plan_check.annotate(
        degraded=f"staged-spill shuffle: {rounds} host-staged morsels "
                 f"of <= {C} rows/cell ({choice.peak_bytes} B/morsel "
                 f"vs {budget} B budget)")
    cap = pid.shape[0] // max(Pn, 1)
    morsel_cap = ops_compact.next_bucket(
        max(min(Pn * C, cap), 1), minimum=8)
    # the host budget covers EVERY stage-out (config contract): reserve
    # the payload PLUS the in-flight staged-morsel working copies (two
    # can be live at once under the HostPipeline prefetch) against the
    # pool before transferring — exhaustion raises the typed OOM the
    # escalation ladder replans on, instead of a raw host OOM
    payload_bytes = int(pid.nbytes) + sum(int(lf.nbytes) for lf in leaves)
    per_row = 4 + sum(int(np.dtype(lf.dtype).itemsize)
                      * int(np.prod(lf.shape[1:], dtype=np.int64))
                      for lf in leaves)
    reserve_bytes = payload_bytes + 2 * Pn * morsel_cap * per_row
    the_pool = spill_pool.get_pool()
    the_pool.reserve_transient(reserve_bytes)
    try:
        hosts = spill_pool.stage_out_arrays([pid] + list(leaves))
    except BaseException:
        the_pool.release_transient(reserve_bytes)
        raise
    hpid = hosts[0].astype(np.int32, copy=False)
    hleaves = hosts[1:]
    # host-side rank of every row within its (shard, target) cell —
    # the same quantity _rank_fn computes on device for the chunked
    # path; morsel k stages exactly the rank slice [k·C, (k+1)·C)
    rank = np.empty(Pn * cap, np.int64)
    for i in range(Pn):
        blk = hpid[i * cap:(i + 1) * cap]
        order = np.argsort(blk, kind="stable")
        cell = np.bincount(blk, minlength=Pn + 2)
        offs = np.concatenate([[0], np.cumsum(cell)])[:-1]
        rank_sorted = np.arange(cap) - offs[blk[order]]
        rank[i * cap:(i + 1) * cap][order] = rank_sorted
    exchange = _exchange_fn(mesh, axis, Pn, block, outcap_k)

    def stage(k: int):
        pid_m = np.full(Pn * morsel_cap, Pn, np.int32)
        lm = [np.zeros((Pn * morsel_cap,) + h.shape[1:], h.dtype)
              for h in hleaves]
        for i in range(Pn):
            lo_, hi_ = i * cap, (i + 1) * cap
            sel = ((hpid[lo_:hi_] < Pn)
                   & (rank[lo_:hi_] >= k * C)
                   & (rank[lo_:hi_] < (k + 1) * C))
            rows = np.nonzero(sel)[0]
            n = len(rows)
            if n:
                at = i * morsel_cap
                pid_m[at:at + n] = hpid[lo_:hi_][rows]
                for lm_j, h in zip(lm, hleaves):
                    lm_j[at:at + n] = h[lo_:hi_][rows]
        devs = spill_pool.stage_in_arrays(ctx, [pid_m] + lm)
        return devs[0], tuple(devs[1:])

    dm0 = _devmem_before(ctx)
    t_ex0 = time.perf_counter()
    acc_cnt = acc = None
    acc_cap = outcap_total
    acc_groups = None
    pipe = HostPipeline(name="spill-exchange")
    try:
        with trace.span_sync("shuffle.exchange") as sp:
            nxt = pipe.submit(lambda: stage(0))
            for k in range(rounds):
                pid_k, leaves_k = nxt.wait()
                if k + 1 < rounds:
                    nxt = pipe.submit(lambda k=k: stage(k + 1))
                trace.count("spill.morsels")
                cnt_k, outs_k = _watchdog_dispatch(
                    "shuffle.exchange",
                    lambda pid_k=pid_k, leaves_k=leaves_k:
                        exchange(pid_k, leaves_k))
                if combine is None:
                    if acc is None:
                        acc_cnt, acc = _fold_fn(mesh, axis, outcap_k,
                                                outcap_total, True)(
                            cnt_k, outs_k)
                    else:
                        acc_cnt, acc = _fold_fn(mesh, axis, outcap_k,
                                                outcap_total, False)(
                            acc_cnt, cnt_k, acc, outs_k)
                    continue
                trace.count("shuffle.fold_combined")
                if acc is None:
                    prev_cap, out_cap = 0, outcap_k
                    acc_cnt, acc = _fold_combine_fn(
                        mesh, axis, combine, outcap_k, 0, out_cap,
                        True)(cnt_k, outs_k)
                else:
                    recv_k = np.minimum(np.maximum(counts - k * C, 0),
                                        C).sum(axis=0)
                    bound = acc_groups + recv_k
                    prev_cap = acc_cap
                    out_cap = ops_compact.next_bucket(
                        max(int(bound.max(initial=0)), 1), minimum=8)
                    acc_cnt, acc = _fold_combine_fn(
                        mesh, axis, combine, outcap_k, acc_cap, out_cap,
                        False)(acc_cnt, cnt_k, acc, outs_k)
                acc_cap = out_cap
                trace.count_max(
                    "shuffle.exchange_bytes_peak",
                    choice.peak_bytes + (prev_cap + acc_cap) * rbytes)
                if k + 1 < rounds:
                    acc_groups = np.asarray(
                        ops_compact._read_counts(acc_cnt))
            sp.sync(acc)
    finally:
        pipe.close()
        the_pool.release_transient(reserve_bytes)
    _note_exchange_ms(ctx, choice, t_ex0, dm0)
    if combine is not None:
        return list(acc), acc_cnt, acc_cap
    return list(acc), acc_cnt, outcap_total


def _chunked_exchange(ctx, pid, leaves, counts: np.ndarray, rbytes: int,
                      budget: int, outcap_total: int, combine=None,
                      plan=None, choice=None):
    """Run the K bounded rounds and fold them into the final
    [P*outcap_total] block.  Peak per-round transient is priced ≤ budget
    (best-effort once the per-cell floor C=1 is reached); the final
    block itself is the shuffle's RESULT — the same capacity the
    single-shot exchange returns — and is not a transient this path can
    shrink (the uniform-capacity DTable model, docs/tpu_perf_notes.md
    'hot-key skew').

    With a ``combine`` spec (the payload is a partial-group table —
    dist_groupby_fused's combine exchange) the receiver-side fold
    COMBINES rows by group key between rounds instead of concatenating
    them (:func:`_fold_combine_fn`): the accumulator block holds one row
    per distinct group received so far, so the result capacity — and
    ``shuffle.exchange_bytes_peak`` — scales with distinct groups, not
    received rows.  The per-round fold capacity is sized exactly from
    the previous fold's group count (one small blocking read per round —
    the degraded path already trades syncs for bounded memory)."""
    mesh, axis, Pn = ctx.mesh, ctx.axis, ctx.get_world_size()
    # ``plan`` is the chooser's already-computed (rounds, C, block,
    # outcap_round) — priced and executed from ONE derivation; the
    # re-derivation below only serves legacy direct callers
    rounds, C, block, outcap_k = (plan if plan is not None else
                                  _chunk_sizes(Pn, counts, rbytes, budget))
    trace.count("shuffle.chunked")
    trace.count("shuffle.chunked_rounds", rounds)
    priced_k = _priced_bytes(Pn, (block, outcap_k), rbytes)
    trace.count_max("shuffle.exchange_bytes_peak", priced_k)
    if priced_k > budget:
        from .. import logging as glog
        glog.warn_once(
            ("shuffle.budget_floor", ctx.mesh, Pn),
            "memory budget %d B is below the smallest possible exchange "
            "round (%d B at 1 row/cell) — running best-effort chunked "
            "rounds anyway", budget, priced_k)
    from ..analysis import plan_check
    plan_check.annotate(
        degraded=f"chunked shuffle: {rounds} rounds of <= {C} rows/cell "
                 f"({priced_k} B/round vs {budget} B budget)")
    dm0 = _devmem_before(ctx)
    t_ex0 = time.perf_counter()
    with trace.span_sync("shuffle.exchange") as sp:
        rank = _rank_fn(mesh, axis, Pn)(pid)
        exchange = _exchange_fn(mesh, axis, Pn, block, outcap_k)
        slicer = _slice_pids_fn(Pn)
        acc_cnt = acc = None
        acc_cap = outcap_total
        acc_groups = None  # per-shard distinct-group counts (combine)
        for k in range(rounds):
            pid_k = slicer(pid, rank, jnp.int32(k * C),
                           jnp.int32((k + 1) * C))
            cnt_k, outs_k = _watchdog_dispatch(
                "shuffle.exchange",
                lambda pid_k=pid_k: exchange(pid_k, tuple(leaves)))
            if combine is None:
                if acc is None:
                    acc_cnt, acc = _fold_fn(mesh, axis, outcap_k,
                                            outcap_total, True)(cnt_k,
                                                                outs_k)
                else:
                    acc_cnt, acc = _fold_fn(mesh, axis, outcap_k,
                                            outcap_total, False)(
                        acc_cnt, cnt_k, acc, outs_k)
                continue
            trace.count("shuffle.fold_combined")
            if acc is None:
                # round 0 already combines: duplicate groups from the P
                # senders collapse to one row each — capacity outcap_k
                # (groups ≤ received rows) can never overflow
                prev_cap, out_cap = 0, outcap_k
                acc_cnt, acc = _fold_combine_fn(
                    mesh, axis, combine, outcap_k, 0, out_cap,
                    True)(cnt_k, outs_k)
            else:
                # exact sizing, no overflow possible: groups after the
                # fold ≤ groups in the accumulator (read from the last
                # fold) + rows this round adds (host count-matrix math)
                recv_k = np.minimum(np.maximum(counts - k * C, 0),
                                    C).sum(axis=0)
                bound = acc_groups + recv_k
                prev_cap = acc_cap
                out_cap = ops_compact.next_bucket(
                    max(int(bound.max(initial=0)), 1), minimum=8)
                acc_cnt, acc = _fold_combine_fn(
                    mesh, axis, combine, outcap_k, acc_cap, out_cap,
                    False)(acc_cnt, cnt_k, acc, outs_k)
            acc_cap = out_cap
            # the fold's transient: the round blocks + both accumulator
            # generations live at once
            trace.count_max(
                "shuffle.exchange_bytes_peak",
                priced_k + (prev_cap + acc_cap) * rbytes)
            if k + 1 < rounds:
                acc_groups = np.asarray(
                    ops_compact._read_counts(acc_cnt))
        sp.sync(acc)
    if choice is not None:
        _note_exchange_ms(ctx, choice, t_ex0, dm0)
    if combine is not None:
        return list(acc), acc_cnt, acc_cap
    return list(acc), acc_cnt, outcap_total


def _choose(Pn: int, cap: int, counts: np.ndarray, rbytes: int,
            budget: int, combine, ctx=None):
    """Run the costed chooser for one sized exchange: enumerate the
    candidate lowerings (parallel/cost.py), restrict combine-spec
    payloads to the single-shot/chunked pair (only the chunked rounds
    implement the receiver-side fold-by-key), and pick under the live
    budget — honoring the ``CYLON_EXCHANGE_STRATEGY`` override and,
    with ``CYLON_COST_MEASURED=1`` and a probed mesh profile for
    ``ctx``'s mesh, ranking by measured collective time instead of the
    (rounds, wire) proxy."""
    from .. import resilience
    from ..config import (cost_measured_enabled, exchange_strategy,
                          spill_enabled)
    from . import meshprobe
    forced = exchange_strategy()
    profile = meshprobe.get_profile(ctx) if ctx is not None else None
    measured = cost_measured_enabled() and profile is not None
    split = _axis_split_of(ctx) if ctx is not None else None
    # the escalation ladder's replan arm (docs/robustness.md): inside a
    # demoted recovery attempt the cheapest catalogue strategies are
    # excluded — the lowering that just failed must not be re-picked
    exclude = resilience.exchange_demotions()
    if forced is None and not measured and not exclude:
        # fast path: a feasible single-shot provably wins the
        # (rounds, wire, catalogue) order — fewest rounds, least wire —
        # so the common under-budget exchange never pays the chunk-plan
        # halving loop or the staged pricing.  (Measured ranking must
        # NOT take it: the measurement may disagree with the proxy —
        # that disagreement is the point of the A/B.  The per-edge
        # measured ranking is ALSO where a hierarchical lowering can
        # genuinely win, so it never short-circuits here.)
        block, outcap, _ = cost.exchange_sizes(counts)
        ss = cost.price_single_shot(Pn, block, outcap, rbytes)
        if ss.peak_bytes <= budget:
            ss = cost.slow_share(ss, Pn, split)
            return ss, f"{ss.describe()} <= budget {budget} B", True
    cands = cost.enumerate_strategies(Pn, cap, counts, rbytes, budget,
                                      staged_ok=combine is None,
                                      spill_ok=spill_enabled(),
                                      split=split)
    return cost.choose(cands, budget, forced, profile=profile,
                       measured=measured, exclude=exclude)


def shuffle_leaves(ctx, pid: jax.Array, leaves: Sequence[jax.Array],
                   combine=None, owner: "str | None" = None
                   ) -> Tuple[List[jax.Array], jax.Array, int]:
    """Repartition rows of sharded ``leaves`` by target ids ``pid``.

    ``pid`` is [P*cap] int32 sharded over the mesh: the target shard per
    row, with padding rows set to P (dropped).  Returns
    ``(new_leaves [P*outcap], counts [P], outcap)``.

    reference: cpp/src/cylon/table_api.cpp:214-297 (Shuffle) — here the
    HashPartition+split+AllToAll+concat pipeline is phase1+phase2.

    Costed redistribution (docs/tpu_perf_notes.md "Choosing the
    collective"): every sized exchange runs through the shared cost
    model (parallel/cost.py), which prices the candidate lowerings —
    single-shot all_to_all, K-round chunked all_to_all, staged ring
    ppermute, allgather replicate-and-filter — on (peak device bytes,
    wire bytes, round count) against the live
    ``resilience.exchange_budget()`` and picks the cheapest feasible
    sequence.  Single-shot keeps winning whenever it fits (the fast
    path is unchanged); over budget the exchange degrades to the
    cheapest fitting strategy instead of hardcoding the chunked path —
    identical rows out, the choice + reason annotated on the plan
    (``exchange=…`` in EXPLAIN / EXPLAIN ANALYZE) and tallied in the
    ``shuffle.strategy.*`` counters.  The choice is re-priced on every
    execution, so cached plans re-decide under a changed
    ``CYLON_MEMORY_BUDGET``.

    ``combine`` declares the payload a partial-group table (the fused
    aggregation exchange, dist_groupby_fused): a static leaf-layout spec
    ``(key_slots, val_slots)`` — see :func:`_fold_combine_fn` — that the
    chunked degraded path uses to fold rounds together BY GROUP KEY, so
    the accumulated block (and ``shuffle.exchange_bytes_peak``) scales
    with distinct groups instead of received rows.  The single-shot path
    ignores it (the local combine downstream handles concatenated
    partials).  ``owner`` attributes exchange bytes to a subsystem for
    the per-family bench accounting (docs/observability.md).
    """
    mesh, axis, Pn = ctx.mesh, ctx.axis, ctx.get_world_size()
    hint_key = (mesh, Pn, pid.shape[0])
    if Pn > 1:
        # one logical exchange per call (a chunked degraded exchange is
        # still ONE exchange — its rounds count separately); with the
        # broadcast gather counters this derives the per-query
        # exchange_count bench emits (docs/observability.md)
        trace.count("shuffle.exchanges")
    # payload width of one row across every exchanged leaf (the shared
    # pricing rule behind both byte counters — observe.row_bytes)
    from .. import observe, resilience
    from ..analysis._abstract import is_abstract
    rbytes = max(observe.row_bytes(leaves), 1)
    # the (slow, fast) mesh factorization, resolved ONCE per exchange
    # from the LIVE context (a degraded survivor mesh re-resolves and
    # re-prices): trivial split → flat accounting, no hierarchy priced
    split = _axis_split_of(ctx)
    with trace.span("shuffle.counts"):
        cnt_dev = _counts_fn(mesh, axis, Pn)(pid)  # async dispatch
    # abstract plan runs (analysis/plan_check) price from zeroed counts
    # and must never degrade — checked on BOTH pid and the staged count
    # output (a concrete closure-captured table under an ambient
    # eval_shape trace has concrete pid but a tracer cnt_dev); the
    # budget guardrail is a RUNTIME concern
    budget = None if (is_abstract(pid) or is_abstract(cnt_dev)) \
        else resilience.exchange_budget()

    cap = pid.shape[0] // max(Pn, 1)

    def dispatch(sizes):
        return _watchdog_dispatch(
            "shuffle.exchange",
            lambda: _exchange_fn(mesh, axis, Pn, *sizes)(pid,
                                                         tuple(leaves)))

    def post(counts):
        # exchange-volume accounting lives HERE, not after the dispatch:
        # post() sees the count matrix in immediate mode AND at the
        # deferred flush, so bench pipelines (run_pipeline) tally the
        # same rows/bytes a blocking run would (docs/observability.md)
        _account(counts, rbytes, combine, owner, split=split)
        block, outcap, per_recv = _sizes_from_counts(counts)
        # Skew cliff: EVERY shard's receive block is sized to the HOTTEST
        # receiver (XLA collectives are ragged-free — uniform shapes or
        # nothing), so one hot key/range makes the global arrays ≈ P× the
        # data.  Warn when the detour is real; mitigations are documented
        # in docs/tpu_perf_notes.md (pre-aggregated groupby never routes
        # raw hot rows; sample-sort splitters spread dense ranges; and
        # when the skewed exchange is a join moving a small side, the
        # broadcast join skips this shuffle entirely — see broadcast.py
        # and docs/tpu_perf_notes.md "broadcast vs shuffle joins").
        _warn_skew(Pn, hint_key, per_recv, outcap)
        need = (block, outcap)
        if budget is None:
            # abstract plan run: static pricing from zeroed counts —
            # never degrades; the annotation keeps the strategy surface
            # visible in static EXPLAIN (docs/query_planner.md)
            from ..analysis import plan_check
            plan_check.annotate_append(
                "exchange", "single-shot (static: priced from zeroed "
                            "counts; re-chosen per execution)")
            return need
        # the costed chooser (docs/tpu_perf_notes.md "Choosing the
        # collective"): a non-single-shot choice — the skew case that
        # used to hardcode the chunked path — aborts the optimistic
        # dispatch instead of letting XLA allocate it.  In immediate
        # mode the raise carries the choice out.  Inside a deferred
        # flush, raising would corrupt the batch walk: the hinted
        # dispatch already RAN (its output is valid — hints are sizes,
        # and over-budget is not undersized), so mark the signature,
        # fail the flush explicitly, and let the replay re-enter
        # through the degraded branch below (which re-chooses).
        choice, reason, _ = _choose(Pn, cap, counts, rbytes,
                                    budget, combine, ctx=ctx)
        if choice.strategy == cost.SINGLE_SHOT:
            _note_choice(choice, reason, nparts=Pn)
            return need
        _mark_degraded(hint_key)
        if ops_compact.in_flush():
            ops_compact.invalidate_flush()
        else:
            # drop the stale optimism before aborting the dispatch
            # (in the flush path the caller's update_size_hint
            # re-records need right after post() returns anyway —
            # the _chunked_keys gate is what keeps an over-budget
            # hint from being dispatched; promotion overwrites it)
            with _chunk_lock:
                _block_hints.pop(hint_key, None)
            raise _OverBudget(np.asarray(counts).copy(), need, choice,
                              reason)
        return need

    if (hint_key in _chunked_keys or resilience.exchange_demotions()) \
            and budget is not None:
        # degraded steady state — or a demoted recovery attempt
        # (resilience.demoted_exchanges: the replanned re-execution
        # must not re-dispatch the single-shot program that just
        # failed): skip the optimistic dispatch (its single-shot
        # program is exactly what blew the budget) and block on the
        # counts — riding the same batched device_get as any queued
        # validations in deferred mode — then re-choose: the chooser
        # either picks a degraded strategy again or self-promotes the
        # signature back to single-shot
        if ops_compact.deferred_mode():
            ok, vals = ops_compact.flush_pending_with((cnt_dev,))
            if not ok:
                ops_compact._abort_if_poisoned()
            counts = np.asarray(vals[0])
        else:
            counts = ops_compact._read_counts(cnt_dev)
        _account(counts, rbytes, combine, owner, split=split)
        block, outcap, per_recv = _sizes_from_counts(counts)
        _warn_skew(Pn, hint_key, per_recv, outcap)
        need = (block, outcap)
        choice, reason, _ = _choose(Pn, cap, counts, rbytes,
                                    budget, combine, ctx=ctx)
        _note_choice(choice, reason, nparts=Pn)
        if choice.strategy == cost.SINGLE_SHOT:
            # this call prices back under budget (the data shrank):
            # promote to the single-shot path and reseed the optimism
            # for the NEXT same-signature call
            _mark_promoted(hint_key, reseed=need)
            trace.count_max("shuffle.exchange_bytes_peak",
                            choice.peak_bytes)
            dm0 = _devmem_before(ctx)
            t_ex0 = time.perf_counter()
            with trace.span_sync("shuffle.exchange") as sp:
                newcounts, outs = dispatch(need)
                sp.sync(outs)
            _note_exchange_ms(ctx, choice, t_ex0, dm0)
            return list(outs), newcounts, outcap
        if choice.strategy == cost.CHUNKED:
            return _chunked_exchange(ctx, pid, leaves, counts, rbytes,
                                     budget, outcap, combine,
                                     plan=choice.sizes, choice=choice)
        if choice.strategy == cost.STAGED_SPILL:
            return _staged_spill_exchange(ctx, pid, leaves, counts,
                                          rbytes, budget, outcap,
                                          choice, combine)
        if choice.strategy in (cost.HIERARCHICAL, cost.HIER_COMBINE):
            return _hierarchical_exchange(ctx, pid, leaves, counts,
                                          rbytes, outcap, choice,
                                          combine)
        return _staged_exchange(ctx, pid, leaves, choice, outcap)

    try:
        dm0 = _devmem_before(ctx)
        t_ex0 = time.perf_counter()
        with trace.span_sync("shuffle.exchange") as sp:
            (newcounts, outs), used, counts = \
                ops_compact.optimistic_dispatch(
                    _block_hints, hint_key, dispatch, cnt_dev, post)
            sp.sync(outs)
    except _OverBudget as ob:
        # the hinted dispatch (if any) was launched before the counts
        # came back — its result is discarded; the chosen degraded
        # strategy recovers from the counts the exception carries
        _note_choice(ob.choice, ob.reason, nparts=Pn)
        if ob.choice.strategy == cost.CHUNKED:
            return _chunked_exchange(ctx, pid, leaves, ob.counts, rbytes,
                                     budget, ob.need[1], combine,
                                     plan=ob.choice.sizes,
                                     choice=ob.choice)
        if ob.choice.strategy == cost.STAGED_SPILL:
            return _staged_spill_exchange(ctx, pid, leaves, ob.counts,
                                          rbytes, budget, ob.need[1],
                                          ob.choice, combine)
        if ob.choice.strategy in (cost.HIERARCHICAL, cost.HIER_COMBINE):
            return _hierarchical_exchange(ctx, pid, leaves, ob.counts,
                                          rbytes, ob.need[1], ob.choice,
                                          combine)
        return _staged_exchange(ctx, pid, leaves, ob.choice, ob.need[1])
    if budget is not None:
        trace.count_max("shuffle.exchange_bytes_peak",
                        _priced_bytes(Pn, used, rbytes))
        _note_exchange_ms(
            ctx, cost.price_single_shot(Pn, used[0], used[1], rbytes),
            t_ex0, dm0)
    return list(outs), newcounts, used[1]
