"""Streaming (chunked) distributed join + the async host ingest/export lane.

TPU-native answer to the reference's ``ArrowJoin`` streaming pipeline
(reference: cpp/src/cylon/arrow/arrow_join.cpp + join tail of
arrow_all_to_all.cpp — right table resident, left batches streamed through
the AllToAll and joined incrementally as they land).  The right side is
co-partitioned ONCE and stays resident; the left side is processed in
``chunks`` row-slices of the padded block, so the left shuffle's in-flight
buffers are one chunk wide — the analogue of the reference's bounded
AllToAll buffers (its backpressure cap).  Chunks run serially: each
chunk's join sizes its output from a host-side count read (the two-phase
capacity protocol), which is a sync point by design.

Per-chunk outputs are re-packed to the front of each shard block
(concat + compaction) so the result honours the DTable invariant
(rows [0, count) valid).  Chunk widths and the packed output capacity are
rounded to ``next_bucket`` size classes to preserve the bounded-recompile
property of the one-shot path.

Semantically identical to ``dist_join`` for INNER/LEFT; RIGHT/FULL_OUTER
fall back to the one-shot join — a right row is unmatched only with
respect to ALL left chunks, which a streaming pass cannot decide per
chunk (the reference's ArrowJoin streams inner joins only).

:class:`HostPipeline` is the second streaming primitive here: a bounded
FIFO worker lane for HOST-side work — Arrow/pandas conversion of one
query's result, or pre-ingest of the next query's frames — so the
host conversion of query N overlaps the device compute of query N+1
(the serving layer's export path, docs/serving.md).  Device dispatch
stays on the submitting thread; only the host-boundary tail moves.
"""
from __future__ import annotations

import math
import queue as _queue
import threading
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from .._jax_compat import shard_map
from jax.sharding import PartitionSpec as P

from .. import trace
from ..analysis import plan_check
from ..config import JoinConfig
from ..observe.compile import kernel_factory, named_jit
from ..observe.locks import OrderedLock
from ..ops import compact as ops_compact
from ..ops import gather as ops_gather
from .dist_ops import (_copartition, _join_copartitioned, _join_prologue,
                       dist_join)
from .dtable import DColumn, DTable


@kernel_factory
def _slice_fn(nparts: int, cap: int, lo: int, hi: int):
    w = hi - lo

    def f(a):
        return a.reshape(nparts, cap)[:, lo:hi].reshape(nparts * w)

    return named_jit(f)


def _slice_rows(dt: DTable, lo: int, hi: int) -> DTable:
    """Rows [lo, hi) of every shard's padded block, as a narrower DTable."""
    f = _slice_fn(dt.nparts, dt.cap, lo, hi)
    w = hi - lo
    cols = [DColumn(c.name, c.dtype, f(c.data),
                    None if c.validity is None else f(c.validity),
                    c.dictionary, c.arrow_type) for c in dt.columns]
    counts = jnp.clip(dt.counts - lo, 0, w).astype(jnp.int32)
    return DTable(dt.ctx, cols, w, counts)


@kernel_factory
def _repack_fn(mesh, axis: str, caps: Tuple[int, ...], outcap: int,
               has_v: Tuple[bool, ...]):
    """Concat per-chunk shard blocks and compact valid rows to the front,
    into an ``outcap``-wide (size-class) block."""

    def kernel(cnts, leaves):
        cnts = cnts.reshape(-1)  # [1, K] shard block -> [K] chunk counts
        valid = jnp.concatenate([jnp.arange(ck) < cnts[k]
                                 for k, ck in enumerate(caps)])
        idx, total = ops_compact.mask_to_indices(valid, outcap)
        concat = []
        for per_chunk, hv in zip(leaves, has_v):
            data = jnp.concatenate([d for d, _ in per_chunk])
            if hv:
                v = jnp.concatenate([
                    jnp.ones(ck, bool) if vv is None else vv
                    for (_, vv), ck in zip(per_chunk, caps)])
            else:
                v = None
            concat.append((data, v))
        outs = tuple(ops_gather.take_many(concat, idx, fill_null=False))
        return outs, total[None].astype(jnp.int32)  # outs: (d, v)

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec, spec), out_specs=(spec, spec)))


def _concat_compact(parts: List[DTable]) -> DTable:
    if len(parts) == 1:
        return parts[0]
    head = parts[0]
    ctx = head.ctx
    caps = tuple(p.cap for p in parts)
    outcap = ops_compact.next_bucket(sum(caps), minimum=8)
    has_v = tuple(any(p.columns[i].validity is not None for p in parts)
                  for i in range(head.num_columns))
    cnts = jnp.stack([p.counts for p in parts], axis=1)  # [P, K]
    leaves = tuple(
        tuple((p.columns[i].data, p.columns[i].validity) for p in parts)
        for i in range(head.num_columns))
    outs, counts = _repack_fn(ctx.mesh, ctx.axis, caps, outcap, has_v)(
        cnts, leaves)
    cols = [DColumn(c.name, c.dtype, d, v if has else None,
                    c.dictionary, c.arrow_type)
            for c, (d, v), has in zip(head.columns, outs, has_v)]
    return DTable(ctx, cols, outcap, counts)


@plan_check.instrument
def dist_join_streaming(left: DTable, right: DTable, config: JoinConfig,
                        chunks: int = 4) -> DTable:
    """Chunked distributed join of ``left`` against a resident ``right``.

    ``chunks`` bounds the left side's in-flight shuffle buffers to
    ``~cap/chunks`` rows per shard; the right side is co-partitioned once.
    Output row SET equals ``dist_join``'s (row order is chunk-major, which
    the DTable contract leaves undefined).  See the module docstring for
    the INNER/LEFT restriction.
    """
    if left.is_spilled and config.join_type.value in ("inner", "left") \
            and not right.is_spilled:
        # out-of-core probe side (docs/out_of_core.md): the leaves live
        # in the host-tier spill pool — stream them from there instead
        # of letting the prologue's first leaf access fault the whole
        # block back in (which would re-create exactly the residency
        # this lowering exists to bound)
        from ..spill import morsel as spill_morsel
        return spill_morsel.morsel_join(left, right, config)
    if (chunks <= 1 or left.cap < chunks
            or config.join_type.value in ("right", "full_outer")):
        from .. import logging as glog
        reason = ("RIGHT/FULL_OUTER cannot stream (unmatched-right needs "
                  "all left chunks)"
                  if config.join_type.value in ("right", "full_outer")
                  else f"chunks={chunks} <= 1 or left cap={left.cap} < "
                  "chunks (no multi-slice split possible)")
        glog.vlog(1, "dist_join_streaming[%s]: falling back to one-shot "
                  "dist_join — %s", config.join_type.value, reason)
        return dist_join(left, right, config)

    plan_check.note("dist_join_streaming", left, right,
                    how=config.join_type.value, chunks=chunks,
                    decision="streaming-shuffle")
    left, right, li_key, ri_key, alg, splitters = _join_prologue(
        left, right, config)
    rsh = _copartition(right, ri_key, alg, splitters)  # once, resident

    def _cells(dt: DTable) -> int:
        per_row = sum(1 + (c.validity is not None) for c in dt.columns)
        return dt.ctx.get_world_size() * dt.cap * per_row

    w = ops_compact.next_bucket(math.ceil(left.cap / chunks), minimum=8)
    parts: List[DTable] = []
    how = config.join_type.value
    with trace.span("join.streaming"):
        for lo in range(0, left.cap, w):
            hi = min(lo + w, left.cap)
            chunk = _slice_rows(left, lo, hi)
            csh = _copartition(chunk, li_key, alg, splitters)
            # the live exchange transient of the staged plan is the
            # RESIDENT right co-partition PLUS the in-flight chunk block
            # — peak-of-single-block would under-report it by up to 2x
            # (experiments/sf100_plan.py projects from this counter)
            trace.count_max("shuffle.capacity_cells_live_peak",
                            _cells(rsh) + _cells(csh))
            parts.append(_join_copartitioned(csh, rsh, li_key, ri_key,
                                             how, alg))
    return _concat_compact(parts)


# ---------------------------------------------------------------------------
# async host ingest/export lane (docs/serving.md "pipelined export")
# ---------------------------------------------------------------------------

# The lint contract (graftlint shared-state-unguarded): submit's
# check-then-put and close's set-closed serialize on the pipeline
# lock; _closed is the only cross-thread flag (HostTask fields are
# single-writer: the owning worker, then the Event hand-off).
GUARDED_STATE = {"_closed": "_lock"}


class HostTask:
    """Handle on one submitted host-side task: ``wait()`` blocks until
    the worker ran it, then returns its result or re-raises its error
    (the error stays attached — a failed export surfaces at the waiting
    consumer, never on the worker thread's stderr alone)."""

    __slots__ = ("_event", "_value", "_error")

    def __init__(self) -> None:
        self._event = threading.Event()
        self._value: Any = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            from ..status import Code, CylonError, Status
            raise CylonError(Status(Code.ExecutionError,
                f"host task not finished within {timeout} s"))
        if self._error is not None:
            raise self._error
        return self._value


class HostPipeline:
    """A bounded FIFO lane of worker threads for host-boundary work.

    The serving dispatcher (cylon_tpu/serve) submits each finished
    query's EXPORT — the device→host gather + Arrow/pandas conversion,
    the slowest host-side step of a query — here, then immediately
    starts the next query's device compute: conversion of query N
    overlaps compute of query N+1, the host-side analogue of the
    chunked join's bounded in-flight buffers above.  Ingest works the
    same way (``submit(lambda: DTable.from_pandas(ctx, df))``).

    ``depth`` bounds queued-but-unstarted tasks (backpressure: a
    producer outrunning the host lane blocks in ``submit`` instead of
    growing an unbounded pinned-result queue).  FIFO order is
    guaranteed per pipeline with ``workers=1`` (the default — host
    conversion parallelism beyond overlap rarely pays while the GIL
    serializes the numpy copies anyway).
    """

    def __init__(self, workers: int = 1, depth: int = 16,
                 name: str = "host-pipeline") -> None:
        if workers < 1 or depth < 1:
            from ..status import Code, CylonError, Status
            raise CylonError(Status(Code.Invalid,
                f"HostPipeline needs workers >= 1 and depth >= 1, got "
                f"workers={workers} depth={depth}"))
        self._q: "_queue.Queue" = _queue.Queue(maxsize=depth)
        self._closed = False
        # serializes submit's check-then-put against close's
        # set-closed: without it a task enqueued between close()'s
        # drain and its worker-stop sentinels would never run, and its
        # wait() would block forever
        self._lock = OrderedLock("streaming.pipeline")
        self._threads = [
            threading.Thread(target=self._run, name=f"{name}-{i}",
                             daemon=True)
            for i in range(workers)]
        for t in self._threads:
            t.start()

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            task, fn, trace_id = item
            try:
                # the task's query trace id (if any) stamps the export
                # span onto that QUERY's track — the export leg of the
                # serving waterfall (docs/observability.md), even
                # though it runs on this worker thread
                with trace.trace_context(trace_id):
                    with trace.span("serve.export"):
                        task._value = fn()
            except BaseException as e:  # graftlint: ok[broad-except] —
                task._error = e  # delivered to the wait()ing consumer
            finally:
                task._event.set()
                self._q.task_done()

    def submit(self, fn: Callable[[], Any],
               trace_id: Optional[str] = None) -> HostTask:
        """Enqueue ``fn`` for a worker; returns its :class:`HostTask`.
        Blocks when ``depth`` tasks are already queued (backpressure —
        the workers draining guarantee progress while we hold the
        lock).  ``trace_id`` stamps the worker-side span onto that
        query's lifecycle track."""
        task = HostTask()
        with self._lock:
            if self._closed:
                from ..status import Code, CylonError, Status
                raise CylonError(Status(Code.Invalid,
                    "HostPipeline is closed"))
            self._q.put((task, fn, trace_id))
        return task

    def drain(self) -> None:
        """Block until every submitted task has finished."""
        self._q.join()

    def close(self, timeout: float = 5.0) -> None:
        """Drain outstanding tasks, then stop the workers
        DETERMINISTICALLY (each worker join bounded by ``timeout``; a
        worker that fails to stop — which the sentinel protocol should
        make impossible — is warned about, never waited on forever).
        Idempotent.  The lock orders this against racing ``submit``s:
        any task that won the race is in the queue before ``_closed``
        flips, so the join below waits for it — nothing lands behind
        the sentinels."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._q.join()
        for _ in self._threads:
            self._q.put(None)
        for t in self._threads:
            t.join(timeout)
            if t.is_alive():
                from .. import logging as glog
                glog.warning("host-pipeline worker %s did not stop "
                             "within %.1f s", t.name, timeout)
