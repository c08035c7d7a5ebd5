"""Distributed Table: columns sharded over the device mesh.

Each column is ONE global ``jax.Array`` of shape ``[P * cap]`` with
``NamedSharding(mesh, P('p'))`` on axis 0 — shard *i* (one TPU chip = one
reference MPI rank) holds rows ``[i*cap, i*cap + counts[i])``; the rest of
its block is padding.  Static per-shard capacity + dynamic valid counts is
how data-dependent row distribution meets XLA's static-shape SPMD model
(SURVEY.md §7 hard part 1).

The reference has no separate distributed-table type: an ``arrow::Table``
per rank *is* the partition (reference: cpp/src/cylon/table.hpp:39-278,
docs/docs/arch.md:7-25 — every rank runs the same program on its local
table).  Under single-controller JAX the partitioned state must be a
first-class object, hence DTable.

String columns carry ONE host dictionary shared by all shards (codes are
what travels through collectives); ``from_partitions`` re-encodes per-rank
dictionaries onto a shared one at ingest.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .._jax_compat import trace_state_clean
from ..analysis._abstract import is_abstract
from ..context import CylonContext
from ..observe.compile import kernel_factory, named_jit
from ..dtypes import DataType, is_dictionary_encoded
from ..ops import compact as ops_compact
from ..status import Code, CylonError, Status
from ..table import Column, Table


# head(n) at or below this row count uses the fused single-round-trip
# kernel (replicated [n] block + psum); above it, the counts-based export
# path, whose transfer scales with rows taken instead of O(P·n) memory
_HEAD_FUSED_MAX = 4096

# to_table probes via the fused head kernel only while the padded block
# holds at most this many CELLS (rows × data/validity arrays): the
# probe's scatters traverse the whole block per array (~6 ns/cell), so
# past this point its cost exceeds the one device-to-host round trip it
# can save (~100 ms measured on the r05 chip setup)
_TO_TABLE_PROBE_MAX_CELLS = 16 << 20


class _SpilledLeaf:
    """Sentinel standing in for a device leaf while the table's data
    resides host-side in the spill pool (cylon_tpu/spill/pool.py).
    Never reaches a kernel: every device-data access path goes through
    the ``DTable.columns``/``counts`` properties, which fault the real
    arrays back in first (docs/out_of_core.md "transparent
    fault-in")."""

    __slots__ = ()

    def __repr__(self) -> str:
        return "<spilled>"


_SPILLED = _SpilledLeaf()


@dataclass
class DColumn:
    """One distributed column: global sharded data + optional validity.

    reference: cpp/src/cylon/column.hpp:163-193, except data is a mesh-
    sharded device array rather than a host Arrow array.
    """

    name: str
    dtype: DataType
    data: jax.Array                        # [P*cap] sharded P('p')
    validity: Optional[jax.Array] = None   # [P*cap] bool, same sharding
    dictionary: Optional[np.ndarray] = None
    arrow_type: Any = None


class DTable:
    """Mesh-partitioned table: padded per-shard blocks + valid counts.

    ``pending_mask`` (set only by ``dist_select(compact=False)``) is a
    deferred row filter: a [P*cap] bool mask, already AND'ed with the
    valid-row mask, that has NOT been compacted yet.  Consumers that can
    fold a row mask into their own kernels (groupby/aggregate ``where``,
    the dense semi/FK probes, further selects) read it and skip the
    standalone compaction scatter (~6 ns/row — the dominant cost of a
    wide select, docs/tpu_perf_notes.md); every other op first calls
    ``_collapse_pending()``, which compacts in place, so correctness
    never depends on a consumer knowing about the mask."""

    def __init__(self, ctx: CylonContext, columns: List[DColumn], cap: int,
                 counts: jax.Array, pending_mask: Optional[jax.Array] = None,
                 pending_cnts: Optional[jax.Array] = None):
        self.ctx = ctx
        # host-tier spill state (cylon_tpu/spill/pool.py): while
        # _spill_entry is set, the leaves live host-side and the
        # columns/counts PROPERTIES fault them back in on first device
        # use.  _spill_sig is the content signature — it survives a
        # fault-in so an unchanged table re-spills without a device
        # read, and is invalidated whenever contents change
        # (_collapse_pending).
        self._spill_entry = None
        self._spill_sig: Optional[int] = None
        self.columns = columns
        self.cap = int(cap)
        self.counts = counts               # [P] int32, sharded P('p')
        self.pending_mask = pending_mask   # [P*cap] bool or None
        self.pending_cnts = pending_cnts   # replicated [P] survivor counts
        self._counts_host: Optional[np.ndarray] = None
        # host counts that bound ``counts`` from above while a delete
        # that shrank them is unread (``delete`` leaves its new counts
        # on the device; ``counts_host`` resolves them)
        self._counts_bound: Optional[np.ndarray] = None
        # the last delete's table shape and its candidate counts on the
        # device, which size the next delete (``_note_delete_total``)
        self._delete_seen = None
        # content-signature epoch (docs/serving.md "Materialized
        # subplans"): bumped on every logical-content change made
        # through the write path (append, delete).  Materialized views
        # record the epoch of every base handle at capture time; a
        # mismatch at probe time invalidates.  _deltas holds the last
        # few appended batches keyed by the epoch they created, so a
        # view whose tail is a mergeable aggregation can fold forward in
        # O(delta) instead of recomputing.
        self._epoch: int = 0
        self._deltas: Dict[int, "DTable"] = {}

    # -- the host tier (docs/out_of_core.md) ---------------------------------

    @property
    def columns(self) -> List[DColumn]:
        if self._spill_entry is not None:
            self._fault_in()
        return self._columns

    @columns.setter
    def columns(self, v: List[DColumn]) -> None:
        self._columns = v

    @property
    def counts(self):
        if self._spill_entry is not None:
            self._fault_in()
        return self._counts

    @counts.setter
    def counts(self, v) -> None:
        self._counts = v

    @property
    def is_spilled(self) -> bool:
        """Whether the leaves currently reside host-side (spill pool)."""
        return self._spill_entry is not None

    def spill(self) -> "DTable":
        """Move this table's leaves to the host-tier spill pool and
        drop the device arrays (docs/out_of_core.md).  The table keeps
        working: metadata (names/dtypes/counts) reads stay host-side,
        any device use faults the leaves back in transparently, and the
        morsel scan (spill/morsel.py) streams row slices straight from
        the pooled blocks.  Idempotent; returns self."""
        from ..spill import pool as spill_pool
        spill_pool.spill_table(self)
        return self

    def ensure_device(self) -> "DTable":
        """Explicitly fault spilled leaves back onto the device (the
        eager counterpart of the transparent property fault-in)."""
        if self._spill_entry is not None:
            self._fault_in()
        return self

    def _fault_in(self) -> None:
        from ..spill import pool as spill_pool
        spill_pool.ensure_device(self)

    def _collapse_pending(self) -> None:
        """Materialize a deferred select IN PLACE (identity-preserving:
        the handle keeps working for callers that captured it)."""
        if self.pending_mask is None:
            return
        from . import dist_ops  # runtime import; no cycle at module load
        mask, cnts = self.pending_mask, self.pending_cnts
        self.pending_mask = self.pending_cnts = None
        out = dist_ops._compact_survivors(
            self, mask, cnts,
            ("pmat", self.ctx.mesh, self.cap, self.num_columns),
            "select.gather")
        self.columns = out.columns
        self.cap = out.cap
        self.counts = out.counts
        self._counts_host = None
        self._spill_sig = None   # contents changed: the pooled host
        #                          copy (if any) no longer matches

    # -- shape ---------------------------------------------------------------

    @property
    def nparts(self) -> int:
        return self.ctx.get_world_size()

    @property
    def num_columns(self) -> int:
        return len(self._columns)   # metadata: never faults a spill in

    @property
    def column_names(self) -> List[str]:
        return [c.name for c in self._columns]   # metadata: no fault-in

    def counts_host(self) -> np.ndarray:
        self._collapse_pending()
        if self._counts_host is not None:
            # cached (ingest / spill): answer host-side — a SPILLED
            # table's row counts must never fault the leaves back in
            return self._counts_host
        if self._counts_host is None and is_abstract(self.counts):
            # abstract plan run: the counts of a derived table are data-
            # dependent by definition — a plan that needs them on host
            # is a plan that cannot be checked without executing
            from ..status import Code, CylonError, Status
            raise CylonError(Status(Code.ExecutionError,
                "plan_check: host row counts of a derived table are "
                "data-dependent (only ingest-cached counts are known "
                "at plan time)"))
        if self._counts_host is None:
            # resolve queued optimistic-capacity validations before trusting
            # any host-visible row counts; inside a failed deferred attempt
            # abort for replay instead of materializing poisoned counts.
            # The counts ride the SAME batched device_get as the flush —
            # one device round trip, not two (round-trip census r5)
            c = self.counts
            if not c.is_fully_addressable:
                # multi-controller: this process only holds its own shards;
                # replicate via all_gather so every controller can read the
                # full count vector (reference: every MPI rank knows the
                # exchange header counts, mpi_channel.cpp's 8-int header)
                c = _replicate_counts_fn(self.ctx.mesh, self.ctx.axis)(c)
            ok, vals = ops_compact.flush_pending_with((c,))
            if not ok:
                ops_compact._abort_if_poisoned()
            self._learn_counts(vals[0])
        return self._counts_host

    def _learn_counts(self, counts) -> None:
        """Cache the row counts just read; where an unread delete left
        only a bound, the rows it deleted are now known."""
        self._counts_host = np.asarray(counts)
        if self._counts_bound is not None:
            from .. import trace
            trace.count("ingest.rows_deleted", int(
                self._counts_bound.sum() - self._counts_host.sum()))
            self._counts_bound = None

    @property
    def num_rows(self) -> int:
        return int(self.counts_host().sum())

    def column(self, i: Union[int, str]) -> DColumn:
        if isinstance(i, str):
            for c in self.columns:
                if c.name == i:
                    return c
            raise CylonError(Status(Code.KeyError, f"no column {i!r}"))
        return self.columns[i]

    def column_index(self, i: Union[int, str]) -> int:
        if isinstance(i, str):
            for j, c in enumerate(self._columns):   # metadata only
                if c.name == i:
                    return j
            raise CylonError(Status(Code.KeyError, f"no column {i!r}"))
        return i

    def verify_same_schema(self, other: "DTable") -> None:
        """reference: table_api.cpp:566 (VerifyTableSchema)."""
        if self.num_columns != other.num_columns:
            raise CylonError(Status(Code.Invalid,
                f"column count mismatch {self.num_columns} vs {other.num_columns}"))
        for a, b in zip(self._columns, other._columns):
            if a.dtype.type != b.dtype.type:
                raise CylonError(Status(Code.TypeError,
                    f"type mismatch {a.name}:{a.dtype.type.name} vs "
                    f"{b.name}:{b.dtype.type.name}"))

    # -- construction --------------------------------------------------------

    @staticmethod
    def from_table(ctx: CylonContext, table: Table, cap: Optional[int] = None
                   ) -> "DTable":
        """Block-distribute a local table's rows over the mesh.

        The single-controller analogue of "mpirun gave every rank a slice"
        (reference: docs/docs/mpi.md:7-14 — scheduling is whatever mpirun
        launched).
        """
        Pn = ctx.get_world_size()
        n = table.num_rows
        base, rem = divmod(n, Pn)
        sizes = np.array([base + (1 if i < rem else 0) for i in range(Pn)],
                         np.int32)
        if cap is None:
            cap = ops_compact.next_bucket(max(int(sizes.max(initial=0)), 1),
                                          minimum=8)
        offs = np.concatenate([[0], np.cumsum(sizes)])
        cols: List[DColumn] = []
        staged = StagedIngest(ctx)
        try:
            for c in table.columns:
                data = staged.put(np.asarray(jax.device_get(c.data)),
                                  sizes, offs, cap)
                validity = (None if c.validity is None else
                            staged.put(np.asarray(jax.device_get(c.validity),
                                                  dtype=bool),
                                       sizes, offs, cap))
                cols.append(DColumn(c.name, c.dtype, data, validity,
                                    c.dictionary, c.arrow_type))
        finally:
            staged.finish()
        counts = jax.device_put(sizes, ctx.sharding())
        out = DTable(ctx, cols, cap, counts)
        # ingest knows the per-shard sizes statically — pre-cache them so
        # planners (broadcast-join threshold) never pay a host read here
        out._counts_host = sizes.copy()
        return out

    @staticmethod
    def from_arrow(ctx: CylonContext, atable, cap: Optional[int] = None
                   ) -> "DTable":
        """Block-distribute an arrow table directly from host memory —
        skips the intermediate single-device Table that ``from_table``
        would build (and the extra host↔device round trip it costs)."""
        from ..table import host_columns_from_arrow
        Pn = ctx.get_world_size()
        n = atable.num_rows
        base, rem = divmod(n, Pn)
        sizes = np.array([base + (1 if i < rem else 0) for i in range(Pn)],
                         np.int32)
        if cap is None:
            cap = ops_compact.next_bucket(max(int(sizes.max(initial=0)), 1),
                                          minimum=8)
        offs = np.concatenate([[0], np.cumsum(sizes)])
        cols: List[DColumn] = []
        staged = StagedIngest(ctx)
        try:
            for name, t, npv, mask, dictionary, ftype in \
                    host_columns_from_arrow(atable):
                data = staged.put(npv, sizes, offs, cap)
                validity = (None if mask is None else
                            staged.put(mask.astype(bool), sizes, offs, cap))
                cols.append(DColumn(name, DataType(t), data, validity,
                                    dictionary, ftype))
        finally:
            staged.finish()
        counts = jax.device_put(sizes, ctx.sharding())
        out = DTable(ctx, cols, cap, counts)
        out._counts_host = sizes.copy()  # statically known at ingest
        return out

    @staticmethod
    def from_pandas(ctx: CylonContext, df, cap: Optional[int] = None
                    ) -> "DTable":
        import pyarrow as pa

        return DTable.from_arrow(
            ctx, pa.Table.from_pandas(df, preserve_index=False), cap)

    @staticmethod
    def from_partitions(ctx: CylonContext, parts: Sequence[Table],
                        cap: Optional[int] = None) -> "DTable":
        """Build from one local Table per mesh position (the per-rank-CSV
        ingest path: reference examples/bench/table_join_dist_test.cpp:87-91
        reads ``csv1_<rank>.csv`` on each rank)."""
        Pn = ctx.get_world_size()
        if len(parts) != Pn:
            raise CylonError(Status(Code.Invalid,
                f"{len(parts)} partitions for a {Pn}-device mesh"))
        head = parts[0]
        for p in parts[1:]:
            head.verify_same_schema(p)
        sizes = np.array([p.num_rows for p in parts], np.int32)
        if cap is None:
            cap = ops_compact.next_bucket(max(int(sizes.max(initial=0)), 1),
                                          minimum=8)
        cols: List[DColumn] = []
        for j, c0 in enumerate(head.columns):
            pcols = [p.columns[j] for p in parts]
            dictionary = None
            hosts = [np.asarray(jax.device_get(pc.data)) for pc in pcols]
            if is_dictionary_encoded(c0.dtype.type):
                dicts = [pc.dictionary for pc in pcols]
                dictionary = np.unique(np.concatenate(dicts)) if any(
                    len(d) for d in dicts) else dicts[0]
                # empty-dict partitions hold only null rows (sorted-encode
                # invariant); zero their codes so nothing decodes against
                # the merged dictionary by accident.
                hosts = [np.searchsorted(dictionary, d)[h].astype(np.int32)
                         if len(d) else np.zeros_like(h, dtype=np.int32)
                         for h, d in zip(hosts, dicts)]
            block = np.zeros((Pn * cap,) + hosts[0].shape[1:], hosts[0].dtype)
            for i in range(Pn):
                block[i * cap:i * cap + sizes[i]] = hosts[i]
            data = jax.device_put(block, ctx.sharding())
            if any(pc.validity is not None for pc in pcols):
                vb = np.zeros((Pn * cap,), bool)
                for i, pc in enumerate(pcols):
                    vb[i * cap:i * cap + sizes[i]] = (
                        np.ones(sizes[i], bool) if pc.validity is None
                        else np.asarray(jax.device_get(pc.validity), bool))
                validity = jax.device_put(vb, ctx.sharding())
            else:
                validity = None
            cols.append(DColumn(c0.name, c0.dtype, data, validity,
                                dictionary, c0.arrow_type))
        counts = jax.device_put(sizes, ctx.sharding())
        out = DTable(ctx, cols, cap, counts)
        out._counts_host = sizes.copy()  # statically known at ingest
        return out

    # -- export --------------------------------------------------------------

    def _export(self, takes: Sequence[int]) -> Table:
        """Gather ``takes[i]`` leading rows of each shard as a local Table.

        Rows are compacted ON DEVICE (one gather per column) before the
        host transfer, so export cost scales with rows *taken*, not with
        ``P * cap`` — a groupby result with 4 valid rows in a multi-million
        capacity block transfers 4 rows, not the padded block.
        """
        self._collapse_pending()
        ops_compact.flush_pending()  # payload must be validation-clean
        ops_compact._abort_if_poisoned()
        # int32 gather indices unless x64 is on: jnp.asarray would silently
        # wrap int64 positions ≥ 2^31 to negative (clamping to row 0)
        if self.nparts * self.cap > np.iinfo(np.int32).max \
                and not jax.config.jax_enable_x64:
            raise CylonError(Status(Code.ExecutionError,
                f"export of a {self.nparts}x{self.cap} block needs 64-bit "
                "gather indices — enable jax_enable_x64"))
        idt = np.int64 if jax.config.jax_enable_x64 else np.int32
        idx_host = np.concatenate(
            [i * self.cap + np.arange(t, dtype=idt)
             for i, t in enumerate(takes)]) if sum(takes) else \
            np.empty((0,), idt)
        idx = jnp.asarray(idx_host)
        # dispatch every compaction first, then ONE batched host transfer
        # (per-column device_get would pay a round trip per array)
        pulls = []
        for c in self.columns:
            pulls.append(_export_take(c.data, idx))
            if c.validity is not None:
                pulls.append(_export_take(c.validity, idx))
        if any(is_abstract(p) for p in pulls):
            # abstract plan run: the "export" is the traced compaction
            # itself — hand back an abstract local Table (no host copies,
            # no transfer); Table.to_arrow marks the plan boundary
            cols_a: List[Column] = []
            hi = 0
            for c in self.columns:
                d = pulls[hi]
                hi += 1
                v = None
                if c.validity is not None:
                    v = pulls[hi]
                    hi += 1
                cols_a.append(Column(c.name, c.dtype, d, v,
                                     dictionary=c.dictionary,
                                     arrow_type=c.arrow_type))
            return Table(self.ctx, cols_a)
        from .. import trace
        with trace.host_read():  # one batched export transfer
            hosts = jax.device_get(pulls)
        cols: List[Column] = []
        hi = 0
        for c in self.columns:
            hd = np.asarray(hosts[hi])
            data = jnp.asarray(hd)
            hi += 1
            validity, hv = None, None
            if c.validity is not None:
                hv = np.asarray(hosts[hi])
                validity = jnp.asarray(hv)
                hi += 1
            # the host copies ride along: to_arrow then transfers nothing
            cols.append(Column(c.name, c.dtype, data, validity,
                               dictionary=c.dictionary,
                               arrow_type=c.arrow_type,
                               host_data=hd, host_validity=hv))
        return Table(self.ctx, cols)

    def to_table(self) -> Table:
        """Gather all shards to one local Table (drops padding).

        Small-result fast path: when the padded block is modest, the
        fused head kernel probes the first ``_HEAD_FUSED_MAX`` rows with
        the COUNT VECTOR riding the same batched flush — a result that
        fits comes back in ONE device round trip (most aggregate tails);
        a bigger result falls through to the counts-based export having
        already paid for its counts (2 trips total, same as before).
        """
        if is_abstract(self.counts) \
                or any(is_abstract(c.data) for c in self.columns):
            # abstract plan run: gather the full capacity bound — row
            # counts are data-dependent, shapes are what the plan checks
            self._collapse_pending()
            return self._export([self.cap] * self.nparts)
        n_arrays = sum(1 + (c.validity is not None) for c in self.columns)
        # the fused probe is a shard_map program: under an ambient trace
        # escape hatch (jax.ensure_compile_time_eval — the plan-time
        # constant-fold path of plan_check) collectives cannot bind the
        # mesh axis, so take the collective-free export path there
        if (self.pending_mask is None and self.columns
                and trace_state_clean()
                and self.nparts * self.cap * n_arrays
                <= _TO_TABLE_PROBE_MAX_CELLS):
            n = min(_HEAD_FUSED_MAX, self.nparts * self.cap)
            leaves = tuple((c.data, c.validity) for c in self.columns)
            outs, got = _head_fn(self.ctx.mesh, self.ctx.axis, self.cap, n,
                                 tuple(c.validity is not None
                                       for c in self.columns))(
                self.counts, leaves)
            cnt_dev = self.counts
            if not cnt_dev.is_fully_addressable:
                cnt_dev = _replicate_counts_fn(self.ctx.mesh,
                                               self.ctx.axis)(cnt_dev)
            flat: List[Any] = [got, cnt_dev]
            for d, v in outs:
                flat.append(d)
                if v is not None:
                    flat.append(v)
            ok, vals = ops_compact.flush_pending_with(flat)
            if not ok:
                ops_compact._abort_if_poisoned()
            take = int(np.asarray(vals[0]))
            cnts = np.asarray(vals[1])
            self._learn_counts(cnts)  # paid for either way — cache it
            if take >= int(cnts.sum()):  # the probe holds the whole table
                return Table(self.ctx,
                             self._columns_from_host(vals, 2, take))
            return self._export([int(c) for c in cnts])
        return self._export([int(c) for c in self.counts_host()])

    def _columns_from_host(self, vals, start: int, take: int
                           ) -> List[Column]:
        """Unflatten a batched host read (data, then validity where
        nullable, per column) into local Columns carrying their host
        copies — the shared tail of ``head`` and the ``to_table``
        probe."""
        cols: List[Column] = []
        hi = start
        for c in self.columns:
            hd = np.asarray(vals[hi])[:take]
            hi += 1
            hv = None
            if c.validity is not None:
                hv = np.asarray(vals[hi])[:take]
                hi += 1
            cols.append(Column(
                c.name, c.dtype, jnp.asarray(hd),
                None if hv is None else jnp.asarray(hv),
                dictionary=c.dictionary, arrow_type=c.arrow_type,
                host_data=hd, host_validity=hv))
        return cols

    def head(self, n: int) -> Table:
        """First ``n`` global rows (shard-major order) as a local Table.

        For ``n`` ≤ _HEAD_FUSED_MAX (the LIMIT-sized case): single round
        trip — the bounded gather runs entirely on device (per-shard
        scatter into a replicated [n] block, combined by psum over
        disjoint positions), and the transfer shares one batched
        ``device_get`` with any queued capacity validations
        (ops.compact.flush_pending_with) — the ORDER BY … LIMIT tail of a
        pipeline costs one host read total.  Larger ``n`` takes the
        counts-based export path instead (two round trips: counts, then
        rows) — the fused kernel's replicated [n] block would cost
        O(P·n) memory.
        """
        self._collapse_pending()
        n_eff = min(int(n), self.nparts * self.cap)
        if n_eff <= 0:
            return self._export([0] * self.nparts)
        abstract = (is_abstract(self.counts)
                    or any(is_abstract(c.data) for c in self.columns))
        if abstract and n_eff > _HEAD_FUSED_MAX:
            # abstract plan run, counts-based path: per-shard takes are
            # data-dependent — export the capacity bound instead
            return self._export([min(n_eff, self.cap)] * self.nparts)
        if n_eff > _HEAD_FUSED_MAX:
            # the fused kernel replicates an [n_eff] block per device and
            # psums it — O(P·n) memory for a big head().  Past a modest n
            # the counts-based export (transfers only the taken rows, one
            # blocking count read) is strictly better.
            cnts = self.counts_host()
            takes, remaining = [], n_eff
            for i in range(self.nparts):
                t = min(int(cnts[i]), remaining)
                takes.append(t)
                remaining -= t
            return self._export(takes)
        leaves = tuple((c.data, c.validity) for c in self.columns)
        outs, got = _head_fn(self.ctx.mesh, self.ctx.axis, self.cap, n_eff,
                             tuple(c.validity is not None
                                   for c in self.columns))(self.counts, leaves)
        if abstract:
            # abstract plan run: the fused [n] block IS the head's shape;
            # rows-taken is data-dependent, so keep the full block
            return Table(self.ctx, [
                Column(c.name, c.dtype, d, v, dictionary=c.dictionary,
                       arrow_type=c.arrow_type)
                for c, (d, v) in zip(self.columns, outs)])
        flat: List[Any] = [got]
        for d, v in outs:
            flat.append(d)
            if v is not None:
                flat.append(v)
        ok, vals = ops_compact.flush_pending_with(flat)
        if not ok:
            # inside a failed deferred attempt: abort for replay rather
            # than hand truncated garbage to the caller
            ops_compact._abort_if_poisoned()
        take = int(np.asarray(vals[0]))
        return Table(self.ctx, self._columns_from_host(vals, 1, take))

    def partition(self, i: int) -> Table:
        """Shard *i*'s rows as a local Table (a rank's-eye view)."""
        cnts = self.counts_host()
        return self._export([int(cnts[j]) if j == i else 0
                             for j in range(self.nparts)])

    def rename(self, names: Sequence[str]) -> "DTable":
        out = DTable(self.ctx, [replace(c, name=n)
                                for c, n in zip(self.columns, names)],
                     self.cap, self.counts, self.pending_mask,
                     self.pending_cnts)
        out._counts_host = self._counts_host  # same rows, same counts
        return out

    # -- the ingest-delta path (docs/serving.md) -----------------------------

    @property
    def content_epoch(self) -> int:
        """Monotone logical-content version of this handle.  Layout
        changes (compaction, spill round trips) do NOT bump it; only
        the write path (:meth:`append`, :meth:`delete`) does."""
        return self._epoch

    def delta_for(self, epoch: int) -> Optional["DTable"]:
        """The appended batch that moved this table TO ``epoch``, if
        still retained — the input to a materialized view's O(delta)
        fold (serve/matview.py)."""
        return self._deltas.get(epoch)

    # how many appended batches stay reachable for folding.  A view
    # more than this many epochs behind recomputes instead — bounded so
    # a table ingesting forever does not retain its whole history.
    _DELTA_KEEP = 8

    def append(self, other) -> "DTable":
        """UNION ALL ``other``'s rows into THIS handle, in place.

        This is the serving ingest path: identity-preserving — every
        holder of this handle (session tables, plans captured by
        value) observes the grown table — so it composes with the
        serving tier's id()-keyed runtime signatures.  ``other`` is a
        ``DTable`` of the same schema, an Arrow table, or a dict
        ``{column: numpy array}`` (string columns as codes in this
        table's dictionaries): how a refresh file arrives.

        Only the delta crosses the host boundary: its rows go
        host→device once, laid out per shard, and one program
        (``_insert_fn``) writes them into the table's spare capacity
        (copying each column: no buffer donation, as in ``delete``).
        The shards with the most slack take the rows; a delta that
        overflows the capacity re-buckets it on the device
        (``ops.compact.next_bucket``).  After a ``delete`` the delta is
        laid out and sent while the delete may still run, from the
        counts before it (an upper bound); the host waits for the exact
        counts only where they decide something: the layout across
        several shards, or a capacity the bound would overflow.  Else
        the new counts stay on the device, bounded by the old bound
        plus the delta.  A delta whose
        dictionary holds values this table's lacks merges into the
        sorted superset, re-coding the base on the device.  The batch
        is registered under the new content epoch, so
        :class:`~cylon_tpu.serve.matview.ViewStore` folds it through
        the mergeable combine kernels in O(delta) instead of
        recomputing.

        Returns ``self`` (for chaining).
        """
        from .. import trace
        with trace.ingest("append"):
            self._collapse_pending()
            host = _host_delta(self, other)
            m = len(host[0][0]) if host else 0
            if m == 0:
                return self
            ctx = self.ctx
            bound = self._counts_host if self._counts_host is not None \
                else self._counts_bound
            moved = 0
            if bound is None:
                bound = self.counts_host()
                moved += bound.nbytes
            unread = self._counts_host is None
            base_leaves, rows, dicts = [], [], []
            for c, (data, valid, dictionary) in zip(self.columns, host):
                bdata, bvalid = c.data, c.validity
                if dictionary is not None:
                    dictionary, bmap, data = _merge_dictionary(
                        c.dictionary, dictionary, data)
                    if bmap is not None:
                        bdata = _recode(jnp.asarray(bmap), bdata)
                        trace.count("ingest.host_bytes", bmap.nbytes)
                if valid is not None and bvalid is None:
                    bvalid = _insert_validity(ctx.mesh, ctx.axis, self.cap)(
                        self.counts)
                elif valid is None and bvalid is not None:
                    valid = np.ones(m, bool)
                base_leaves.append((bdata, bvalid))
                rows += [data.astype(bdata.dtype),
                         None if valid is None else valid.astype(bool)]
                dicts.append(dictionary)
            take = _water_fill(bound, m)
            dcap, blocks, staged = _stage(ctx, rows, take)
            moved += sum(x.nbytes for x in blocks if x is not None)
            if unread and (self.nparts > 1
                           or int((bound + take).max()) > self.cap):
                # the exact counts decide the layout across shards or a
                # capacity the bound would overflow: wait for the delete
                bound = self.counts_host()
                moved += bound.nbytes
                unread = False
                if not np.array_equal(_water_fill(bound, m), take):
                    # the delete left other shards emptiest: lay out again
                    take = _water_fill(bound, m)
                    dcap, blocks, staged = _stage(ctx, rows, take)
                    moved += sum(x.nbytes for x in blocks if x is not None)
            trace.count("ingest.host_bytes", moved)
            *staged, take_dev = staged
            grown = (bound.astype(np.int64) + take).astype(np.int32)
            cap = self.cap if grown.max() <= self.cap else \
                ops_compact.next_bucket(int(grown.max()), minimum=8)
            delta_leaves = tuple(zip(staged[::2], staged[1::2]))
            leaves, new_counts = _insert_fn(
                ctx.mesh, ctx.axis, self.cap, cap, dcap)(
                self.counts, take_dev, tuple(base_leaves), delta_leaves)
            delta_cols = [DColumn(c.name, c.dtype, d, v, dictionary,
                                  c.arrow_type)
                          for c, (d, v), dictionary in zip(
                              self.columns, delta_leaves, dicts)]
            trace.count("ingest.rows_in", m)
            delta = DTable(ctx, delta_cols, dcap, take_dev)
            delta._counts_host = take.astype(np.int32)
            self._replace_contents(
                [replace(dc, data=d, validity=v)
                 for dc, (d, v) in zip(delta_cols, leaves)],
                cap, new_counts, None if unread else grown)
            if unread:  # an unread delete's counts, grown by the delta
                self._counts_bound = grown
            self._deltas[self._epoch] = delta
            for e in sorted(self._deltas):
                if len(self._deltas) <= self._DELTA_KEEP:
                    break
                del self._deltas[e]
            return self

    def delete(self, key_column: Union[int, str], keys) -> "DTable":
        """Remove, in place, every row whose ``key_column`` value is in
        ``keys`` (any integer array: sorted or not, contiguous or not;
        a key that is not present is a no-op).

        Works shard by shard on the device: the rows whose key lies in
        ``[min(keys), max(keys)]`` are the candidates.  ``_delete_fn``
        finds them by a running count, tests them against the sorted
        keys and fills each hole below the new end with a surviving row
        from above it: O(candidates) gathers and scatters past a pass
        over the key column, and a copy of each column (the write
        programs donate no buffers: derived tables may share them).  It
        runs on the size class of the last delete of this table shape,
        and finishes on the device whatever the count: a pass more for
        each size class of candidates past it.  So the host reads
        nothing to delete: the candidate counts size the next delete
        once the device has them (``_note_delete_total``).  Only the
        first delete of a shape counts its candidates first
        (``_delete_scan_fn``) and reads the count; when there is none,
        nothing changes.  Row order within a shard is not kept (the
        DTable contract leaves it undefined).  The new counts stay on
        the device until something reads them (``counts_host``), so the
        host does not wait for the delete to finish.  The content epoch
        bumps with no foldable delta, so a view over this table
        recomputes.

        Returns ``self`` (for chaining).
        """
        from .. import trace
        with trace.ingest("delete"):
            self._collapse_pending()
            ki = self.column_index(key_column)
            kc = self.columns[ki]
            if is_dictionary_encoded(kc.dtype.type) or not jnp.issubdtype(
                    kc.data.dtype, jnp.integer):
                raise CylonError(Status(Code.TypeError,
                    f"delete: key column {kc.name!r} is not an integer "
                    f"column ({kc.dtype.type.name})"))
            k = _delete_keys(keys, np.dtype(kc.data.dtype))
            if not len(k):
                return self
            ctx = self.ctx
            kcap = ops_compact.next_bucket(len(k), minimum=8)
            kdev = jax.device_put(
                np.concatenate([k, np.full(kcap - len(k), k[-1], k.dtype)]),
                ctx.replicated())
            moved = kdev.nbytes * self.nparts  # one copy per chip
            counts, cap = self.counts, self.cap
            shape = (ctx.mesh, cap, ki)
            seen = _note_delete_total(self)
            if seen is not None:
                moved += seen.nbytes
            sizes = ops_compact.hint_value(_delete_cap_hints, shape)
            if sizes is None:  # the first delete of this shape
                per_shard = ops_compact._read_counts(
                    _delete_scan_fn(ctx.mesh, ctx.axis, cap)(
                        counts, kdev, kc.data, kc.validity))
                moved += per_shard.nbytes
                if not per_shard.any():
                    trace.count("ingest.host_bytes", moved)
                    return self  # no row in the keys' range
                sizes = _delete_sizes(per_shard)
                ops_compact.update_size_hint(_delete_cap_hints, shape, sizes)
            trace.count("ingest.host_bytes", moved)
            outs, new_counts, total = _delete_fn(
                ctx.mesh, ctx.axis, cap, sizes[0], ki)(
                counts, kdev, tuple((c.data, c.validity)
                                    for c in self.columns))
            bound = self._counts_host if self._counts_host is not None \
                else self._counts_bound
            if bound is None:
                bound = self.counts_host()
            self._replace_contents(
                [replace(c, data=d, validity=v)
                 for c, (d, v) in zip(self.columns, outs)],
                cap, new_counts, None)
            self._counts_bound = bound
            self._delete_seen = (shape, total)
            return self

    def _replace_contents(self, columns: List[DColumn], cap: int, counts,
                          counts_host: Optional[np.ndarray]) -> None:
        """Swap in a write's new leaves and bump the content epoch
        (``counts_host`` None: the new counts are on the device only)."""
        if self._spill_entry is not None:
            # the pooled host copy describes the PRE-write contents;
            # drop it rather than fault stale bytes back in later
            from ..spill.pool import get_pool
            get_pool().drop_entry(self._spill_entry.sig)
        self._spill_entry = None
        self._spill_sig = None
        self._columns = columns
        self.cap = int(cap)
        self._counts = counts
        self.pending_mask = None
        self.pending_cnts = None
        self._counts_host = (None if counts_host is None
                             else np.asarray(counts_host, np.int32))
        self._counts_bound = None
        self._epoch += 1

    def explain(self, plan=None, *, tables=None, validate: bool = False,
                concrete=(), analyze: bool = False,
                optimize: bool = False):
        """Describe — and optionally validate or measure — a plan.

        ``dt.explain()`` returns a structural summary of the table
        itself; with ``validate=True`` it additionally checks the
        engine's plan-shape invariants (counts dtype/width, leaf
        lengths, validity dtypes, dictionary sort order).

        ``dt.explain(plan, validate=True)`` abstract-interprets
        ``plan`` — a callable receiving this table (or, when ``tables``
        is given, that dict of tables, the whole-query shape:
        ``dt.explain(lambda t: q5(ctx, t), tables=t, validate=True)``) —
        via ``jax.eval_shape``: every distributed op in the plan is
        shape/dtype-checked with ZERO data movement, and the returned
        ``PlanReport`` lists the operator sequence.  ``concrete`` names
        tables in ``tables`` to keep un-abstracted (tiny dimension
        tables whose values the plan folds at build time).  See
        docs/static_analysis.md.

        ``dt.explain(plan, tables=..., analyze=True)`` is **EXPLAIN
        ANALYZE**: the plan runs FOR REAL, once, with tracing on and
        every distributed operator instrumented; the returned report's
        nodes carry runtime annotations (rows in/out, bytes moved per
        exchange, planner decision + reason, wall-clock) and
        ``report.output`` holds the query's actual result.  ``validate``
        and ``concrete`` do not apply to an analyze run (the tables are
        already concrete).  See docs/observability.md.

        ``optimize=True`` routes the plan through the logical query
        planner (docs/query_planner.md) first — both the static and the
        analyze form then describe the OPTIMIZED plan: rewrite-rule
        fires appear as ``optimizer=…`` annotations on the affected
        nodes, and an analyze report's head carries the pre-/post-
        optimization exchange byte totals and plan-cache hit counts.
        """
        from ..analysis import plan_check
        if plan is None:
            if analyze:
                raise CylonError(Status(Code.Invalid,
                    "explain(analyze=True) needs a plan callable — there "
                    "is nothing to run"))
            if validate:
                plan_check._check_table("explain", self)
            cols = ", ".join(f"{c.name}:{c.dtype.type.name}"
                             for c in self._columns)
            ch = self._counts_host
            rows = (f"{int(ch.sum())} rows" if ch is not None
                    else "rows data-dependent")
            mask = ", deferred-select mask pending" \
                if self.pending_mask is not None else ""
            spilled = ", spilled to host" if self.is_spilled else ""
            return (f"DTable[{rows} over {self.nparts} shards, "
                    f"cap={self.cap}{mask}{spilled}]({cols})")
        target = tables if tables is not None else self
        op = plan
        if optimize:
            from .. import plan as planner
            ctx = self.ctx

            def op(tgt, _plan=plan, _ctx=ctx):  # noqa: F811 — optimized form
                return planner.run(_ctx, _plan, tgt)
        if analyze:
            from .. import observe
            return observe.analyze(op, target)
        return plan_check.explain(op, target, validate=validate,
                                  concrete=concrete)

    def __repr__(self) -> str:
        cols = ", ".join(f"{c.name}:{c.dtype.type.name}"
                         for c in self._columns)
        ch = self._counts_host
        if ch is not None:
            rows = f"{int(ch.sum())} rows"
        elif is_abstract(self._counts):
            # abstract plan run: a repr (user print, debugger, error
            # formatter) must never raise the counts_host plan error
            rows = "abstract rows"
        else:
            rows = f"{self.num_rows} rows"
        spilled = ", spilled to host" if self.is_spilled else ""
        return (f"DTable[{rows} over {self.nparts} shards, "
                f"cap={self.cap}{spilled}]({cols})")


@functools.partial(named_jit, name="dtable._export_take")
def _export_take(a: jax.Array, idx: jax.Array) -> jax.Array:
    """Device-side row compaction for export (re-traced per shape bucket)."""
    return jnp.take(a, idx, axis=0)


@kernel_factory
def _replicate_counts_fn(mesh, axis: str):
    """[P]-sharded counts → replicated copy every controller can read."""
    from .._jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    def kernel(cnt_blk):
        return jax.lax.all_gather(cnt_blk[0], axis)

    # check_vma=False: the all_gathered output is replicated, which
    # shard_map cannot statically infer
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=P(axis),
                               out_specs=P(), check_vma=False))


@kernel_factory
def _head_fn(mesh, axis: str, cap: int, n: int, has_v):
    """Per shard: scatter my first ``take`` rows into a replicated [n]
    block at my global shard-major offset; shards write disjoint slots, so
    a psum combines them.  Returns ((data, validity), …) + rows-taken."""
    from .._jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    def kernel(cnt_blk, leaves):
        gcnts = jax.lax.all_gather(cnt_blk, axis, tiled=True)  # [P]
        me = jax.lax.axis_index(axis)
        before = jnp.sum(jnp.where(jnp.arange(gcnts.shape[0]) < me,
                                   gcnts, 0)).astype(jnp.int32)
        i = jnp.arange(cap, dtype=jnp.int32)
        pos = before + i
        keep = (i < cnt_blk[0]) & (pos < n)
        tgt = jnp.where(keep, pos, jnp.int32(n))
        outs = []
        for (d, v), hv in zip(leaves, has_v):
            od = jnp.zeros((n,) + d.shape[1:], d.dtype).at[tgt].set(
                jnp.where(keep.reshape((-1,) + (1,) * (d.ndim - 1)), d,
                          jnp.zeros((), d.dtype)), mode="drop")
            od = jax.lax.psum(od, axis)
            if hv:
                vv = v if v is not None else jnp.ones(cap, bool)
                ov = jnp.zeros((n,), jnp.uint8).at[tgt].set(
                    jnp.where(keep, vv, False).astype(jnp.uint8),
                    mode="drop")
                ov = jax.lax.psum(ov, axis).astype(bool)
            else:
                ov = None
            outs.append((od, ov))
        got = jnp.minimum(jnp.sum(gcnts), n).astype(jnp.int32)
        return tuple(outs), got

    spec = P(axis)
    # check_vma=False: psum outputs are replicated
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec, spec), out_specs=(P(), P()),
                               check_vma=False))


# ---------------------------------------------------------------------------
# the write path: DTable.append / DTable.delete
# ---------------------------------------------------------------------------

def _host_delta(base: DTable, other) -> List[tuple]:
    """``other``'s rows as host columns aligned with ``base``'s:
    ``[(data, validity | None, dictionary | None), ...]``, a string
    column as codes into the dictionary beside it."""
    from .. import trace
    if isinstance(other, dict):
        if sorted(other) != sorted(base.column_names):
            raise CylonError(Status(Code.Invalid,
                f"append: columns {sorted(other)} != "
                f"{sorted(base.column_names)}"))
        out = []
        for c in base.columns:
            a = np.asarray(other[c.name])
            coded = is_dictionary_encoded(c.dtype.type)
            if coded and not np.issubdtype(a.dtype, np.integer):
                raise CylonError(Status(Code.TypeError,
                    f"append: string column {c.name!r} must be given as "
                    "codes in the table's dictionary"))
            out.append((a, None, c.dictionary if coded else None))
        if len({len(a) for a, _, _ in out}) > 1:
            raise CylonError(Status(Code.Invalid,
                "append: columns of different lengths"))
        return out
    if isinstance(other, DTable):
        base.verify_same_schema(other)
        out = []
        for c in other.to_table().columns:  # the delta's rows only
            d = (c.host_data if c.host_data is not None
                 else np.asarray(jax.device_get(c.data)))
            v = c.host_validity
            if v is None and c.validity is not None:
                v = np.asarray(jax.device_get(c.validity))
            trace.count("ingest.host_bytes",
                        d.nbytes + (0 if v is None else v.nbytes))
            out.append((d, v, c.dictionary))
        return out
    from ..table import host_columns_from_arrow
    cols = host_columns_from_arrow(other)
    if len(cols) != base.num_columns or any(
            t != c.dtype.type for (_, t, *_), c in zip(cols, base.columns)):
        raise CylonError(Status(Code.TypeError,
            "append: the Arrow table's column types differ from the "
            "table's"))
    return [(npv, mask, dictionary)
            for _, _, npv, mask, dictionary, _ in cols]


def _stage(ctx, rows: List[Optional[np.ndarray]], take: np.ndarray):
    """The delta's host columns laid out per shard (``take[p]`` rows at
    the head of shard ``p``'s ``[dcap]`` block) and sent host→device in
    one batched transfer, the per-shard row counts last.  Returns
    ``(dcap, host blocks, device blocks)``."""
    dcap = ops_compact.next_bucket(int(take.max()), minimum=8)
    offs = np.concatenate([[0], np.cumsum(take)])
    blocks = [None if a is None else _shard_blocks(a, take, offs, dcap)
              for a in rows] + [take.astype(np.int32)]
    return dcap, blocks, jax.device_put(blocks, ctx.sharding())


def _shard_blocks(a: np.ndarray, take: np.ndarray, offs: np.ndarray,
                  dcap: int) -> np.ndarray:
    """Rows ``offs[p]:offs[p+1]`` of ``a`` at the head of shard ``p``'s
    ``[dcap]`` block, zero padding after."""
    out = np.zeros((len(take) * dcap,) + a.shape[1:], a.dtype)
    for p, n in enumerate(take):
        out[p * dcap:p * dcap + n] = a[offs[p]:offs[p + 1]]
    return out


def _water_fill(counts: np.ndarray, m: int) -> np.ndarray:
    """Rows per shard for ``m`` new rows: the shards with the most
    slack first, so the fullest shard ends as low as it can."""
    level = counts.astype(np.int64)
    lo, hi = int(level.min()), int(level.min()) + m
    while lo < hi:  # the lowest level L with room for m rows below it
        mid = (lo + hi) // 2
        if np.maximum(mid - level, 0).sum() >= m:
            hi = mid
        else:
            lo = mid + 1
    take = np.maximum(lo - 1 - level, 0)
    take[np.flatnonzero(level < lo)[:m - int(take.sum())]] += 1
    return take


def _merge_dictionary(base_dict: np.ndarray, delta_dict: np.ndarray,
                      codes: np.ndarray):
    """``(dictionary, base re-code map | None, delta codes in it)``: the
    base's dictionary when it holds every delta value, else the sorted
    union, with the map that re-codes the base onto it."""
    codes = np.asarray(codes)
    if delta_dict is base_dict:  # codes given in the table's dictionary
        return base_dict, None, codes
    if not len(delta_dict):  # an all-null delta column
        return base_dict, None, np.zeros_like(codes, np.int32)
    pos = np.searchsorted(base_dict, delta_dict)
    if len(base_dict) and np.array_equal(
            base_dict[np.minimum(pos, len(base_dict) - 1)], delta_dict):
        return base_dict, None, pos.astype(np.int32)[codes]
    merged = np.union1d(base_dict, delta_dict)
    bmap = (np.searchsorted(merged, base_dict).astype(np.int32)
            if len(base_dict) else None)
    return merged, bmap, \
        np.searchsorted(merged, delta_dict).astype(np.int32)[codes]


@functools.partial(named_jit, name="dtable._insert_recode")
def _recode(table: jax.Array, codes: jax.Array) -> jax.Array:
    """A string column's codes onto a merged dictionary."""
    return jnp.take(table, codes, axis=0)


@kernel_factory
def _insert_validity(mesh, axis: str, cap: int):
    """Per shard: True on the first ``counts`` rows — the validity of a
    column that had none, once a delta brings it nulls."""
    from .._jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    def kernel(cnt):
        return jnp.arange(cap, dtype=jnp.int32) < cnt[0]

    return named_jit(shard_map(kernel, mesh=mesh, in_specs=P(axis),
                               out_specs=P(axis)))


@kernel_factory
def _insert_fn(mesh, axis: str, cap: int, out_cap: int, dcap: int):
    """Per shard: write the first ``take`` rows of the staged ``[dcap]``
    delta block after the shard's ``counts`` rows, the block grown from
    ``cap`` to ``out_cap`` rows (zero padding) when the delta overflows
    it.  Returns the leaves and the new counts."""
    from .._jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    def kernel(cnt, take, base, delta):
        i = jnp.arange(dcap, dtype=jnp.int32)
        tgt = jnp.where(i < take[0], cnt[0] + i, out_cap)
        outs = []
        for (bd, bv), (dd, dv) in zip(base, delta):
            bd = ops_compact.pad_to(bd, out_cap).at[tgt].set(dd, mode="drop")
            if bv is not None:
                bv = ops_compact.pad_to(bv, out_cap).at[tgt].set(
                    dv, mode="drop")
            outs.append((bd, bv))
        return tuple(outs), cnt + take

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh, in_specs=(spec,) * 4,
                               out_specs=(spec, spec)))


def _delete_keys(keys, dtype: np.dtype) -> np.ndarray:
    """``keys`` sorted and unique, as ``dtype``; a key no value of
    ``dtype`` can equal is dropped."""
    k = np.unique(np.asarray(jax.device_get(keys)).ravel())
    if not k.size:
        return k.astype(dtype)
    if not np.issubdtype(k.dtype, np.integer):
        raise CylonError(Status(Code.TypeError,
            f"delete: keys must be integers, got {k.dtype}"))
    info = np.iinfo(dtype)
    return k[(k >= info.min) & (k <= info.max)].astype(dtype)


def _delete_candidates(cap: int, n, keys, x, xv):
    """The first ``n`` rows of a shard whose key lies in
    ``[keys[0], keys[-1]]``."""
    c = ((jnp.arange(cap, dtype=jnp.int32) < n)
         & (x >= keys[0]) & (x <= keys[-1]))
    return c if xv is None else c & xv


# size-class hints of the delete's candidate count, per table shape
_delete_cap_hints: dict = {}


def _delete_sizes(per_shard: np.ndarray) -> tuple:
    """``_delete_fn``'s size class for these candidate counts."""
    return (ops_compact.next_bucket(max(int(per_shard.max(initial=0)), 1),
                                    minimum=8),)


def _note_delete_total(dt: DTable) -> Optional[np.ndarray]:
    """Fold the candidate counts of ``dt``'s last delete into the size
    hint of its table shape, once the device has them (a finished
    program's output: reading it waits for nothing); returns them, or
    None."""
    if dt._delete_seen is None or not dt._delete_seen[1].is_ready():
        return None
    shape, total = dt._delete_seen
    dt._delete_seen = None
    per_shard = ops_compact._read_counts(total)
    ops_compact.update_size_hint(_delete_cap_hints, shape,
                                 _delete_sizes(per_shard))
    return per_shard


@kernel_factory
def _delete_scan_fn(mesh, axis: str, cap: int):
    """Per shard: the delete's candidate count (one pass over the key
    column), which sizes the first ``_delete_fn`` of a table shape."""
    from .._jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    def kernel(cnt, keys, x, xv):
        return jnp.sum(_delete_candidates(cap, cnt[0], keys, x, xv),
                       dtype=jnp.int32)[None]

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec, P(), spec, spec),
                               out_specs=spec))


@kernel_factory
def _delete_fn(mesh, axis: str, cap: int, ccap: int, key: int):
    """Per shard: drop every row whose key (leaf ``key``) is in the
    sorted ``keys``.  One pass (``_delete_pass``) handles the first
    ``ccap`` candidates, all of them when the size class is right; while
    candidates remain, further passes handle ``ccap`` more each, those
    already found not to match marked so that each pass makes progress.
    Returns the leaves, the new counts and the candidate count of the
    first pass (which sizes the next delete)."""
    from .._jax_compat import shard_map
    from jax.sharding import PartitionSpec as P

    def kernel(cnt, keys, leaves):
        leaves, n, total, _ = _delete_pass(cap, ccap, key, cnt[0], keys,
                                           leaves)

        def again(state):
            leaves, n, _, checked = state
            leaves, n, seen, checked = _delete_pass(
                cap, ccap, key, n, keys, leaves, checked)
            return leaves, n, seen - jnp.minimum(seen, ccap), checked

        leaves, n, _, _ = jax.lax.while_loop(
            lambda state: state[2] > 0, again,
            (leaves, n, total - jnp.minimum(total, ccap),
             jnp.full(cap, n < 0)))  # none checked, per shard
        return leaves, n[None], total[None]

    spec = P(axis)
    return named_jit(shard_map(kernel, mesh=mesh,
                               in_specs=(spec, P(), spec),
                               out_specs=(spec, spec, spec)))


def _delete_pass(cap: int, ccap: int, key: int, n, keys, leaves,
                 checked=None):
    """Drop the rows whose key is in ``keys`` among the first ``ccap``
    candidates of a shard's ``n`` rows (those not yet ``checked``, and
    mark the rest of them checked).  With ``d`` rows dropped, the j-th
    hole below the new end ``n - d`` takes the j-th surviving row of
    ``[n - d, n)``, and the vacated rows are zeroed: O(candidates)
    gathers and scatters past one scan of the key column.  Returns the
    leaves, the new row count, the candidates seen and ``checked``."""
    x, xv = leaves[key]
    cand = _delete_candidates(cap, n, keys, x, xv)
    if checked is not None:
        cand = cand & ~checked
    cs = jnp.cumsum(cand, dtype=jnp.int32)
    t = jnp.arange(ccap, dtype=jnp.int32)
    at = jnp.minimum(jnp.searchsorted(cs, t + 1), cap - 1) \
        .astype(jnp.int32)  # the t-th candidate's row, ascending
    ck = x[at]
    live = t < cs[-1]
    hit = live & (keys[jnp.minimum(
        jnp.searchsorted(keys, ck), keys.shape[0] - 1)] == ck)
    if checked is not None:
        checked = checked.at[jnp.where(live & ~hit, at, cap)].set(
            True, mode="drop")
    d = jnp.sum(hit, dtype=jnp.int32)
    holes = _pick(hit, at, cap)
    end = n - d
    tail = end + t
    on_hole = holes[jnp.minimum(jnp.searchsorted(holes, tail),
                                ccap - 1)] == tail
    src = _pick((t < d) & ~on_hole, tail, cap)
    dst = jnp.where(holes < end, holes, cap)
    vacated = jnp.where(t < d, tail, cap)
    leaves = tuple((_move_rows(dd, src, dst, vacated, cap),
                    None if dv is None else
                    _move_rows(dv, src, dst, vacated, cap))
                   for dd, dv in leaves)
    if checked is not None:
        checked = _move_rows(checked, src, dst, vacated, cap)
    return leaves, end, cs[-1], checked


def _pick(mask, rows, fill: int):
    """``rows[mask]`` in order, padded with ``fill`` to ``len(rows)``."""
    n = rows.shape[0]
    return jnp.append(rows, jnp.int32(fill))[
        ops_compact.compact_indices(mask, n, fill=n)]


def _move_rows(a, src, dst, vacated, cap: int):
    """Row ``src[j]`` into row ``dst[j]``, then zeros at ``vacated``
    (out-of-range targets are dropped)."""
    a = a.at[dst].set(a[jnp.minimum(src, cap - 1)], mode="drop")
    return a.at[vacated].set(jnp.zeros((), a.dtype), mode="drop")


_ARENA_CAP = 256 << 20
_arena = None
_arena_lock = threading.Lock()
# diagnostic switch (bench.py's ingest A/B): False forces the numpy
# fallback path even on real H2D targets
ARENA_ENABLED = True


class StagedIngest:
    """One table's worth of staged H2D transfers through the native arena.

    Columns bump-allocate staging blocks from the shared arena (C++
    allocator, cylon_tpu/native/_cylon_native.cpp; numpy fallback — the
    role the reference's MemoryPool plays on its ingest path,
    ctx/memory_pool.hpp:25-66), every ``device_put`` stays asynchronous so
    transfers overlap the next column's assembly, and ``finish()`` blocks
    ONCE and resets the arena when all buffers have been read.

    CPU backends can zero-copy-alias numpy buffers into device arrays, so
    arena reuse would clobber live data there — the arena engages only
    for real H2D targets, where ``device_put`` copies (``force_arena``
    exists for tests on such targets; never set it on CPU).  A column
    that doesn't fit the remaining arena space falls back to a one-off
    allocation.
    """

    def __init__(self, ctx: CylonContext, force_arena: bool = False):
        global _arena
        self._ctx = ctx
        self._owns_arena = False
        platform = ctx.mesh.devices.flat[0].platform
        if (platform != "cpu" or force_arena) and ARENA_ENABLED:
            # exclusive ownership: a second concurrent ingest must not
            # reset the arena under the first one's in-flight transfers
            if _arena_lock.acquire(blocking=False):
                self._owns_arena = True
                if _arena is None:
                    from ..native.runtime import StagingArena
                    _arena = StagingArena(_ARENA_CAP)
                self._arena = _arena
            else:
                self._arena = None
        else:
            self._arena = None
        self._pending: List[jax.Array] = []

    def _block(self, shape, dtype) -> np.ndarray:
        nbytes = int(np.prod(shape)) * dtype.itemsize
        if self._arena is not None:
            try:
                buf = self._arena.allocate(nbytes)
            except MemoryError:
                return np.zeros(shape, dtype)
            block = np.frombuffer(buf, dtype=dtype,
                                  count=int(np.prod(shape))).reshape(shape)
            block[:] = 0
            return block
        return np.zeros(shape, dtype)

    def put(self, host: np.ndarray, sizes: np.ndarray, offs: np.ndarray,
            cap: int) -> jax.Array:
        """Assemble one column's padded shard blocks; async transfer."""
        Pn = len(sizes)
        block = self._block((Pn * cap,) + host.shape[1:], host.dtype)
        for i in range(Pn):
            block[i * cap:i * cap + sizes[i]] = host[offs[i]:offs[i + 1]]
        out = jax.device_put(block, self._ctx.sharding())
        self._pending.append(out)
        return out

    def finish(self) -> None:
        """Block on outstanding transfers, reset + release the arena.
        Idempotent; callers run it in a ``finally``."""
        try:
            if self._arena is not None and self._pending:
                jax.block_until_ready(self._pending)  # buffers consumed
                self._arena.reset()
        finally:
            self._pending = []
            self._arena = None
            if self._owns_arena:
                self._owns_arena = False
                _arena_lock.release()
