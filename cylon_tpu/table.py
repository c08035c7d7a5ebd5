"""Device-resident columnar Table.

Design (SURVEY.md §7): the reference keeps ``arrow::Table`` in host RAM behind
a global uuid→table registry (reference: cpp/src/cylon/table_api.cpp:45-73,
table.hpp:39-278).  Here a Table is a plain Python object holding **device
arrays**: per column a fixed-width data array + optional validity mask; no
registry, no mutex (the registry existed only to serve id-based FFI — the
pycylon compat layer keeps ids at that boundary only).

Strings/binary are **dictionary-encoded at ingest** (host side): the device
stores int32 codes, the host stores the dictionary.  The dictionary is sorted,
so code order == lexical order — sorts and comparisons work directly on codes.
Cross-table ops on string columns first *unify* dictionaries (sorted union +
code remap) so equal strings have equal codes in both tables.

Null semantics follow the reference: hash of null is 0 and a −1 gather index
appends null (reference: arrow/arrow_partition_kernels.hpp:55-57,93-95,
util/copy_arrray.cpp:38-43).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .context import CylonContext
from .dtypes import (DataType, Type, device_dtype, from_arrow_type,
                     is_dictionary_encoded, to_arrow_type)
from .status import Code, CylonError, Status


# "keep the current value" marker for Column.with_data — compared by
# identity because the real operands are arrays
_SAME = object()


@dataclass
class Column:
    """One column: logical type + device data (+ validity, + host dictionary).

    reference: cpp/src/cylon/column.hpp:163-193 — but data lives in HBM.
    """

    name: str
    dtype: DataType
    data: jax.Array                      # [n] device array (codes for strings)
    validity: Optional[jax.Array] = None  # [n] bool device array; None = all valid
    dictionary: Optional[np.ndarray] = None  # host payload for STRING/BINARY
    arrow_type: Any = None               # original pyarrow type for round-trip
    # host copies of data/validity when the producer already paid the
    # transfer (DTable export): to_arrow reads these instead of pulling
    # the re-uploaded device arrays back — every pull is a device-to-
    # host round trip (~100 ms on the r05 chip setup), and the per-column
    # pulls were the single largest hidden cost of small-query exports
    # (round-trip census r5)
    host_data: Optional[np.ndarray] = None
    host_validity: Optional[np.ndarray] = None

    def __post_init__(self):
        if is_dictionary_encoded(self.dtype.type) and self.dictionary is None:
            self.dictionary = np.empty((0,), dtype=object)

    @property
    def length(self) -> int:
        return int(self.data.shape[0])

    def has_nulls(self) -> bool:
        return self.validity is not None

    def with_data(self, data, validity=_SAME, dictionary=_SAME) -> "Column":
        """THE way to derive a column with new contents: every
        data/validity/dictionary-changing site goes through here so the
        export-time host caches can never survive a device-side change
        (``to_arrow`` would silently export the stale host copy
        otherwise — the invariant is also assert-checked at export)."""
        # identity sentinel, not ==: validity/dictionary operands are
        # arrays, whose == against a marker is elementwise
        v = self.validity if validity is _SAME else validity
        d = self.dictionary if dictionary is _SAME else dictionary
        # new device contents ⇒ the export-time host caches are stale
        return replace(self, data=data, validity=v, dictionary=d,
                       host_data=None, host_validity=None)


def _combine(chunked):
    import pyarrow as pa

    if isinstance(chunked, pa.ChunkedArray):
        return chunked.combine_chunks()
    return chunked


def _typed_numpy(arr, npd: np.dtype) -> np.ndarray:
    """Arrow array -> numpy of exactly ``npd`` without lossy intermediates.

    Temporal arrays come back as datetime64/timedelta64; reinterpret the
    underlying int64 rather than casting.  time32/time64 come back as object
    arrays of datetime.time — cast those to their integer storage inside
    arrow first.  Everything else is a typed copy.
    """
    import pyarrow as pa

    npv = arr.to_numpy(zero_copy_only=False)
    if npv.dtype.kind in "mM":
        npv = npv.view(np.int64)
    elif npv.dtype.kind == "O":  # e.g. time32/time64 -> datetime.time objects
        target = pa.int64() if npd.itemsize == 8 else pa.int32()
        npv = arr.cast(target).to_numpy(zero_copy_only=False)
    return np.ascontiguousarray(npv).astype(npd, copy=False)


def _narrow_host(npv: np.ndarray, t: Type, col_name: str):
    """Host-side handling of x64-disabled narrowing (numpy in, numpy out).

    Under JAX's default config 64-bit arrays silently narrow to 32-bit.
    Silent corruption is unacceptable: ints are range-checked (narrow +
    logical-type downgrade when lossless, error otherwise); floats narrow
    with a warning (precision loss is the expected trade on TPU).
    Returns (host_array, effective_logical_type).

    Warnings go through ``glog.warn_once`` keyed per (column, dtype) —
    the engine's one warning channel (re-ingesting the same frame in a
    loop logs one line per column, not one per call); the rest of the
    tree logs through glog too, so capture/filtering is uniform.
    """
    from . import logging as glog

    if npv.dtype.itemsize == 8 and not jax.config.jax_enable_x64:
        if npv.dtype.kind in "iu":
            lo = int(npv.min()) if npv.size else 0
            hi = int(npv.max()) if npv.size else 0
            narrow = np.int32 if npv.dtype.kind == "i" else np.uint32
            info = np.iinfo(narrow)
            if lo < info.min or hi > info.max:
                raise CylonError(Status(Code.ExecutionError,
                    f"column {col_name!r}: 64-bit values do not fit in 32 bits "
                    f"and jax_enable_x64 is off — enable x64 or use 32-bit data"))
            eff = {Type.INT64: Type.INT32, Type.UINT64: Type.UINT32}.get(t, t)
            glog.warn_once(
                ("table.narrow", col_name, str(npv.dtype)),
                "column %r: narrowing %s to 32-bit (jax_enable_x64 is "
                "off)", col_name, npv.dtype)
            return npv.astype(narrow), eff
        if npv.dtype.kind == "f":
            glog.warn_once(
                ("table.narrow", col_name, str(npv.dtype)),
                "column %r: narrowing float64 to float32 "
                "(jax_enable_x64 is off)", col_name)
            return npv.astype(np.float32), \
                Type.FLOAT if t == Type.DOUBLE else t
    return npv, t


def _device_put(npv: np.ndarray, t: Type, col_name: str):
    """jnp.asarray of ``_narrow_host`` — see that function for semantics."""
    npv, t = _narrow_host(npv, t, col_name)
    return jnp.asarray(npv), t


def host_columns_from_arrow(atable):
    """Arrow table → per-column host tuples, the shared ingest front half.

    Returns ``[(name, effective Type, np data, np validity|None,
    dictionary|None, arrow value type), …]`` — everything decoded, null-
    filled, dictionary-encoded and narrowed, but NOT yet transferred to
    device.  ``Table.from_arrow`` device-puts these whole;
    ``DTable.from_arrow`` block-distributes them over the mesh without an
    intermediate single-device copy (the ingest path would otherwise move
    every byte host→device→host→device).
    """
    import pyarrow as pa
    import pyarrow.compute as pc

    out = []
    for fld, col in zip(atable.schema, atable.columns):
        t = from_arrow_type(fld.type)
        arr = _combine(col)
        ftype = fld.type
        if pa.types.is_dictionary(ftype):
            # decode to values; _encode_dictionary re-encodes onto the
            # framework's sorted dictionary (code order == lexical order)
            arr = arr.cast(ftype.value_type)
            ftype = ftype.value_type
        if is_dictionary_encoded(t):
            codes, dictionary, validity = _encode_dictionary(arr)
            out.append((fld.name, t, codes, validity, dictionary, ftype))
            continue
        npd = device_dtype(t)
        if arr.null_count:
            mask = np.asarray(
                arr.is_valid().to_numpy(zero_copy_only=False), dtype=bool)
            # lossless: fill nulls inside arrow (typed), never via float64
            fill = False if t == Type.BOOL else 0
            filled_arr = pc.fill_null(arr, pa.scalar(fill, type=arr.type))
            npv, t = _narrow_host(_typed_numpy(filled_arr, npd), t, fld.name)
            out.append((fld.name, t, npv, mask, None, ftype))
        else:
            npv, t = _narrow_host(_typed_numpy(arr, npd), t, fld.name)
            out.append((fld.name, t, npv, None, None, ftype))
    return out


def _encode_dictionary(arr) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Host-side sorted-dictionary encode of a string/binary arrow array.

    Returns (codes int32, dictionary, validity-or-None).  Sorted dictionary ⇒
    code order == lexical order, so device-side sort/compare on codes is
    order-correct.  Uses the native C++ encoder when built (cylon_tpu.native),
    falling back to numpy.
    """
    values = arr.to_numpy(zero_copy_only=False)  # object ndarray, None for null
    mask = ~np.asarray(arr.is_valid().to_numpy(zero_copy_only=False), dtype=bool)
    valid_values = values[~mask]
    from .native import runtime as _native
    codes_valid, dictionary = _native.dictionary_encode(valid_values)
    codes = np.zeros(len(values), dtype=np.int32)
    codes[~mask] = codes_valid
    validity = None if not mask.any() else ~mask
    return codes, dictionary, validity


class Table:
    """Immutable columnar table on device.

    reference: cpp/src/cylon/table.hpp:39-278 (handle façade) — here the
    object *is* the table; ops produce new Tables.
    """

    def __init__(self, ctx: CylonContext, columns: List[Column]):
        if columns:
            n = columns[0].length
            for c in columns:
                if c.length != n:
                    raise CylonError(Status(Code.Invalid,
                        f"column {c.name!r} length {c.length} != {n}"))
        self.ctx = ctx
        self.columns: List[Column] = columns

    # -- shape ---------------------------------------------------------------

    @property
    def num_rows(self) -> int:
        return self.columns[0].length if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def column_names(self) -> List[str]:
        return [c.name for c in self.columns]

    def row(self, i: int):
        """Typed accessor for one row (reference: row.hpp:22-50)."""
        from .row import Row

        return Row(self, i)

    def iter_rows(self):
        for i in range(self.num_rows):
            yield self.row(i)

    def column(self, i: Union[int, str]) -> Column:
        if isinstance(i, str):
            for c in self.columns:
                if c.name == i:
                    return c
            raise CylonError(Status(Code.KeyError, f"no column {i!r}"))
        return self.columns[i]

    # -- construction --------------------------------------------------------

    @staticmethod
    def from_arrow(ctx: CylonContext, atable) -> "Table":
        """Ingest a pyarrow Table (host→device transfer happens here).

        reference: table.cpp (FromArrowTable) + type validation
        arrow/arrow_types.cpp:57-114.
        """
        cols: List[Column] = []
        for name, t, npv, mask, dictionary, ftype in \
                host_columns_from_arrow(atable):
            data = jnp.asarray(npv)
            val = jnp.asarray(mask) if mask is not None else None
            # ingest already has the host values — cache them so an
            # export of this table pulls nothing back from the device
            cols.append(Column(name, DataType(t), data, val,
                               dictionary=dictionary, arrow_type=ftype,
                               host_data=np.asarray(npv),
                               host_validity=(None if mask is None
                                              else np.asarray(mask))))
        return Table(ctx, cols)

    @staticmethod
    def from_pandas(ctx: CylonContext, df) -> "Table":
        import pyarrow as pa

        return Table.from_arrow(ctx, pa.Table.from_pandas(df, preserve_index=False))

    @staticmethod
    def from_columns(ctx: CylonContext, data: Dict[str, Any]) -> "Table":
        """Build from a dict of name -> numpy/jnp array (numeric fast path)."""
        import pyarrow as pa

        cols: List[Column] = []
        for name, arr in data.items():
            npa = np.asarray(arr)
            if npa.dtype == object or npa.dtype.kind in ("U", "S"):
                return Table.from_arrow(ctx, pa.table(
                    {k: np.asarray(v) for k, v in data.items()}))
            try:
                t = _TYPE_OF_NUMPY[np.dtype(npa.dtype).name]
            except KeyError:
                raise CylonError(Status(Code.NotImplemented,
                    f"column {name!r}: unsupported numpy dtype {npa.dtype!r} "
                    "(use from_arrow for temporal/other types)")) from None
            npa = npa.astype(device_dtype(t), copy=False)
            data, t = _device_put(npa, t, name)
            cols.append(Column(name, DataType(t), data))
        return Table(ctx, cols)

    # -- export --------------------------------------------------------------

    def to_arrow(self):
        """Device→host; decode dictionaries; reattach nulls.

        All columns missing a host cache transfer in ONE batched
        ``device_get`` (per-column pulls would pay one device round trip
        each); columns exported from a DTable carry their host copies
        already and transfer nothing.  A ``cylon.export`` profiler
        span."""
        from . import trace
        with trace.annotate("export"):
            return self._to_arrow()

    def _to_arrow(self):
        import pyarrow as pa

        from .analysis._abstract import PlanExportReached, is_abstract
        if any(is_abstract(c.data) for c in self.columns):
            # abstract plan run (analysis/plan_check.py): this is the
            # host-export boundary — the distributed plan above has been
            # fully checked; what follows is host post-processing
            raise PlanExportReached(
                "Table.to_arrow",
                [(c.name, c.dtype.type.name, c.length)
                 for c in self.columns])
        from .config import sanitizing
        for c in self.columns:
            # host-cache staleness guard, ALWAYS ON (formerly asserts,
            # promoted by the sanitizer work — a stripped-assert build
            # must not silently export stale host copies): a cache may
            # only coexist with the device array it was copied from
            # (every contents change routes through Column.with_data,
            # which drops it).  A length mismatch is the cheap
            # observable of a violation.
            if c.host_data is not None \
                    and c.host_data.shape[0] != c.length:
                raise CylonError(Status(Code.ExecutionError,
                    f"stale host_data cache on column {c.name!r} "
                    f"({c.host_data.shape[0]} host vs {c.length} device "
                    "rows) — derive columns via Column.with_data"))
            if c.host_validity is not None and (
                    c.validity is None
                    or c.host_validity.shape[0] != c.length):
                raise CylonError(Status(Code.ExecutionError,
                    f"stale host_validity cache on column {c.name!r} — "
                    "derive columns via Column.with_data"))
        if sanitizing():
            # sanitizer backstop: byte-compare every host cache against
            # the device truth before trusting it for export.  Costs a
            # full pull — exactly what sanitize mode is for.
            self._verify_host_caches()
        pulls, slots = [], []
        for i, c in enumerate(self.columns):
            if c.host_data is None:
                pulls.append(c.data)
                slots.append((i, False))
            if c.validity is not None and c.host_validity is None:
                pulls.append(c.validity)
                slots.append((i, True))
        pulled = jax.device_get(pulls) if pulls else []
        got = {}
        for (i, is_v), v in zip(slots, pulled):
            got[(i, is_v)] = np.asarray(v)

        arrays, names = [], []
        for i, c in enumerate(self.columns):
            host = (c.host_data if c.host_data is not None
                    else got[(i, False)])
            if c.validity is None:
                mask = None
            else:
                hv = (c.host_validity if c.host_validity is not None
                      else got[(i, True)])
                mask = ~np.asarray(hv, dtype=bool)
            if is_dictionary_encoded(c.dtype.type):
                vals = (c.dictionary[np.clip(host, 0, max(len(c.dictionary) - 1, 0))]
                        if len(c.dictionary)
                        else np.full(len(host), None, dtype=object))
                arrays.append(pa.array(vals, type=to_arrow_type(c.dtype.type,
                                                                orig=c.arrow_type),
                                       mask=mask))
            elif c.dtype.type == Type.BOOL:
                arrays.append(pa.array(host.astype(bool), type=pa.bool_(), mask=mask))
            else:
                at = to_arrow_type(c.dtype.type, orig=c.arrow_type)
                arrays.append(pa.array(host, type=at, mask=mask))
            names.append(c.name)
        return pa.table(arrays, names=names)

    def _verify_host_caches(self) -> None:
        """Sanitizer content check (config.sanitize()): device arrays are
        the truth; any host cache that disagrees is a with_data-contract
        violation that would otherwise export silently-wrong data."""
        pulls = []
        for c in self.columns:
            if c.host_data is not None:
                pulls.append((c.name, "host_data", c.host_data, c.data))
            if c.host_validity is not None:
                pulls.append((c.name, "host_validity", c.host_validity,
                              c.validity))
        if not pulls:
            return
        fresh = jax.device_get([d for _, _, _, d in pulls])
        for (name, kind, cached, _), dev in zip(pulls, fresh):
            if not np.array_equal(np.asarray(cached), np.asarray(dev)):
                raise CylonError(Status(Code.ExecutionError,
                    f"sanitize: {kind} cache on column {name!r} disagrees "
                    "with the device array — a contents change bypassed "
                    "Column.with_data"))

    def to_pandas(self):
        from . import trace
        with trace.annotate("export"):
            return self._to_arrow().to_pandas()

    # -- schema --------------------------------------------------------------

    def schema_types(self) -> List[Type]:
        return [c.dtype.type for c in self.columns]

    def verify_same_schema(self, other: "Table") -> None:
        """Column-count + per-column logical type equality.

        reference: table_api.cpp:566 (VerifyTableSchema)
        """
        if self.num_columns != other.num_columns:
            raise CylonError(Status(Code.Invalid,
                f"column count mismatch {self.num_columns} vs {other.num_columns}"))
        for a, b in zip(self.columns, other.columns):
            if a.dtype.type != b.dtype.type:
                raise CylonError(Status(Code.TypeError,
                    f"type mismatch {a.name}:{a.dtype.type.name} vs "
                    f"{b.name}:{b.dtype.type.name}"))

    # -- convenience ---------------------------------------------------------

    def project(self, indices: Sequence[Union[int, str]]) -> "Table":
        """Zero-copy column subset (reference: table_api.cpp:1007-1026)."""
        return Table(self.ctx, [self.column(i) for i in indices])

    def rename(self, names: Sequence[str]) -> "Table":
        return Table(self.ctx, [replace(c, name=n)
                                for c, n in zip(self.columns, names)])

    def rename_column(self, old: str, new: str) -> "Table":
        return self.rename([new if c.name == old else c.name
                            for c in self.columns])

    def to_string(self, row1: int = 0, row2: Optional[int] = None,
                  col1: int = 0, col2: Optional[int] = None) -> str:
        """A window of the table, formatted (reference: table_api.cpp
        PrintToOStream — the misc-util stringify behind Print/WriteCSV)."""
        df = self.to_pandas()
        row2 = df.shape[0] if row2 is None else row2
        col2 = df.shape[1] if col2 is None else col2
        return df.iloc[row1:row2, col1:col2].to_string(index=False)

    def show(self, row1: int = 0, row2: Optional[int] = None,
             col1: int = 0, col2: Optional[int] = None) -> None:
        """Print a window of the table (reference: table_api.cpp Print*)."""
        print(self.to_string(row1, row2, col1, col2))

    def __repr__(self) -> str:
        cols = ", ".join(f"{c.name}:{c.dtype.type.name}" for c in self.columns)
        return f"Table[{self.num_rows} x {self.num_columns}]({cols})"


_TYPE_OF_NUMPY = {
    "bool": Type.BOOL,
    "uint8": Type.UINT8, "int8": Type.INT8,
    "uint16": Type.UINT16, "int16": Type.INT16,
    "uint32": Type.UINT32, "int32": Type.INT32,
    "uint64": Type.UINT64, "int64": Type.INT64,
    "float16": Type.HALF_FLOAT, "float32": Type.FLOAT, "float64": Type.DOUBLE,
}


# ---------------------------------------------------------------------------
# dictionary unification (cross-table string ops)
# ---------------------------------------------------------------------------

def unify_dictionaries(a: Column, b: Column) -> Tuple[Column, Column]:
    """Re-encode two dictionary columns onto one shared sorted dictionary.

    Equal strings get equal codes in both columns, and code order stays
    lexical — after this, joins/set-ops/sorts treat the column as plain int32.
    """
    if not (is_dictionary_encoded(a.dtype.type) and is_dictionary_encoded(b.dtype.type)):
        return a, b
    if a.dictionary is b.dictionary or (
            len(a.dictionary) == len(b.dictionary)
            and bool(np.all(a.dictionary == b.dictionary))):
        return a, b
    merged = np.unique(np.concatenate([a.dictionary, b.dictionary]))
    map_a = jnp.asarray(np.searchsorted(merged, a.dictionary).astype(np.int32))
    map_b = jnp.asarray(np.searchsorted(merged, b.dictionary).astype(np.int32))
    new_a = a.with_data(map_a[a.data] if len(a.dictionary) else a.data,
                        dictionary=merged)
    new_b = b.with_data(map_b[b.data] if len(b.dictionary) else b.data,
                        dictionary=merged)
    return new_a, new_b


def unify_tables(left: Table, right: Table,
                 left_cols: Sequence[int], right_cols: Sequence[int]
                 ) -> Tuple[Table, Table]:
    """Unify dictionaries for the given column pairs across two tables."""
    lcols, rcols = list(left.columns), list(right.columns)
    for li, ri in zip(left_cols, right_cols):
        lcols[li], rcols[ri] = unify_dictionaries(lcols[li], rcols[ri])
    return Table(left.ctx, lcols), Table(right.ctx, rcols)
