"""Columnar batch: the device-resident data model.

TPU-native replacement for the reference's ``GpuColumnVector``/``ColumnarBatch``
(sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java): columns
are JAX arrays in TPU HBM instead of cuDF device buffers.  The key design
divergence (SURVEY.md §7.3 "dynamic shapes") is that XLA wants static shapes, so:

  * every device column is padded to a power-of-two *capacity bucket* —
    executables are compiled once per (operator, bucket) and reused;
  * a batch carries ``num_rows`` (leading valid rows; the rest is padding) and
    an optional ``sel`` boolean *selection mask* produced by filters.  Filter
    does no data movement at all — it just narrows the mask, which downstream
    fused stages incorporate.  Compaction (gathering live rows to the front)
    happens only at boundaries that need dense rows (shuffle slicing, sort,
    join, collect).

Nulls are boolean validity masks (True = valid), matching Arrow; ``valid=None``
means "no nulls" and lets XLA skip the mask entirely.

Strings are carried as host-side Arrow arrays (``HostStringColumn``) until the
device string kernels land; the planner routes string *compute* accordingly.
"""

from __future__ import annotations

import datetime
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import types as T
from .types import DataType

__all__ = [
    "Schema", "Field", "DeviceColumn", "HostStringColumn",
    "PageCodedStringColumn", "ColumnBatch",
    "bucket_capacity", "from_arrow", "to_arrow", "to_arrow_async",
    "from_numpy",
]


@dataclass(frozen=True)
class Field:
    name: str
    dtype: DataType
    nullable: bool = True


class Schema:
    def __init__(self, fields: Sequence[Field]):
        self.fields = list(fields)
        self._index = {f.name: i for i, f in enumerate(self.fields)}
        assert len(self._index) == len(self.fields), "duplicate column names"

    @staticmethod
    def of(*pairs: Tuple[str, DataType]) -> "Schema":
        return Schema([Field(n, d) for n, d in pairs])

    def __len__(self):
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def index_of(self, name: str) -> int:
        return self._index[name]

    def __contains__(self, name: str) -> bool:
        return name in self._index

    def field(self, name: str) -> Field:
        return self.fields[self._index[name]]

    def names(self) -> List[str]:
        return [f.name for f in self.fields]

    def __eq__(self, other):
        return isinstance(other, Schema) and self.fields == other.fields

    def __repr__(self):
        inner = ", ".join(f"{f.name}: {f.dtype}" for f in self.fields)
        return f"Schema({inner})"


def estimated_row_bytes(schema) -> int:
    """Planning-time row width estimate (bytes): the ONE formula shared by
    the batch byte caps and the auto-broadcast threshold.

    Nested (ARRAY/STRUCT/MAP) and other host-carried columns get a
    conservative 64-byte weight so auto-broadcast sizing never drastically
    underestimates a nested-typed build side (memory blow-up risk)."""
    def w(f):
        if f.dtype.is_string:
            return 24
        if getattr(f.dtype, "is_host_carried", False):
            return 64  # nested types / wide decimals ride as Python objects
        return 8
    return sum(w(f) for f in schema) or 8


# Armed by plan/bucketing.install() when the conf picks a non-default
# ladder; None means the classic power-of-two ladder below (the import
# points this way, not batch->bucketing, to keep the plan package free
# to import batch at module scope).
_ladder_hook = None


def bucket_capacity(n_rows: int, min_capacity: int = 1024,
                    has_strings: bool = False) -> int:
    """Smallest ladder rung >= max(n_rows, min_capacity).

    Default ladder: powers of two — multiples of the TPU lane width (128)
    that keep the XLA executable cache small: one compile per
    (stage, bucket).  ``spark.rapids.tpu.warmstore.bucket.*`` swaps in a
    geometric ladder (see plan/bucketing.py); ``has_strings`` lets the
    ladder apply its per-dtype minimum for host-string batches.
    """
    hook = _ladder_hook
    if hook is not None:
        return hook.capacity_for(n_rows, min_capacity, has_strings)
    cap = max(int(min_capacity), 1)
    n = max(int(n_rows), 1)
    while cap < n:
        cap <<= 1
    return cap


@dataclass
class DeviceColumn:
    """One column resident in device memory.

    ``data`` has physical length == batch capacity.  ``valid`` is a same-length
    boolean mask (True = non-null) or None for no-nulls.  Padding rows beyond
    ``num_rows`` hold unspecified values; kernels must mask with the batch's
    active-row mask before any reduction or comparison that could observe them.
    """

    dtype: DataType
    data: jax.Array
    valid: Optional[jax.Array] = None

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def nullable(self) -> bool:
        return self.valid is not None

    def astuple(self):
        return (self.dtype, self.data, self.valid)


class HostStringColumn:
    """A string column kept on host as a pyarrow array.

    Device string kernels (Arrow offsets+bytes as int tensors — SURVEY.md §7.3)
    are staged work; until then string *data* stays host-side and string
    compute happens on the CPU fallback path, while group-by/join on strings
    uses device-side dictionary codes (see ops/strings.py).
    """

    def __init__(self, array, capacity: Optional[int] = None):
        import pyarrow as pa
        if isinstance(array, pa.ChunkedArray):
            array = array.combine_chunks()
        if not isinstance(array, pa.Array):
            array = pa.array(array, type=pa.string())
        if pa.types.is_large_string(array.type):
            array = array.cast(pa.string())
        if pa.types.is_large_list(array.type):
            array = array.cast(pa.list_(array.type.value_type))
        if capacity is not None and len(array) < capacity:
            array = pa.concat_arrays(
                [array, pa.nulls(capacity - len(array), type=array.type)])
        self.array = array
        # also carries ARRAY<...> columns (collect_list output): any arrow
        # type with no device representation rides as a host column
        self.dtype = T.STRING if pa.types.is_string(array.type) \
            else _arrow_to_logical(array.type)

    @property
    def capacity(self) -> int:
        return len(self.array)

    @property
    def nullable(self) -> bool:
        return self.array.null_count > 0

    def to_pylist(self):
        return self.array.to_pylist()


class DictStringColumn(HostStringColumn):
    """A string column carried as DEVICE int32 dictionary codes plus a
    host arrow dictionary of distinct values.

    The r4 engine paid for strings at every join/agg boundary: payload
    strings either forced joins off the dense path (host gather + arrow
    take per output batch) or were fetched+decoded eagerly.  This column
    keeps codes on device so gathers/scatters/compacts ride the same int
    kernels as any device column, and the decode (one counted fetch of
    the codes) happens LAZILY — only when a consumer actually touches
    ``.array`` (writers, string compute, final collect).

    Subclasses HostStringColumn so every host-string fallback path keeps
    working unchanged (correctness by default); fast paths special-case
    it FIRST.  Codes are dictionary-ordered by first occurrence, valid
    for equality ops only — range comparisons and ORDER BY must decode.
    """

    def __init__(self, codes, valid, dictionary):
        import pyarrow as pa
        self.codes = codes        # jax int32 [capacity]
        self.valid = valid        # jax bool [capacity] or None
        if isinstance(dictionary, pa.ChunkedArray):
            dictionary = dictionary.combine_chunks()
        self.dictionary = dictionary  # pa.StringArray of distinct values
        self.dtype = T.STRING
        self._decoded = None

    @property
    def capacity(self) -> int:
        return int(self.codes.shape[0])

    @property
    def nullable(self) -> bool:
        return self.valid is not None

    @property
    def array(self):
        if self._decoded is None:
            import pyarrow as pa
            from .utils.metrics import fetch
            if self.valid is not None:
                codes, valid = fetch((self.codes, self.valid))
            else:
                codes, valid = fetch(self.codes), None
            self._decoded = decode_dict_codes(codes, valid, self.dictionary)
        return self._decoded

    @array.setter
    def array(self, value):  # pragma: no cover - defensive
        self._decoded = value


class PageCodedStringColumn(HostStringColumn):
    """A string column as the parquet file stored it: for each run of
    rows that one dictionary page coded, the page's int32 indices and its
    dictionary (``chunks``, arrow ``DictionaryArray``s in row order).

    Only the indices count toward the capacity: the column holds no
    string until a consumer reads ``.array``, which decodes every chunk
    once (remembered) and pads with nulls to the capacity, so it reads
    exactly what the plain column would.  An aggregate's string keys skip
    the decode: ``StringDictionary.encode_page_codes`` maps each chunk's
    few dictionary values to the query's codes and gathers the indices
    through that map, hashing no row.
    """

    def __init__(self, chunks, capacity: int):
        self.chunks = list(chunks)
        self.num_rows = sum(len(c) for c in self.chunks)
        self._capacity = int(capacity)
        self.dtype = T.STRING
        self._decoded = None

    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def nullable(self) -> bool:
        # as the decoded array's null count: the padding is null
        return self.num_rows < self._capacity or \
            any(c.null_count for c in self.chunks)

    @property
    def array(self):
        if self._decoded is None:
            import pyarrow as pa
            parts = [c.dictionary_decode() for c in self.chunks]
            if self.num_rows < self._capacity:
                parts.append(pa.nulls(self._capacity - self.num_rows,
                                      type=pa.string()))
            self._decoded = parts[0] if len(parts) == 1 \
                else pa.concat_arrays(parts)
        return self._decoded

    @array.setter
    def array(self, value):  # pragma: no cover - defensive
        self._decoded = value


def _page_chunks(col):
    """The chunks of a ``dictionary<int32, string>`` column, as the
    parquet reader's ``read_dictionary`` makes it, or None for any other
    column (another dictionary is decoded like a plain column)."""
    import pyarrow as pa
    if col.type != pa.dictionary(pa.int32(), pa.string()):
        return None
    chunks = col.chunks if isinstance(col, pa.ChunkedArray) else [col]
    if any(c.dictionary.null_count for c in chunks):
        return None
    return chunks


def decode_dict_codes(codes, valid, dictionary):
    """HOST int32 codes (+validity) + arrow dictionary → plain
    StringArray; out-of-range codes are nulls."""
    import numpy as np
    import pyarrow as pa
    c = np.asarray(codes).astype(np.int64, copy=True)
    bad = (c < 0) | (c >= len(dictionary))
    if valid is not None:
        bad |= ~np.asarray(valid)
    c[bad] = 0
    ind = pa.array(c.astype(np.int32), type=pa.int32(),
                   mask=bad if bad.any() else None)
    return pa.DictionaryArray.from_arrays(
        ind, dictionary).dictionary_decode()


Column = Union[DeviceColumn, HostStringColumn]


class ColumnBatch:
    """A batch of rows: columns + row accounting.

    Active rows are ``i < num_rows`` AND ``sel[i]`` (when ``sel`` is present).
    ``sel`` is how filters stay fused: GpuFilterExec in the reference gathers
    immediately (basicPhysicalOperators.scala:763); here the mask rides along
    and XLA fuses the predicate into whatever consumes the batch.

    ``bound`` (optional) is a STATIC upper limit on live rows, set by
    bounded producers (dense-grid aggregation): it lets downstream
    compaction stay sync-free (ops/batch_utils.compact_packed).
    """

    bound = None

    def __init__(self, schema: Schema, columns: Sequence[Column], num_rows: int,
                 sel: Optional[jax.Array] = None):
        assert len(schema) == len(columns)
        self.schema = schema
        self.columns = list(columns)
        self.num_rows = int(num_rows)
        self.sel = sel
        caps = {c.capacity for c in self.columns}
        assert len(caps) <= 1, f"ragged column capacities {caps}"
        self._capacity = caps.pop() if caps else bucket_capacity(num_rows)
        assert self.num_rows <= self._capacity

    # ------------------------------------------------------------------ accounting
    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def has_selection(self) -> bool:
        return self.sel is not None

    def active_mask(self) -> jax.Array:
        """Boolean [capacity] mask of live rows (device)."""
        m = jnp.arange(self._capacity, dtype=jnp.int32) < self.num_rows
        if self.sel is not None:
            m = m & self.sel
        return m

    def row_count(self) -> int:
        """Exact live-row count. Syncs with device when a selection exists."""
        if self.sel is None:
            return self.num_rows
        from .utils.metrics import fetch_scalars
        return fetch_scalars(jnp.sum(self.active_mask()))[0]

    def column(self, name: str) -> Column:
        return self.columns[self.schema.index_of(name)]

    def with_columns(self, schema: Schema, columns: Sequence[Column]) -> "ColumnBatch":
        return ColumnBatch(schema, columns, self.num_rows, self.sel)

    def device_size_bytes(self) -> int:
        total = 0
        for c in self.columns:
            if isinstance(c, DeviceColumn):
                total += c.data.size * c.data.dtype.itemsize
                if c.valid is not None:
                    total += c.valid.size
        return total

    def __repr__(self):
        sel = ", sel" if self.sel is not None else ""
        return (f"ColumnBatch(rows={self.num_rows}/{self._capacity}{sel}, "
                f"schema={self.schema})")


# ---------------------------------------------------------------------------------
# Host <-> device interchange (Arrow is the host interchange format, like the
# reference's HostColumnarToGpu.scala path).
# ---------------------------------------------------------------------------------

def _arrow_to_logical(pa_type) -> DataType:
    import pyarrow as pa
    if pa.types.is_dictionary(pa_type):
        # a dictionary-coded column is its values' type
        return _arrow_to_logical(pa_type.value_type)
    if pa.types.is_boolean(pa_type):
        return T.BOOLEAN
    if pa.types.is_int8(pa_type):
        return T.INT8
    if pa.types.is_int16(pa_type):
        return T.INT16
    if pa.types.is_int32(pa_type):
        return T.INT32
    if pa.types.is_int64(pa_type):
        return T.INT64
    if pa.types.is_float32(pa_type):
        return T.FLOAT32
    if pa.types.is_float64(pa_type):
        return T.FLOAT64
    if pa.types.is_string(pa_type) or pa.types.is_large_string(pa_type):
        return T.STRING
    if pa.types.is_date32(pa_type):
        return T.DATE
    if pa.types.is_timestamp(pa_type):
        return T.TIMESTAMP
    if pa.types.is_decimal(pa_type):
        return T.decimal(pa_type.precision, pa_type.scale)
    if pa.types.is_list(pa_type) or pa.types.is_large_list(pa_type):
        return T.array(_arrow_to_logical(pa_type.value_type))
    if pa.types.is_struct(pa_type):
        return T.struct([(pa_type.field(i).name,
                          _arrow_to_logical(pa_type.field(i).type))
                         for i in range(pa_type.num_fields)])
    if pa.types.is_map(pa_type):
        return T.map_of(_arrow_to_logical(pa_type.key_type),
                        _arrow_to_logical(pa_type.item_type))
    raise TypeError(f"unsupported arrow type {pa_type}")


def logical_to_arrow(dt: DataType):
    import pyarrow as pa
    m = {
        T.BOOLEAN: pa.bool_(), T.INT8: pa.int8(), T.INT16: pa.int16(),
        T.INT32: pa.int32(), T.INT64: pa.int64(), T.FLOAT32: pa.float32(),
        T.FLOAT64: pa.float64(), T.STRING: pa.string(), T.DATE: pa.date32(),
        T.TIMESTAMP: pa.timestamp("us"),
    }
    if dt.is_decimal:
        return pa.decimal128(dt.precision, dt.scale)
    if dt.kind == T.TypeKind.ARRAY:
        return pa.list_(logical_to_arrow(dt.element))
    if dt.kind == T.TypeKind.STRUCT:
        return pa.struct([pa.field(n, logical_to_arrow(t))
                          for n, t in dt.fields])
    if dt.kind == T.TypeKind.MAP:
        return pa.map_(logical_to_arrow(dt.fields[0][1]),
                       logical_to_arrow(dt.fields[1][1]))
    return m[dt]


def _pad_to(arr: np.ndarray, capacity: int) -> np.ndarray:
    if arr.shape[0] == capacity:
        return arr
    out = np.zeros((capacity,) + arr.shape[1:], dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def zero_scalar(t):
    """Typed zero for null-filling an arrow column of type ``t`` — the ONE
    definition shared by scan upload and the device explode."""
    import pyarrow as pa
    if pa.types.is_boolean(t):
        return pa.scalar(False, type=t)
    if pa.types.is_date(t):
        return pa.scalar(datetime.date(1970, 1, 1), type=t)
    if pa.types.is_timestamp(t):
        return pa.scalar(datetime.datetime(1970, 1, 1), type=t)
    return pa.scalar(0).cast(t)


def from_arrow(table, min_capacity: int = 1024, device=None) -> ColumnBatch:
    """Build a ColumnBatch from a pyarrow Table (one upload per column)."""
    import pyarrow as pa
    n = table.num_rows
    has_strings = any(_arrow_to_logical(t).is_string
                      for t in table.schema.types)
    cap = bucket_capacity(n, min_capacity, has_strings=has_strings)
    fields: List[Field] = []
    cols: List[Column] = []
    from .utils.metrics import upload
    for name, col in zip(table.column_names, table.columns):
        chunks = _page_chunks(col)
        if chunks is not None:
            # the file's page codes: no string is made or padded here
            fields.append(Field(name, T.STRING, col.null_count > 0))
            cols.append(PageCodedStringColumn(chunks, cap))
            continue
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks() if col.num_chunks != 1 else col.chunk(0)
        if pa.types.is_dictionary(col.type):
            col = col.dictionary_decode()
        dt = _arrow_to_logical(col.type)
        fields.append(Field(name, dt, col.null_count > 0))
        if dt.is_string or dt.is_nested or \
                (dt.is_decimal and dt.precision > 38):
            # no device representation (decimal>38 exceeds the emulated
            # 128-bit limbs) — ride as a host column; sig tagging keeps
            # compute over these off the device
            cols.append(HostStringColumn(col, capacity=cap))
            continue
        if dt.is_decimal:
            # the scaled values straight from the arrow buffer: int64 for
            # p <= 18, (n, 2) int64 limbs [lo, hi] of the 128-bit value
            # past it (emulated int128)
            data, valid_np = decimal_scaled(col, dt.is_wide_decimal)
            data = _pad_to(data, cap)
        else:
            # null payload slots are masked by the validity array; fill them
            # with a typed zero so integer casts are well-defined (float NaN
            # payloads at null slots are harmless and stay put).
            if col.null_count > 0 and not dt.is_floating:
                col_f = col.fill_null(zero_scalar(col.type))
            else:
                col_f = col
            np_col = col_f.to_numpy(zero_copy_only=False)
            if dt.kind == T.TypeKind.DATE:
                np_col = np_col.astype("datetime64[D]").astype(np.int32)
            elif dt.kind == T.TypeKind.TIMESTAMP:
                np_col = np_col.astype("datetime64[us]").astype(np.int64)
            else:
                np_col = np_col.astype(dt.numpy_dtype, copy=False)
            data = _pad_to(np.ascontiguousarray(np_col), cap)
            valid_np = np.asarray(col.is_valid()) if col.null_count > 0 else None
        # one counted upload a column, issued as soon as the column is
        # ready: its transfer overlaps the next column's conversion
        jdata, jvalid = upload(
            (data, _pad_to(valid_np, cap) if valid_np is not None
             and col.null_count > 0 else None), device)
        cols.append(DeviceColumn(dt, jdata, jvalid))
    return ColumnBatch(Schema(fields), cols, n)


def from_numpy(data: Dict[str, np.ndarray], min_capacity: int = 1024) -> ColumnBatch:
    """Test/bench helper: build a batch from plain numpy arrays (no nulls)."""
    n = len(next(iter(data.values())))
    cap = bucket_capacity(n, min_capacity)
    fields, cols = [], []
    np_to_logical = {
        np.dtype(np.bool_): T.BOOLEAN, np.dtype(np.int8): T.INT8,
        np.dtype(np.int16): T.INT16, np.dtype(np.int32): T.INT32,
        np.dtype(np.int64): T.INT64, np.dtype(np.float32): T.FLOAT32,
        np.dtype(np.float64): T.FLOAT64,
    }
    for name, arr in data.items():
        arr = np.asarray(arr)
        if arr.dtype.kind in ("U", "O", "S"):
            fields.append(Field(name, T.STRING, False))
            cols.append(HostStringColumn([str(x) for x in arr], capacity=cap))
            continue
        dt = np_to_logical[arr.dtype]
        fields.append(Field(name, dt, False))
        cols.append(DeviceColumn(dt, jnp.asarray(_pad_to(arr, cap))))
    return ColumnBatch(Schema(fields), cols, n)


def decimal_scaled(col, wide: bool):
    """An arrow decimal array's scaled values, read as a view of its value
    buffer (no value goes through python): (n,) int64 for p <= 18, which
    is the low word of each 128-bit value, or (n, 2) int64 limbs [lo, hi];
    NULL slots zero.  Returns (values, validity or None).  Both parquet
    encodings of a decimal (INT64 / FIXED_LEN_BYTE_ARRAY) arrive here as
    the same arrow array; the decode is a ``scan:decimal`` span whose
    seconds add to ``QueryStats.decimal_decode_s``."""
    from .utils import tracing
    from .utils.metrics import QueryStats
    with tracing.span(None, "scan:decimal", "io") as sp:
        out = decimal_words(col, wide)
        sp.set(rows=len(col))
    QueryStats.get().decimal_decode_s += sp.dur
    return out


def decimal_view(col) -> np.ndarray:
    """An arrow decimal array's value buffer as it lies, with no copy:
    (n,) words for a 4- or 8-byte decimal, (n, 2) int64 [lo, hi] for
    decimal128, whose low word alone is the scaled value at p <= 18.
    NULL slots hold whatever the buffer holds."""
    n, off, width = len(col), col.offset, col.type.byte_width
    words = np.frombuffer(col.buffers()[1], dtype=np.int64
                          if width >= 8 else np.int32,
                          count=(off + n) * max(width // 8, 1))
    if width == 16:
        words = words.reshape(-1, 2)
    return words[off:off + n]


def decimal_words(col, wide: bool):
    """:func:`decimal_scaled` with no span: for arrays that are no scan's."""
    words = decimal_view(col).astype(np.int64, copy=False)
    if wide and words.ndim == 1:
        words = np.stack([words, words >> 63], axis=-1)
    elif not wide and words.ndim == 2:
        words = words[:, 0]
    if not col.null_count:
        return np.ascontiguousarray(words), None
    valid = np.asarray(col.is_valid())
    return np.where(valid.reshape((-1,) + (1,) * (words.ndim - 1)),
                    words, 0), valid


def decimal_array(data: np.ndarray, valid: Optional[np.ndarray],
                  dtype: DataType):
    """Scaled values ((n,) int64 or (n, 2) limbs) -> an arrow decimal128
    array, built on their buffer: the inverse of :func:`decimal_scaled`."""
    import pyarrow as pa
    n = len(data)
    words = np.empty((n, 2), dtype=np.int64)
    if data.ndim == 1:
        words[:, 0] = data
        words[:, 1] = data >> 63
    else:
        words[:] = data
    vbuf = None if valid is None else pa.py_buffer(
        np.packbits(np.asarray(valid, dtype=bool), bitorder="little"))
    return pa.Array.from_buffers(logical_to_arrow(dtype), n,
                                 [vbuf, pa.py_buffer(words)])


def _to_arrow_tree(batch: ColumnBatch) -> dict:
    """The device arrays one batched D2H transfer must move to realize
    this batch as an arrow table — shared by the sync and async paths."""
    # keys are column ordinals, not names — names may collide with the
    # reserved mask/validity keys ("#buf0"-style generated names exist)
    fetch = {}
    if batch.sel is not None:
        fetch[("m", -1)] = batch.active_mask()
    for i, col in enumerate(batch.columns):
        if isinstance(col, DictStringColumn):
            if col._decoded is None:
                # codes ride in the same single batched fetch
                fetch[("dc", i)] = col.codes
                if col.valid is not None:
                    fetch[("dv", i)] = col.valid
        elif isinstance(col, DeviceColumn):
            fetch[("d", i)] = col.data
            if col.valid is not None:
                fetch[("v", i)] = col.valid
    return fetch


def to_arrow(batch: ColumnBatch):
    """Download a batch to a pyarrow Table (compacts through the selection).

    All device arrays are fetched in ONE ``jax.device_get`` call: each
    transfer blocks on the device, so per-column ``np.asarray`` would
    dominate collect.
    """
    fetch = _to_arrow_tree(batch)
    from .utils.metrics import fetch as _counted_fetch
    host = _counted_fetch(fetch) if fetch else {}
    return _to_arrow_finish(batch, host)


def to_arrow_async(batch: ColumnBatch):
    """Start the batch's D2H transfer NOW; return a zero-arg finisher.

    The copy runs behind the dispatch front (utils.metrics.fetch_async),
    so the next batch's XLA programs dispatch while this one's bytes move
    — the finisher blocks only on whatever is still in flight.  The
    finisher pins the batch's device buffers until called; CollectExec
    bounds how many are outstanding by the pipeline depth.
    """
    fetch = _to_arrow_tree(batch)
    from .utils.metrics import fetch_async as _afetch
    fut = _afetch(fetch) if fetch else None

    def finish():
        return _to_arrow_finish(batch, fut.result() if fut is not None  # wait-ok (async D2H already in flight; an in-query wedge is the watchdog's to reclaim)
                                else {})
    return finish


def _to_arrow_finish(batch: ColumnBatch, host: dict):
    from .utils import tracing
    with tracing.span(None, "result:arrow", "result"):
        return _host_to_arrow(batch, host)


def _host_to_arrow(batch: ColumnBatch, host: dict):
    import pyarrow as pa
    for i, col in enumerate(batch.columns):
        if isinstance(col, DictStringColumn) and ("dc", i) in host:
            col._decoded = decode_dict_codes(
                host[("dc", i)], host.get(("dv", i)), col.dictionary)
    mask = None
    if batch.sel is not None:
        mask = host[("m", -1)][: batch.num_rows]
    arrays, names = [], []
    for i, (f, col) in enumerate(zip(batch.schema, batch.columns)):
        names.append(f.name)
        if isinstance(col, HostStringColumn):
            arr = col.array.slice(0, batch.num_rows)
            if mask is not None:
                arr = arr.filter(pa.array(mask))
            arrays.append(arr)
            continue
        data = host[("d", i)][: batch.num_rows]
        valid = (host[("v", i)][: batch.num_rows]
                 if col.valid is not None else None)
        if mask is not None:
            data = data[mask]
            valid = valid[mask] if valid is not None else None
        if f.dtype.kind == T.TypeKind.DATE:
            arrays.append(pa.array(data.astype("datetime64[D]"),
                                   type=pa.date32(),
                                   mask=(~valid if valid is not None else None)))
        elif f.dtype.kind == T.TypeKind.TIMESTAMP:
            arrays.append(pa.array(data.astype("datetime64[us]"),
                                   type=pa.timestamp("us"),
                                   mask=(~valid if valid is not None else None)))
        elif f.dtype.is_decimal:
            arrays.append(decimal_array(data, valid, f.dtype))
        else:
            arrays.append(pa.array(data, type=logical_to_arrow(f.dtype),
                                   mask=(~valid if valid is not None else None)))
    return pa.table(dict(zip(names, arrays)))
