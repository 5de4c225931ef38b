"""Logical data types and the TypeSig support-signature algebra.

TPU-native re-design of the reference's type system:
  - Spark SQL logical types (reference: sql-plugin TypeChecks.scala:141 ``TypeEnum``)
    map onto physical JAX/XLA dtypes here.  There is no native string or
    decimal128 on TPU, so STRING is carried as Arrow offsets+bytes (host or
    device int tensors) and DECIMAL is carried as a scaled int64 (precision
    <= 18) with emulated wide arithmetic planned for 128-bit.
  - ``TypeSig`` mirrors the reference's support-signature algebra
    (TypeChecks.scala:171,556): each operator/expression declares which input
    and output types it supports on the accelerator, and the planner uses the
    signature to tag unsupported nodes for CPU fallback with a reason.
"""

from __future__ import annotations

import decimal as _decimal
import enum
from dataclasses import dataclass
from typing import Iterable, Optional

import numpy as np

__all__ = [
    "DataType",
    "BOOLEAN", "INT8", "INT16", "INT32", "INT64",
    "FLOAT32", "FLOAT64", "STRING", "DATE", "TIMESTAMP",
    "NULLTYPE", "decimal",
    "TypeSig",
]


class TypeKind(enum.Enum):
    BOOLEAN = "boolean"
    INT8 = "tinyint"
    INT16 = "smallint"
    INT32 = "int"
    INT64 = "bigint"
    FLOAT32 = "float"
    FLOAT64 = "double"
    STRING = "string"
    DATE = "date"              # days since epoch, int32 physical
    TIMESTAMP = "timestamp"    # microseconds since epoch, int64 physical
    DECIMAL = "decimal"        # scaled integer, int64 physical for p <= 18
    NULL = "void"
    ARRAY = "array"
    STRUCT = "struct"
    MAP = "map"


_NUMPY_PHYSICAL = {
    TypeKind.BOOLEAN: np.bool_,
    TypeKind.INT8: np.int8,
    TypeKind.INT16: np.int16,
    TypeKind.INT32: np.int32,
    TypeKind.INT64: np.int64,
    TypeKind.FLOAT32: np.float32,
    TypeKind.FLOAT64: np.float64,
    TypeKind.DATE: np.int32,
    TypeKind.TIMESTAMP: np.int64,
    TypeKind.DECIMAL: np.int64,
    TypeKind.NULL: np.bool_,
}


@dataclass(frozen=True)
class DataType:
    """A Spark-SQL-equivalent logical type.

    ``precision``/``scale`` are used only for DECIMAL.  ``element``/``fields``
    are used for nested types (ARRAY/STRUCT/MAP), which are planned but not
    yet executed on device.
    """

    kind: TypeKind
    precision: int = 0
    scale: int = 0
    element: Optional["DataType"] = None
    fields: tuple = ()

    # ---- classification helpers -------------------------------------------------
    @property
    def is_numeric(self) -> bool:
        return self.kind in (
            TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64,
            TypeKind.FLOAT32, TypeKind.FLOAT64, TypeKind.DECIMAL,
        )

    @property
    def is_integral(self) -> bool:
        return self.kind in (
            TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64,
        )

    @property
    def is_floating(self) -> bool:
        return self.kind in (TypeKind.FLOAT32, TypeKind.FLOAT64)

    @property
    def is_datetime(self) -> bool:
        return self.kind in (TypeKind.DATE, TypeKind.TIMESTAMP)

    @property
    def is_string(self) -> bool:
        return self.kind == TypeKind.STRING

    @property
    def is_decimal(self) -> bool:
        return self.kind == TypeKind.DECIMAL

    @property
    def is_host_carried(self) -> bool:
        """True if columns of this type ride as host arrow columns in
        device batches (no device representation: strings, nested,
        decimal beyond emulated-128-bit range)."""
        return (self.is_string or self.is_nested
                or (self.is_decimal and self.precision > 38))

    @property
    def is_wide_decimal(self) -> bool:
        """decimal with 18 < precision <= 38: device representation is a
        (capacity, 2) int64 limb array [lo, hi] of the scaled 128-bit
        two's-complement value (GpuCast.scala/DecimalUtil.scala analog —
        the TPU has no int128, so add/compare/sum emulate via limbs;
        unsupported wide ops fall back per TypeSig)."""
        return self.is_decimal and 18 < self.precision <= 38

    @property
    def is_nested(self) -> bool:
        return self.kind in (TypeKind.ARRAY, TypeKind.STRUCT, TypeKind.MAP)

    # ---- physical mapping -------------------------------------------------------
    @property
    def numpy_dtype(self):
        """Physical numpy/JAX dtype used for the device representation."""
        if self.kind == TypeKind.STRING:
            # strings are (offsets:int32, bytes:uint8); the "data" array of a
            # device string column is the int32 dictionary code / offset array.
            return np.int32
        if self.is_nested:
            raise TypeError(f"no flat physical dtype for {self}")
        return np.dtype(_NUMPY_PHYSICAL[self.kind])

    def __str__(self) -> str:
        if self.kind == TypeKind.DECIMAL:
            return f"decimal({self.precision},{self.scale})"
        if self.kind == TypeKind.ARRAY:
            return f"array<{self.element}>"
        if self.kind == TypeKind.STRUCT:
            inner = ",".join(f"{n}:{t}" for n, t in self.fields)
            return f"struct<{inner}>"
        if self.kind == TypeKind.MAP:
            return f"map<{self.fields[0][1]},{self.fields[1][1]}>"
        return self.kind.value

    def simple_name(self) -> str:
        return str(self)


BOOLEAN = DataType(TypeKind.BOOLEAN)
INT8 = DataType(TypeKind.INT8)
INT16 = DataType(TypeKind.INT16)
INT32 = DataType(TypeKind.INT32)
INT64 = DataType(TypeKind.INT64)
FLOAT32 = DataType(TypeKind.FLOAT32)
FLOAT64 = DataType(TypeKind.FLOAT64)
STRING = DataType(TypeKind.STRING)
DATE = DataType(TypeKind.DATE)
TIMESTAMP = DataType(TypeKind.TIMESTAMP)
NULLTYPE = DataType(TypeKind.NULL)


def array(element: DataType) -> DataType:
    """ARRAY<element> — produced by collect_list/collect_set; carried as
    host arrow list columns (no device representation)."""
    return DataType(TypeKind.ARRAY, element=element)


def struct(fields) -> DataType:
    """STRUCT<name: type, ...> — carried as host arrow struct columns
    (complexTypeCreator.scala analog); ``fields`` is [(name, DataType)]."""
    return DataType(TypeKind.STRUCT, fields=tuple(fields))


def map_of(key: DataType, value: DataType) -> DataType:
    """MAP<key, value> — carried as host arrow map columns
    (GpuCreateMap, complexTypeCreator.scala:84); python-space values are
    lists of (key, value) pairs."""
    return DataType(TypeKind.MAP, fields=(("key", key), ("value", value)))


def decimal(precision: int, scale: int) -> DataType:
    # precision <= 18: scaled int64; 18 < p <= 38: two int64 limbs on
    # device (add/compare/sum emulated); > 38: host-carried arrow column.
    return DataType(TypeKind.DECIMAL, precision=precision, scale=scale)


_INT_WIDENING = [TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64]


# Max decimal digits an integral type can hold (Spark's DecimalType.forType).
_INT_DECIMAL_DIGITS = {TypeKind.INT8: 3, TypeKind.INT16: 5,
                       TypeKind.INT32: 10, TypeKind.INT64: 19}


def integral_as_decimal(a: DataType) -> DataType:
    """View an integral type as the narrowest decimal that can hold it."""
    return decimal(min(_INT_DECIMAL_DIGITS[a.kind], 18), 0)


# -- Spark 3.x decimal result types (DecimalPrecision, with its default
# spark.sql.decimalOperations.allowPrecisionLoss=true) ------------------------

MAX_DECIMAL_PRECISION = 38
MINIMUM_ADJUSTED_SCALE = 6


def decimal_for_integral(a: DataType) -> DataType:
    """DecimalType.forType: an integral COLUMN in decimal arithmetic
    (tinyint (3,0), smallint (5,0), int (10,0), bigint (20,0))."""
    return decimal({TypeKind.INT8: 3, TypeKind.INT16: 5, TypeKind.INT32: 10,
                    TypeKind.INT64: 20}[a.kind], 0)


def decimal_of_literal(v: int) -> DataType:
    """DecimalType.fromLiteral: an integral LITERAL at its own digits
    (``1`` is decimal(1,0))."""
    return decimal(len(str(abs(int(v)))), 0)


def decimal_bounded(p: int, s: int) -> DataType:
    return decimal(min(p, MAX_DECIMAL_PRECISION),
                   min(s, MAX_DECIMAL_PRECISION))


def adjust_precision_scale(p: int, s: int) -> DataType:
    """DecimalType.adjustPrecisionScale: past 38 digits the integer digits
    are kept and the scale gives way, down to min(s, 6)."""
    if p <= MAX_DECIMAL_PRECISION:
        return decimal(p, s)
    int_digits = p - s
    return decimal(MAX_DECIMAL_PRECISION,
                   max(MAX_DECIMAL_PRECISION - int_digits,
                       min(s, MINIMUM_ADJUSTED_SCALE)))


def decimal_add_type(a: DataType, b: DataType) -> DataType:
    """``+`` / ``-``: p = max(s1, s2) + max(p1 - s1, p2 - s2) + 1."""
    s = max(a.scale, b.scale)
    return adjust_precision_scale(
        s + max(a.precision - a.scale, b.precision - b.scale) + 1, s)


def decimal_multiply_type(a: DataType, b: DataType) -> DataType:
    """``*``: p = p1 + p2 + 1, s = s1 + s2."""
    return adjust_precision_scale(a.precision + b.precision + 1,
                                  a.scale + b.scale)


def decimal_divide_type(a: DataType, b: DataType) -> DataType:
    """``/``: s = max(6, s1 + p2 + 1), p = p1 - s1 + s2 + s."""
    s = max(MINIMUM_ADJUSTED_SCALE, a.scale + b.precision + 1)
    return adjust_precision_scale(a.precision - a.scale + b.scale + s, s)


def decimal_sum_type(a: DataType) -> DataType:
    return decimal_bounded(a.precision + 10, a.scale)


def decimal_avg_type(a: DataType) -> DataType:
    return decimal_bounded(a.precision + 4, a.scale + 4)


# python's decimal rounds to its context's 28 digits: wide values go
# through a context that holds them whole
_EXACT = _decimal.Context(prec=100)


def decimal_value(unscaled: int, scale: int) -> _decimal.Decimal:
    """The Decimal of ``unscaled`` units of 10^-scale, exactly, with that
    exponent (``Decimal(x).scaleb(-s)`` rounds past 28 digits)."""
    return _decimal.Decimal(int(unscaled)).scaleb(-scale, context=_EXACT)


def unscaled_value(v: _decimal.Decimal, scale: int) -> int:
    """A Decimal as a count of 10^-scale units (rounded half-even where it
    holds finer digits), exactly."""
    return int(v.scaleb(scale, context=_EXACT).to_integral_value(
        context=_EXACT))


def common_type(a: DataType, b: DataType) -> DataType:
    """Spark's findTightestCommonType subset for binary arithmetic/comparison."""
    if a == b:
        return a
    if a.kind == TypeKind.NULL:
        return b
    if b.kind == TypeKind.NULL:
        return a
    if a.is_integral and b.is_integral:
        ia, ib = _INT_WIDENING.index(a.kind), _INT_WIDENING.index(b.kind)
        return DataType(_INT_WIDENING[max(ia, ib)])
    if a.is_floating and b.is_floating:
        return FLOAT64 if TypeKind.FLOAT64 in (a.kind, b.kind) else FLOAT32
    if (a.is_integral and b.is_floating):
        return b if b.kind == TypeKind.FLOAT64 or a.kind in _INT_WIDENING[:2] else FLOAT64
    if (b.is_integral and a.is_floating):
        return common_type(b, a)
    if a.is_decimal and b.is_decimal:
        # widest integral part + widest scale (Spark widerDecimalType)
        s = max(a.scale, b.scale)
        ip = max(a.precision - a.scale, b.precision - b.scale)
        # Spark add/compare result precision caps at DECIMAL128's 38
        # (two-limb device kernels handle 18 < p <= 38)
        return decimal(min(ip + s, 38), s)
    if a.is_decimal and b.is_integral:
        return common_type(a, integral_as_decimal(b))
    if b.is_decimal and a.is_integral:
        return common_type(integral_as_decimal(a), b)
    if (a.is_decimal and b.is_floating) or (b.is_decimal and a.is_floating):
        return FLOAT64
    raise TypeError(f"no common type for {a} and {b}")


class TypeSig:
    """A set of supported :class:`DataType` kinds, with reason reporting.

    Mirrors the reference's ``TypeSig`` algebra (TypeChecks.scala:171): sigs
    combine with ``+`` and subtract with ``-``; ``check(dt)`` returns None when
    supported or a human-readable reason string used by the planner's
    ``will_not_work_on_tpu`` accumulation (RapidsMeta.scala:184).
    """

    def __init__(self, kinds: Iterable[TypeKind] = (), max_decimal_precision: int = 18,
                 notes: Optional[dict] = None):
        self.kinds = frozenset(kinds)
        self.max_decimal_precision = max_decimal_precision
        self.notes = dict(notes or {})

    # -- construction --------------------------------------------------------------
    @staticmethod
    def none() -> "TypeSig":
        return TypeSig(())

    def __add__(self, other: "TypeSig") -> "TypeSig":
        return TypeSig(self.kinds | other.kinds,
                       max(self.max_decimal_precision, other.max_decimal_precision),
                       {**self.notes, **other.notes})

    def __sub__(self, other: "TypeSig") -> "TypeSig":
        return TypeSig(self.kinds - other.kinds, self.max_decimal_precision, self.notes)

    def describe(self) -> str:
        """Compact human-readable rendering for generated docs."""
        order = [TypeKind.BOOLEAN, TypeKind.INT8, TypeKind.INT16,
                 TypeKind.INT32, TypeKind.INT64, TypeKind.FLOAT32,
                 TypeKind.FLOAT64, TypeKind.DECIMAL, TypeKind.STRING,
                 TypeKind.DATE, TypeKind.TIMESTAMP, TypeKind.NULL,
                 TypeKind.ARRAY, TypeKind.STRUCT, TypeKind.MAP]
        names = [k.value for k in order if k in self.kinds]
        extra = [k.value for k in self.kinds
                 if k not in order]  # pragma: no cover
        return ", ".join(names + sorted(extra))

    def with_note(self, kind: TypeKind, note: str) -> "TypeSig":
        notes = dict(self.notes)
        notes[kind] = note
        return TypeSig(self.kinds, self.max_decimal_precision, notes)

    # -- checking ------------------------------------------------------------------
    def supports(self, dt: DataType) -> bool:
        return self.check(dt) is None

    def check(self, dt: DataType) -> Optional[str]:
        if dt.kind not in self.kinds:
            return f"type {dt} is not supported"
        if dt.kind == TypeKind.DECIMAL and dt.precision > self.max_decimal_precision:
            return (f"decimal precision {dt.precision} exceeds max supported "
                    f"{self.max_decimal_precision}")
        if dt.kind in self.notes:
            return None  # supported with a note, not a rejection
        return None

    def __str__(self):
        return "{" + ", ".join(sorted(k.value for k in self.kinds)) + "}"


def _sig(*kinds: TypeKind) -> TypeSig:
    return TypeSig(kinds)


# Common signatures (reference: TypeChecks.scala:664 ``commonCudfTypes``).
TypeSig.BOOLEAN = _sig(TypeKind.BOOLEAN)
TypeSig.integral = _sig(TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64)
TypeSig.fp = _sig(TypeKind.FLOAT32, TypeKind.FLOAT64)
TypeSig.numeric = TypeSig.integral + TypeSig.fp + _sig(TypeKind.DECIMAL)
TypeSig.datetime = _sig(TypeKind.DATE, TypeKind.TIMESTAMP)
TypeSig.string = _sig(TypeKind.STRING)
TypeSig.null = _sig(TypeKind.NULL)
TypeSig.common = (TypeSig.numeric + TypeSig.datetime + TypeSig.BOOLEAN
                  + TypeSig.string + TypeSig.null)
TypeSig.orderable = TypeSig.common
TypeSig.device_compute = TypeSig.common - TypeSig.string  # strings: host kernels for now
# opt-in for expressions with emulated two-limb decimal128 kernels
TypeSig.decimal128 = TypeSig((TypeKind.DECIMAL,), max_decimal_precision=38)
TypeSig.all = TypeSig.common + _sig(TypeKind.ARRAY, TypeKind.STRUCT, TypeKind.MAP)
