"""Expression IR: Spark-SQL-semantics expressions that lower to JAX.

TPU-native analog of the reference's ``GpuExpression`` library
(GpuExpressions.scala:99-141 ``columnarEval``; expression files under
org/apache/spark/sql/rapids/).  The key architectural difference: the
reference issues one cuDF kernel per expression node, with an optional "AST"
fusion path for joins (GpuExpressions.scala:157 ``convertToAst``).  On TPU
*every* expression lowers into the enclosing stage's single XLA computation —
whole-stage fusion is the default, not the exception — so the per-node
``eval`` here returns traced ``jnp`` values, and ``jax.jit`` + XLA do the
fusion and scheduling.

Null model: a value is a pair ``(data, valid)`` where ``valid`` is a boolean
mask or ``None`` (= all valid).  Semantics match Spark CPU: null propagation
for arithmetic, Kleene three-valued logic for AND/OR, null (not NaN/error) for
division by zero unless ANSI mode.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import types as T
from .types import DataType, TypeSig

__all__ = [
    "Expression", "BoundReference", "UnresolvedColumn", "Literal", "Alias",
    "ParamExpr", "bind_params",
    "Cast", "Add", "Subtract", "Multiply", "Divide", "IntegralDivide", "Remainder",
    "Pmod", "UnaryMinus", "Abs",
    "EqualTo", "EqualNullSafe", "LessThan", "LessThanOrEqual", "GreaterThan",
    "GreaterThanOrEqual", "Not", "And", "Or", "In",
    "IsNull", "IsNotNull", "IsNan", "Coalesce", "If", "CaseWhen",
    "Value", "bind", "AggregateExpression",
]

Value = Tuple[jax.Array, Optional[jax.Array]]  # (data, valid-or-None)


def _and_valid(a: Optional[jax.Array], b: Optional[jax.Array]) -> Optional[jax.Array]:
    if a is None:
        return b
    if b is None:
        return a
    return a & b


class Expression:
    """Base expression node.  Subclasses set ``dtype``/``nullable`` on resolve."""

    dtype: DataType = None  # set by bind()
    nullable: bool = True
    children: Tuple["Expression", ...] = ()

    # Accelerator support signature, checked by the planner (TypeChecks.scala
    # ExprChecks analog).  Default: common non-nested, non-string types.
    input_sig: TypeSig = TypeSig.device_compute
    output_sig: TypeSig = TypeSig.device_compute

    def eval(self, ctx: "EvalContext") -> Value:
        raise NotImplementedError(type(self).__name__)

    # -- resolution ---------------------------------------------------------------
    def resolved(self) -> bool:
        return self.dtype is not None and all(c.resolved() for c in self.children)

    def fingerprint(self) -> str:
        """Stable structural key for the stage-compile cache."""
        args = ",".join(c.fingerprint() for c in self.children)
        extra = self._fp_extra()
        return f"{type(self).__name__}[{extra}]({args})"

    def _fp_extra(self) -> str:
        return str(self.dtype)

    def references(self) -> set:
        out = set()
        for c in self.children:
            out |= c.references()
        return out

    def __repr__(self):
        return self.fingerprint()


class EvalContext:
    """Carries the stage inputs during tracing.

    ``arrays[i]`` is the (data, valid) pair for bound reference ordinal ``i``;
    ``capacity`` is the padded physical length; ``active`` is the live-row mask
    (padding + upstream filters), used by aggregates and by ops whose padding
    lanes could misbehave (division, gathers).
    """

    def __init__(self, arrays: Sequence[Value], capacity: int,
                 active: Optional[jax.Array] = None, ansi: bool = False,
                 extras: Sequence[Value] = ()):
        self.arrays = list(arrays)
        self.capacity = capacity
        self.active = active
        self.ansi = ansi
        # host-precomputed inputs (dictionary-lowered string predicates)
        self.extras = list(extras)
        # ANSI error channel: expressions append per-row error masks
        # (overflow, invalid cast, division by zero); the enclosing stage
        # reduces them into one flag it raises on (GpuCast.scala ANSI /
        # SparkArithmeticException analog)
        self.errors: list = []

    def record_error(self, err, valid=None) -> None:
        """Append a per-row ANSI error mask, confined to live valid rows."""
        if valid is not None:
            err = err & valid
        if self.active is not None:
            err = err & self.active
        self.errors.append(err)


# ---------------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------------

class UnresolvedColumn(Expression):
    """A by-name column reference produced by the DataFrame API (``col('x')``)."""

    def __init__(self, name: str):
        self.name = name
        self.children = ()

    def resolved(self):
        return False

    def _fp_extra(self):
        return self.name

    def references(self):
        return {self.name}


class BoundReference(Expression):
    input_sig = TypeSig.device_compute + TypeSig.decimal128
    output_sig = TypeSig.device_compute + TypeSig.decimal128

    def __init__(self, ordinal: int, dtype: DataType, nullable: bool, name: str = ""):
        self.ordinal = ordinal
        self.dtype = dtype
        self.nullable = nullable
        self.name = name
        self.children = ()

    def eval(self, ctx: EvalContext) -> Value:
        return ctx.arrays[self.ordinal]

    def _fp_extra(self):
        return f"{self.ordinal}:{self.dtype}"


class Literal(Expression):
    input_sig = TypeSig.device_compute + TypeSig.decimal128
    output_sig = TypeSig.device_compute + TypeSig.decimal128

    def __init__(self, value: Any, dtype: Optional[DataType] = None):
        self.value = value
        self.dtype = dtype if dtype is not None else _infer_literal_type(value)
        self.nullable = value is None
        self.children = ()

    def eval(self, ctx: EvalContext) -> Value:
        wide = getattr(self.dtype, "is_wide_decimal", False)
        if self.value is None:
            shape = (ctx.capacity, 2) if wide else (ctx.capacity,)
            data = jnp.zeros(shape, dtype=self.dtype.numpy_dtype)
            return data, jnp.zeros((ctx.capacity,), dtype=jnp.bool_)
        if wide:
            u = int(physical_literal(self.value, self.dtype)) & ((1 << 128) - 1)
            lo, hi = u & ((1 << 64) - 1), u >> 64
            lo = lo - (1 << 64) if lo >= (1 << 63) else lo
            hi = hi - (1 << 64) if hi >= (1 << 63) else hi
            row = jnp.asarray(np.array([lo, hi], dtype=np.int64))
            return jnp.broadcast_to(row, (ctx.capacity, 2)), None
        data = jnp.full((ctx.capacity,), physical_literal(self.value, self.dtype),
                        dtype=self.dtype.numpy_dtype)
        return data, None

    def _fp_extra(self):
        return f"{self.value!r}:{self.dtype}"


def physical_literal(v: Any, dtype: DataType):
    """Convert a python literal to its physical device representation."""
    import datetime
    if dtype.is_decimal:
        from decimal import Decimal
        if isinstance(v, Decimal):
            return T.unscaled_value(v, dtype.scale)
        return int(round(float(v) * 10 ** dtype.scale))
    if dtype.kind == T.TypeKind.DATE:
        if isinstance(v, datetime.date):
            return (v - datetime.date(1970, 1, 1)).days
        return int(v)
    if dtype.kind == T.TypeKind.TIMESTAMP:
        if isinstance(v, datetime.datetime):
            epoch = datetime.datetime(1970, 1, 1, tzinfo=v.tzinfo)
            return int((v - epoch).total_seconds() * 1_000_000)
        return int(v)
    return v


def _infer_literal_type(v: Any) -> DataType:
    import datetime
    import decimal as _dec
    if v is None:
        return T.NULLTYPE
    if isinstance(v, _dec.Decimal):
        sign, digits, exp = v.as_tuple()
        scale = max(0, -exp)
        precision = max(len(digits), scale)
        return T.decimal(min(precision, 38), scale)
    if isinstance(v, bool):
        return T.BOOLEAN
    if isinstance(v, int):
        return T.INT32 if -(2**31) <= v < 2**31 else T.INT64
    if isinstance(v, float):
        return T.FLOAT64
    if isinstance(v, str):
        return T.STRING
    if isinstance(v, datetime.datetime):
        return T.TIMESTAMP
    if isinstance(v, datetime.date):
        return T.DATE
    if isinstance(v, np.generic):
        return {np.dtype(np.int32): T.INT32, np.dtype(np.int64): T.INT64,
                np.dtype(np.float32): T.FLOAT32,
                np.dtype(np.float64): T.FLOAT64}[v.dtype]
    raise TypeError(f"cannot infer literal type for {v!r}")


# ---------------------------------------------------------------------------------
# Prepared-statement parameters.  A ParamExpr is a literal-shaped leaf whose
# VALUE is resolved from a contextvar at evaluation time, not baked at plan
# time — the prepared-statement plan cache (server/prepared.py) plans a query
# once and re-executes the same physical tree under different bindings.
# Deliberately NOT a Literal subclass: plan-time literal consumers (filter
# pushdown in plan/pushdown.py, scan cache tokens) must skip parameters, or a
# prepare-time value would be baked into pushed predicates and silently
# mis-prune later executions.  The value DOES enter the expression
# fingerprint, so each distinct binding compiles (and caches) its own stage
# program — exactly like the equivalent inline literal.
# ---------------------------------------------------------------------------------

_PARAM_BINDINGS: "contextvars.ContextVar[Optional[Tuple[Any, ...]]]" = \
    contextvars.ContextVar("srt_param_bindings", default=None)


@contextlib.contextmanager
def bind_params(values: Sequence[Any]):
    """Scope a tuple of prepared-statement parameter values; ParamExpr
    leaves in any plan executed inside resolve against it.  Scheduler
    workers run copied contexts, so a binding installed inside the
    submitted callable stays isolated per query."""
    tok = _PARAM_BINDINGS.set(tuple(values))
    try:
        yield
    finally:
        _PARAM_BINDINGS.reset(tok)


class ParamExpr(Expression):
    """Placeholder for prepared-statement parameter ``index`` with a
    DECLARED type (the spec carries it — planning needs the dtype before
    any value exists)."""

    input_sig = TypeSig.device_compute + TypeSig.decimal128
    output_sig = TypeSig.device_compute + TypeSig.decimal128

    def __init__(self, index: int, dtype: DataType):
        self.index = int(index)
        self.dtype = dtype
        self.nullable = True
        self.children = ()

    @property
    def value(self) -> Any:
        vals = _PARAM_BINDINGS.get()
        if vals is None:
            raise RuntimeError(
                f"parameter ?{self.index} evaluated outside bind_params() "
                f"— prepared statements execute through "
                f"server/prepared.py, which installs the binding scope")
        if self.index >= len(vals):
            raise RuntimeError(
                f"parameter ?{self.index} unbound: only {len(vals)} "
                f"values supplied")
        return vals[self.index]

    def eval(self, ctx: "EvalContext") -> Value:
        # delegate to Literal for the physical encoding (decimal scaling,
        # epoch conversion, null broadcast) — one literal lowering
        return Literal(self.value, self.dtype).eval(ctx)

    def _fp_extra(self):
        # BOUND, the value keys the program cache: distinct bindings are
        # distinct programs, identical re-bindings reuse the executable.
        # UNBOUND (plan-time explain/node_desc rendering), stay
        # structural — `?i` — like the SQL placeholder it is.
        vals = _PARAM_BINDINGS.get()
        if vals is None or self.index >= len(vals):
            return f"?{self.index}:{self.dtype}"
        return f"?{self.index}={vals[self.index]!r}:{self.dtype}"


class Alias(Expression):
    input_sig = TypeSig.device_compute + TypeSig.decimal128
    output_sig = TypeSig.device_compute + TypeSig.decimal128

    def __init__(self, child: Expression, name: str):
        self.children = (child,)
        self.name = name
        self.dtype = child.dtype
        self.nullable = child.nullable

    def eval(self, ctx):
        return self.children[0].eval(ctx)

    def _fp_extra(self):
        return self.name


# ---------------------------------------------------------------------------------
# Cast (numeric subset here; the full GpuCast.scala matrix grows in ops/cast.py)
# ---------------------------------------------------------------------------------

class Cast(Expression):
    def __init__(self, child: Expression, to: DataType, ansi: bool = False):
        self.children = (child,)
        self.dtype = to
        self.nullable = child.nullable or self._can_produce_null(child.dtype, to)
        self.ansi = ansi

    @staticmethod
    def _can_produce_null(src: DataType, dst: DataType) -> bool:
        return src.is_string  # string->number parse failures become null

    def eval(self, ctx: EvalContext) -> Value:
        from .ops.cast import cast_value
        data, valid = self.children[0].eval(ctx)
        ansi = self.ansi or ctx.ansi
        errors = [] if ansi else None
        out = cast_value(data, valid, self.children[0].dtype, self.dtype,
                         ansi=ansi, errors=errors)
        if errors:
            ctx.record_error(errors[0], valid)
        return out

    def _fp_extra(self):
        return f"->{self.dtype}"


# ---------------------------------------------------------------------------------
# Arithmetic (reference: org/apache/spark/sql/rapids/arithmetic.scala)
# ---------------------------------------------------------------------------------

def promote_physical(data: jax.Array, src: DataType, dst: DataType) -> jax.Array:
    """Convert a physical device value from ``src``'s representation to
    ``dst``'s, honoring decimal scale (decimals are scaled int64 on device).

    A plain astype of a decimal's scaled int would be silently off by
    10^scale; promotion must rescale (decimal→float divides by 10^scale,
    decimal→decimal shifts by the scale delta, int→decimal multiplies in).
    """
    from .ops import wide_decimal as _wd
    np_dt = dst.numpy_dtype
    src_wide = getattr(src, "is_wide_decimal", False)
    dst_wide = getattr(dst, "is_wide_decimal", False)
    if dst_wide:
        # target is two-limb int128: lift then rescale by the scale delta
        if src_wide:
            limbs = data
            delta = dst.scale - src.scale
        elif src.is_decimal:
            limbs = _wd.from_scaled64(data)
            delta = dst.scale - src.scale
        else:  # integral / bool operand joining a wide computation
            limbs = _wd.from_scaled64(data.astype(jnp.int64))
            delta = dst.scale
        if delta < 0:
            raise TypeError(
                f"wide-decimal down-scale {src} -> {dst} not supported "
                "on device")
        return _wd.mul_pow10(limbs, delta)
    if src_wide and dst.is_floating:
        # lossy by definition (like Spark's Decimal.toDouble): recombine
        # limbs in float64 space, then unscale
        lo, hi = data[..., 0], data[..., 1]
        lo_f = jnp.where(lo >= 0, lo.astype(jnp.float64),
                         lo.astype(jnp.float64) + np.float64(2.0 ** 64))
        val = hi.astype(jnp.float64) * np.float64(2.0 ** 64) + lo_f
        return (val / np.float64(10.0 ** src.scale)).astype(np_dt)
    if src_wide:
        raise TypeError(
            f"wide-decimal narrowing {src} -> {dst} not supported on "
            "device")
    if src.is_decimal and dst.is_floating:
        return data.astype(np_dt) / np.float64(10.0 ** src.scale).astype(np_dt)
    if src.is_decimal and dst.is_decimal:
        if dst.scale == src.scale:
            return data
        if dst.scale > src.scale:
            return data * np.int64(10 ** (dst.scale - src.scale))
        return _round_div(data, 10 ** (src.scale - dst.scale))
    if dst.is_decimal and not src.is_decimal:
        # integral (or bool) operand joining a decimal computation
        return data.astype(np_dt) * np.int64(10 ** dst.scale)
    if data.dtype != np_dt:
        return data.astype(np_dt)
    return data


class BinaryExpression(Expression):
    symbol = "?"

    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)
        if left.resolved() and right.resolved():
            self._resolve()

    def _resolve(self):
        l, r = self.children
        self.dtype = self._result_type(l.dtype, r.dtype)
        self.nullable = l.nullable or r.nullable

    def _result_type(self, lt: DataType, rt: DataType) -> DataType:
        return T.common_type(lt, rt)

    def _eval_children_promoted(self, ctx) -> Tuple[jax.Array, jax.Array,
                                                    Optional[jax.Array]]:
        l, r = self.children
        ld, lv = l.eval(ctx)
        rd, rv = r.eval(ctx)
        ct = self._operand_type()
        ld = promote_physical(ld, l.dtype, ct)
        rd = promote_physical(rd, r.dtype, ct)
        return ld, rd, _and_valid(lv, rv)

    def _operand_type(self) -> DataType:
        return T.common_type(self.children[0].dtype, self.children[1].dtype)


def _decimal_operand(e: Expression) -> Optional[DataType]:
    """The decimal type an operand takes in decimal arithmetic (Spark's
    DecimalPrecision): a decimal as it is, an integral literal at its own
    digits, any other integral at DecimalType.forType; None otherwise."""
    dt = e.dtype
    if dt.is_decimal:
        return dt
    if dt.is_integral:
        if isinstance(e, Literal) and e.value is not None:
            return T.decimal_of_literal(e.value)
        return T.decimal_for_integral(dt)
    return None


def rescale_decimal(data: jax.Array, src: DataType, dst: DataType,
                    limbs: bool):
    """A decimal value (an integral one at scale 0) in ``dst``'s scale and
    representation, HALF_UP where the scale drops; ``limbs`` says whether
    ``data`` is (n, 2) limbs or one int lane.  Returns (data, overflow or
    None): the overflow mask is computed only where ``dst`` may not hold
    the value (fewer integer digits, or as many and a rounding that can
    carry into a new one), and then through 128 bits, so that no int64
    step can wrap unseen."""
    from .ops import wide_decimal as _wd
    delta = dst.scale - src.scale
    room = (dst.precision - dst.scale) - (src.precision - src.scale)
    if (room > 0 or (room == 0 and delta >= 0)) \
            and (dst.is_wide_decimal or not limbs):
        if dst.is_wide_decimal:
            x = data if limbs else _wd.from_scaled64(data.astype(jnp.int64))
            return (_wd.mul_pow10(x, delta) if delta >= 0
                    else _wd.round_pow10(x, -delta)), None
        x = data.astype(jnp.int64)
        return (x * np.int64(10 ** delta) if delta >= 0
                else _round_div(x, 10 ** -delta)), None
    x = data if limbs else _wd.from_scaled64(data.astype(jnp.int64))
    x = _wd.round_pow10(x, -delta) if delta < 0 else x
    over = ~_wd.fits(x, dst.precision - max(delta, 0))
    x = _wd.mul_pow10(x, max(delta, 0))
    return (x if dst.is_wide_decimal else x[..., 0]), over


class _DecimalArithmetic(BinaryExpression):
    """``+ - *`` with Spark 3.x's decimal result types: operands typed by
    :func:`_decimal_operand`, the result by ``types.decimal_*_type`` up to
    precision 38 with the precision-loss adjustment, a value past the
    result's precision NULL (raised under ANSI).  Non-decimal operands
    keep the plain path."""

    def decimal_operands(self) -> Optional[Tuple[DataType, DataType]]:
        l, r = self.children
        if not (l.dtype.is_decimal or r.dtype.is_decimal):
            return None
        dl, dr = _decimal_operand(l), _decimal_operand(r)
        return None if dl is None or dr is None else (dl, dr)

    def _resolve(self):
        super()._resolve()
        ops = self.decimal_operands()
        if ops is not None:
            self.dtype = self._decimal_type(*ops)
            self.nullable = self.nullable or self.may_overflow()

    def may_overflow(self) -> bool:
        """Whether a value can pass the result's precision: only where the
        adjustment cut the precision Spark's rule asked for."""
        raw = self._decimal_raw_precision(*self.decimal_operands())
        return raw > T.MAX_DECIMAL_PRECISION

    def _null_on_overflow(self, ctx, data, valid, over):
        if over is None:
            return data, valid
        if ctx.ansi:
            ctx.record_error(over, valid)
        return data, _and_valid(valid, ~over)


class _DecimalAddSub(_DecimalArithmetic):
    input_sig = TypeSig.device_compute + TypeSig.decimal128
    output_sig = TypeSig.device_compute + TypeSig.decimal128

    def _decimal_type(self, dl, dr):
        return T.decimal_add_type(dl, dr)

    @staticmethod
    def _decimal_raw_precision(dl, dr):
        s = max(dl.scale, dr.scale)
        return s + max(dl.precision - dl.scale, dr.precision - dr.scale) + 1

    def eval(self, ctx):
        ops = self.decimal_operands()
        if ops is None:
            ld, rd, v = self._eval_children_promoted(ctx)
            if getattr(self._operand_type(), "is_wide_decimal", False):
                from .ops import wide_decimal as _wd
                return getattr(_wd, self.wide_op)(ld, rd), v
            return self.narrow_op(ld, rd), v
        # Spark 3.x casts both operands to the result type, then adds
        (l, r), res = self.children, self.dtype
        (ld, lv), (rd, rv) = l.eval(ctx), r.eval(ctx)
        ld, lo = rescale_decimal(ld, ops[0], res, l.dtype.is_wide_decimal)
        rd, ro = rescale_decimal(rd, ops[1], res, r.dtype.is_wide_decimal)
        over = lo if ro is None else (ro if lo is None else lo | ro)
        if not res.is_wide_decimal:
            return self._null_on_overflow(
                ctx, self.narrow_op(ld, rd), _and_valid(lv, rv), over)
        from .ops import wide_decimal as _wd
        out = getattr(_wd, self.wide_op)(ld, rd)
        if self.may_overflow():
            rd_ = rd if type(self) is Add else _wd.neg(rd)
            bad = _wd.add_overflows(ld, rd_, out) \
                | ~_wd.fits(out, res.precision)
            over = bad if over is None else over | bad
        return self._null_on_overflow(ctx, out, _and_valid(lv, rv), over)

    def _operand_type(self) -> DataType:
        if self.decimal_operands() is not None:
            return self.dtype
        return super()._operand_type()


class Add(_DecimalAddSub):
    symbol = "+"
    narrow_op = staticmethod(lambda a, b: a + b)
    wide_op = "add"


class Subtract(_DecimalAddSub):
    symbol = "-"
    narrow_op = staticmethod(lambda a, b: a - b)
    wide_op = "sub"


class Multiply(_DecimalArithmetic):
    symbol = "*"
    input_sig = TypeSig.device_compute + TypeSig.decimal128
    output_sig = TypeSig.device_compute + TypeSig.decimal128

    def _decimal_type(self, dl, dr):
        return T.decimal_multiply_type(dl, dr)

    @staticmethod
    def _decimal_raw_precision(dl, dr):
        return dl.precision + dr.precision + 1

    def eval(self, ctx):
        ops = self.decimal_operands()
        if ops is None:
            ld, rd, v = self._eval_children_promoted(ctx)
            return ld * rd, v
        # operands at their OWN scales: the exact product has scale
        # s1 + s2, rounded HALF_UP to the result's where the adjustment
        # dropped digits
        (l, r), res = self.children, self.dtype
        (ld, lv), (rd, rv) = l.eval(ctx), r.eval(ctx)
        valid = _and_valid(lv, rv)
        if not res.is_wide_decimal:  # p1 + p2 + 1 <= 18: exact in int64
            return ld.astype(jnp.int64) * rd.astype(jnp.int64), valid
        from .ops import wide_decimal as _wd
        prod, over = _wd.multiply(
            ld if l.dtype.is_wide_decimal else ld.astype(jnp.int64),
            rd if r.dtype.is_wide_decimal else rd.astype(jnp.int64),
            ops[0].scale + ops[1].scale - res.scale)
        if not self.may_overflow():
            return prod, valid  # |a * b| < 10^(p1 + p2): always fits
        return self._null_on_overflow(
            ctx, prod, valid, over | ~_wd.fits(prod, res.precision))


def wide_decimal_outputs(exprs) -> int:
    """The distinct computed nodes (no column, literal or alias, no
    aggregate) whose value is a wide decimal, over the expression trees
    of one program: what ``QueryStats.wide_decimal_slots`` counts a slot
    each of, per batch."""
    seen = set()

    def walk(e):
        if e is None:
            return
        if (getattr(e.dtype, "is_wide_decimal", False) and e.children
                and not isinstance(e, (Alias, AggregateExpression))):
            seen.add(e.fingerprint())
        for c in e.children:
            walk(c)
    for e in exprs:
        walk(e)
    return len(seen)


def _round_div(x: jax.Array, d: int) -> jax.Array:
    """Integer division rounding half away from zero (Spark decimal rounding)."""
    sign = jnp.where(x >= 0, 1, -1)
    return sign * ((jnp.abs(x) + d // 2) // d)


class Divide(BinaryExpression):
    """Spark ``/``: always floating (double) for non-decimal; null on /0."""
    symbol = "/"

    def _result_type(self, lt, rt):
        if lt.is_decimal or rt.is_decimal:
            return T.FLOAT64  # decimal division → double for now (planner notes it)
        return T.FLOAT64

    def _operand_type(self):
        return T.FLOAT64

    def eval(self, ctx):
        ld, rd, v = self._eval_children_promoted(ctx)
        zero = rd == 0
        if ctx.ansi:
            # ANSI: division by zero raises instead of nulling
            ctx.record_error(zero, v)
        out = ld / jnp.where(zero, 1.0, rd)
        valid = _and_valid(v, ~zero)
        return out, valid


class IntegralDivide(BinaryExpression):
    symbol = "div"

    def _result_type(self, lt, rt):
        return T.INT64

    def _operand_type(self):
        return T.INT64

    def eval(self, ctx):
        ld, rd, v = self._eval_children_promoted(ctx)
        zero = rd == 0
        safe = jnp.where(zero, 1, rd)
        q = jnp.sign(ld) * jnp.sign(safe) * (jnp.abs(ld) // jnp.abs(safe))
        return q.astype(jnp.int64), _and_valid(v, ~zero)


class Remainder(BinaryExpression):
    """Spark ``%``: sign follows the dividend (C semantics), null on %0."""
    symbol = "%"

    def eval(self, ctx):
        ld, rd, v = self._eval_children_promoted(ctx)
        zero = rd == 0
        safe = jnp.where(zero, 1, rd)
        r = jnp.sign(ld) * (jnp.abs(ld) % jnp.abs(safe))
        return r.astype(ld.dtype), _and_valid(v, ~zero)


class Pmod(BinaryExpression):
    symbol = "pmod"

    def eval(self, ctx):
        ld, rd, v = self._eval_children_promoted(ctx)
        zero = rd == 0
        safe = jnp.where(zero, 1, rd)
        r = jnp.mod(ld, jnp.abs(safe))
        return r.astype(ld.dtype), _and_valid(v, ~zero)


class UnaryMinus(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)
        self.dtype = child.dtype
        self.nullable = child.nullable

    def eval(self, ctx):
        d, v = self.children[0].eval(ctx)
        return -d, v


class Abs(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)
        self.dtype = child.dtype
        self.nullable = child.nullable

    def eval(self, ctx):
        d, v = self.children[0].eval(ctx)
        return jnp.abs(d), v


# ---------------------------------------------------------------------------------
# Comparisons & boolean logic (reference: predicates.scala)
# ---------------------------------------------------------------------------------

class BinaryComparison(BinaryExpression):
    op: Callable = None
    wide_op: str = None  # wide_decimal function name (limb comparisons)
    input_sig = TypeSig.device_compute + TypeSig.decimal128
    output_sig = TypeSig.BOOLEAN

    def _result_type(self, lt, rt):
        T.common_type(lt, rt)  # raises on incomparable
        return T.BOOLEAN

    def _operand_type(self):
        return T.common_type(self.children[0].dtype, self.children[1].dtype)

    def eval(self, ctx):
        ld, rd, v = self._eval_children_promoted(ctx)
        if getattr(self._operand_type(), "is_wide_decimal", False):
            from .ops import wide_decimal as _wd
            name = type(self).wide_op
            if name is None:
                raise TypeError(
                    f"{type(self).__name__} unsupported for decimal128")
            return getattr(_wd, name)(ld, rd), v
        return type(self).op(ld, rd), v


class EqualTo(BinaryComparison):
    symbol = "="
    op = staticmethod(lambda a, b: a == b)
    wide_op = "eq"


class LessThan(BinaryComparison):
    symbol = "<"
    op = staticmethod(lambda a, b: a < b)
    wide_op = "lt"


class LessThanOrEqual(BinaryComparison):
    symbol = "<="
    op = staticmethod(lambda a, b: a <= b)
    wide_op = "le"


class GreaterThan(BinaryComparison):
    symbol = ">"
    op = staticmethod(lambda a, b: a > b)
    wide_op = "gt"


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="
    op = staticmethod(lambda a, b: a >= b)
    wide_op = "ge"


class EqualNullSafe(BinaryExpression):
    """``<=>``: nulls compare equal; never returns null."""
    symbol = "<=>"

    def _resolve(self):
        super()._resolve()
        self.dtype = T.BOOLEAN
        self.nullable = False

    def _result_type(self, lt, rt):
        return T.BOOLEAN

    def eval(self, ctx):
        l, r = self.children
        ld, lv = l.eval(ctx)
        rd, rv = r.eval(ctx)
        ct = T.common_type(l.dtype, r.dtype).numpy_dtype
        ld, rd = ld.astype(ct), rd.astype(ct)
        ln = jnp.zeros_like(ld, dtype=bool) if lv is None else ~lv
        rn = jnp.zeros_like(rd, dtype=bool) if rv is None else ~rv
        eq = (ld == rd) & ~ln & ~rn
        return eq | (ln & rn), None


class Not(Expression):
    input_sig = TypeSig.BOOLEAN + TypeSig.null
    output_sig = TypeSig.BOOLEAN

    def __init__(self, child: Expression):
        self.children = (child,)
        self.dtype = T.BOOLEAN
        self.nullable = child.nullable

    def eval(self, ctx):
        d, v = self.children[0].eval(ctx)
        return ~d, v


class And(BinaryExpression):
    """Kleene AND: F&null=F (predicates.scala GpuAnd)."""
    symbol = "and"
    input_sig = TypeSig.BOOLEAN + TypeSig.null
    output_sig = TypeSig.BOOLEAN

    def _result_type(self, lt, rt):
        return T.BOOLEAN

    def eval(self, ctx):
        ld, lv = self.children[0].eval(ctx)
        rd, rv = self.children[1].eval(ctx)
        data = ld & rd
        if lv is None and rv is None:
            return data, None
        lt = ld if lv is None else (ld & lv)   # definitely-true
        rt_ = rd if rv is None else (rd & rv)
        lf = (~ld) if lv is None else ((~ld) & lv)  # definitely-false
        rf = (~rd) if rv is None else ((~rd) & rv)
        valid = lf | rf | (lt & rt_)
        return lt & rt_, valid


class Or(BinaryExpression):
    symbol = "or"
    input_sig = TypeSig.BOOLEAN + TypeSig.null
    output_sig = TypeSig.BOOLEAN

    def _result_type(self, lt, rt):
        return T.BOOLEAN

    def eval(self, ctx):
        ld, lv = self.children[0].eval(ctx)
        rd, rv = self.children[1].eval(ctx)
        if lv is None and rv is None:
            return ld | rd, None
        lt = ld if lv is None else (ld & lv)
        rt_ = rd if rv is None else (rd & rv)
        valid_l = jnp.ones_like(ld) if lv is None else lv
        valid_r = jnp.ones_like(rd) if rv is None else rv
        valid = lt | rt_ | (valid_l & valid_r)
        return lt | rt_, valid


class In(Expression):
    """``col IN (literals...)`` — unrolled OR of equality tests."""

    def __init__(self, child: Expression, values: Sequence[Any]):
        self.children = (child,)
        self.values = tuple(values)
        self.dtype = T.BOOLEAN
        self.nullable = child.nullable or any(v is None for v in values)

    def eval(self, ctx):
        d, v = self.children[0].eval(ctx)
        hit = jnp.zeros((ctx.capacity,), dtype=bool)
        for val in self.values:
            if val is None:
                continue
            lit = Literal(val, self.children[0].dtype).eval(ctx)[0]
            hit = hit | (d == lit)
        valid = v
        if any(x is None for x in self.values):
            # non-matching rows with a null in the list → null
            miss_null = ~hit
            valid = _and_valid(valid, ~miss_null | hit)
        return hit, valid

    def _fp_extra(self):
        return f"{self.values!r}"


class IsNull(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)
        self.dtype = T.BOOLEAN
        self.nullable = False

    def eval(self, ctx):
        _, v = self.children[0].eval(ctx)
        if v is None:
            return jnp.zeros((ctx.capacity,), dtype=bool), None
        return ~v, None


class IsNotNull(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)
        self.dtype = T.BOOLEAN
        self.nullable = False

    def eval(self, ctx):
        _, v = self.children[0].eval(ctx)
        if v is None:
            return jnp.ones((ctx.capacity,), dtype=bool), None
        return v, None


class IsNan(Expression):
    def __init__(self, child: Expression):
        self.children = (child,)
        self.dtype = T.BOOLEAN
        self.nullable = False

    def eval(self, ctx):
        d, v = self.children[0].eval(ctx)
        nan = jnp.isnan(d) if jnp.issubdtype(d.dtype, jnp.floating) else (
            jnp.zeros_like(d, dtype=bool))
        if v is not None:
            nan = nan & v
        return nan, None


# ---------------------------------------------------------------------------------
# Conditionals (reference: conditionalExpressions.scala — note the reference
# does *lazy* side evaluation; under XLA both sides trace and fuse, and
# ``jnp.where`` selects, which is the right model for a vector machine).
# ---------------------------------------------------------------------------------

class If(Expression):
    def __init__(self, pred: Expression, then: Expression, other: Expression):
        self.children = (pred, then, other)
        if then.resolved() and other.resolved():
            self.dtype = T.common_type(then.dtype, other.dtype)
            self.nullable = pred.nullable or then.nullable or other.nullable

    def eval(self, ctx):
        p, pv = self.children[0].eval(ctx)
        td, tv = self.children[1].eval(ctx)
        ed, ev = self.children[2].eval(ctx)
        np_dt = self.dtype.numpy_dtype
        td, ed = td.astype(np_dt), ed.astype(np_dt)
        cond = p if pv is None else (p & pv)  # null predicate → else branch
        data = jnp.where(cond, td, ed)
        if tv is None and ev is None:
            valid = None
        else:
            tvv = tv if tv is not None else jnp.ones_like(cond)
            evv = ev if ev is not None else jnp.ones_like(cond)
            valid = jnp.where(cond, tvv, evv)
        return data, valid


class CaseWhen(Expression):
    def __init__(self, branches: Sequence[Tuple[Expression, Expression]],
                 otherwise: Optional[Expression] = None):
        flat: List[Expression] = []
        for c, v in branches:
            flat += [c, v]
        if otherwise is not None:
            flat.append(otherwise)
        self.branches = list(branches)
        self.otherwise = otherwise
        self.children = tuple(flat)
        vals = [v for _, v in branches] + ([otherwise] if otherwise else [])
        if all(v.resolved() for v in vals):
            dt = vals[0].dtype
            for v in vals[1:]:
                dt = T.common_type(dt, v.dtype)
            self.dtype = dt
            self.nullable = (otherwise is None) or any(v.nullable for v in vals) \
                or any(c.nullable for c, _ in branches)

    def eval(self, ctx):
        np_dt = self.dtype.numpy_dtype
        if self.otherwise is not None:
            data, valid = self.otherwise.eval(ctx)
            data = data.astype(np_dt)
        else:
            data = jnp.zeros((ctx.capacity,), dtype=np_dt)
            valid = jnp.zeros((ctx.capacity,), dtype=bool)
        # Iterate branches last-to-first so the first matching branch wins.
        out_d, out_v = data, valid
        for cond_e, val_e in reversed(self.branches):
            cd, cv = cond_e.eval(ctx)
            c = cd if cv is None else (cd & cv)
            vd, vv = val_e.eval(ctx)
            vd = vd.astype(np_dt)
            out_d = jnp.where(c, vd, out_d)
            if vv is None and out_v is None:
                out_v = None
            else:
                vvv = vv if vv is not None else jnp.ones_like(c)
                ovv = out_v if out_v is not None else jnp.ones_like(c)
                out_v = jnp.where(c, vvv, ovv)
        return out_d, out_v


class Coalesce(Expression):
    def __init__(self, *children: Expression):
        self.children = tuple(children)
        if all(c.resolved() for c in children):
            dt = children[0].dtype
            for c in children[1:]:
                dt = T.common_type(dt, c.dtype)
            self.dtype = dt
            self.nullable = all(c.nullable for c in children)

    def eval(self, ctx):
        np_dt = self.dtype.numpy_dtype
        out_d = jnp.zeros((ctx.capacity,), dtype=np_dt)
        out_v = jnp.zeros((ctx.capacity,), dtype=bool)
        for c in reversed(self.children):
            d, v = c.eval(ctx)
            d = d.astype(np_dt)
            if v is None:
                out_d, out_v = d, jnp.ones((ctx.capacity,), dtype=bool)
            else:
                out_d = jnp.where(v, d, out_d)
                out_v = out_v | v
        return out_d, (None if not self.nullable else out_v)


# ---------------------------------------------------------------------------------
# Aggregates are *declared* here; their device implementation lives in
# ops/groupby.py and the aggregate exec (reference: AggregateFunctions.scala).
# ---------------------------------------------------------------------------------

class AggregateExpression(Expression):
    """Marker base: func name + child; update/merge handled by the agg exec."""

    func: str = "?"

    def __init__(self, child: Optional[Expression]):
        self.children = (child,) if child is not None else ()
        if child is not None and child.resolved():
            self._resolve()

    def _resolve(self):
        c = self.children[0]
        self.dtype = c.dtype
        self.nullable = True

    def _fp_extra(self):
        return f"{self.func}:{self.dtype}"


# ---------------------------------------------------------------------------------
# Binding: resolve UnresolvedColumn against a schema, rebuilding the tree.
# ---------------------------------------------------------------------------------

def bind(expr: Expression, schema) -> Expression:
    """Return a copy of ``expr`` with columns bound to ordinals and types set."""
    from .batch import Schema  # noqa: F401  (typing only)
    if isinstance(expr, UnresolvedColumn):
        idx = schema.index_of(expr.name)
        f = schema.fields[idx]
        return BoundReference(idx, f.dtype, f.nullable, f.name)
    if not expr.children:
        return expr
    new_children = tuple(bind(c, schema) for c in expr.children)
    return _rebuild(expr, new_children)


def _rebuild(expr: Expression, children: Tuple[Expression, ...]) -> Expression:
    import copy
    node = copy.copy(expr)
    node.children = children
    if isinstance(node, Alias):
        node.dtype = children[0].dtype
        node.nullable = children[0].nullable
    elif isinstance(node, BinaryExpression):
        node._resolve()
    elif isinstance(node, (UnaryMinus, Abs)):
        node.dtype = children[0].dtype
        node.nullable = children[0].nullable
    elif isinstance(node, (Not,)):
        node.nullable = children[0].nullable
    elif isinstance(node, If):
        node.dtype = T.common_type(children[1].dtype, children[2].dtype)
        node.nullable = any(c.nullable for c in children)
    elif isinstance(node, CaseWhen):
        n = len(node.branches)
        node.branches = [(children[2 * i], children[2 * i + 1]) for i in range(n)]
        node.otherwise = children[2 * n] if len(children) > 2 * n else None
        vals = [v for _, v in node.branches] + (
            [node.otherwise] if node.otherwise else [])
        dt = vals[0].dtype
        for v in vals[1:]:
            dt = T.common_type(dt, v.dtype)
        node.dtype = dt
        node.nullable = (node.otherwise is None) or any(v.nullable for v in vals)
    elif isinstance(node, Coalesce):
        dt = children[0].dtype
        for c in children[1:]:
            dt = T.common_type(dt, c.dtype)
        node.dtype = dt
        node.nullable = all(c.nullable for c in children)
    elif isinstance(node, AggregateExpression):
        node._resolve()
    elif isinstance(node, (IsNull, IsNotNull, IsNan)):
        pass
    elif isinstance(node, In):
        node.nullable = children[0].nullable or any(
            v is None for v in node.values)
    elif isinstance(node, Cast):
        node.nullable = children[0].nullable or Cast._can_produce_null(
            children[0].dtype, node.dtype)
    elif hasattr(node, "_rebind"):
        node._rebind()
    return node
