"""CPU (numpy/pandas) evaluator for the expression IR.

Mirror of the device lowering in exprs.py, kept in sync by the differential
tests.  Values are (numpy array, valid-mask-or-None) pairs over *dense* rows
(CPU batches are compacted; no capacity padding here).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .. import aggfns as A
from .. import exprs as E
from .. import types as T

Value = Tuple[np.ndarray, Optional[np.ndarray]]


def _and(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


import threading

_ANSI = threading.local()


def set_ansi(enabled: bool) -> None:
    """Set by CpuOpExec around fallback execution: ANSI raises on overflow
    and invalid casts instead of nulling (GpuCast.scala ANSI analog)."""
    _ANSI.enabled = enabled


def ansi_enabled() -> bool:
    return getattr(_ANSI, "enabled", False)


def eval_cpu(expr: E.Expression, arrays, n: int) -> Value:
    """Evaluate a bound expression against dense host columns.

    ``arrays[i]`` is (data, valid) for ordinal i; string columns pass numpy
    object arrays of str/None.
    """
    ev = lambda e: eval_cpu(e, arrays, n)  # noqa: E731

    if isinstance(expr, E.BoundReference):
        return arrays[expr.ordinal]
    if isinstance(expr, E.Literal):
        if expr.value is None:
            return (np.zeros(n, dtype=_np_dtype(expr.dtype)),
                    np.zeros(n, dtype=bool))
        if expr.dtype.is_string:
            return np.array([expr.value] * n, dtype=object), None
        v = E.physical_literal(expr.value, expr.dtype)
        return np.full(n, v, dtype=_np_dtype(expr.dtype)), None
    if isinstance(expr, E.Alias) or type(expr).__name__ == "_AliasMarker":
        return ev(expr.children[0])
    from ..udf import UserDefinedFunction
    if isinstance(expr, UserDefinedFunction):
        child_values = [ev(c) for c in expr.children]
        if expr.device:
            # jax-traceable fn also runs fine eagerly on host arrays
            import jax.numpy as jnp
            datas, valid = [], None
            for (d, v) in child_values:
                datas.append(jnp.asarray(d))
                valid = _and(valid, v)
            out = expr.fn(*datas)
            if isinstance(out, tuple):
                data, fv = out
                valid = _and(valid, None if fv is None else np.asarray(fv))
            else:
                data = out
            return np.asarray(data, dtype=_np_dtype(expr.dtype)), valid
        return expr.eval_rows(child_values, n)
    if isinstance(expr, E.Cast):
        d, v = ev(expr.children[0])
        return _cast_cpu(d, v, expr.children[0].dtype, expr.dtype)

    if isinstance(expr, (E.Add, E.Subtract, E.Multiply)) \
            and expr.decimal_operands() is not None:
        return _decimal_arith_cpu(expr, ev(expr.children[0]),
                                  ev(expr.children[1]))
    if isinstance(expr, (E.Add, E.Subtract, E.Multiply)):
        ld, lv = ev(expr.children[0])
        rd, rv = ev(expr.children[1])
        ld = _promote_cpu(ld, expr.children[0].dtype, expr.dtype)
        rd = _promote_cpu(rd, expr.children[1].dtype, expr.dtype)
        op = {E.Add: np.add, E.Subtract: np.subtract,
              E.Multiply: np.multiply}[type(expr)]
        return op(ld, rd), _and(lv, rv)
    if isinstance(expr, E.Divide):
        ld, lv = ev(expr.children[0])
        rd, rv = ev(expr.children[1])
        ld = _promote_cpu(ld, expr.children[0].dtype, T.FLOAT64)
        rd = _promote_cpu(rd, expr.children[1].dtype, T.FLOAT64)
        zero = rd == 0
        if ansi_enabled():
            live = _and(lv, rv)
            live = np.ones(n, bool) if live is None else np.asarray(live)
            if bool((zero & live).any()):
                raise ArithmeticError("ANSI mode: division by zero")
        out = ld / np.where(zero, 1.0, rd)
        return out, _and(_and(lv, rv), ~zero)
    if isinstance(expr, E.Remainder):
        ld, lv = ev(expr.children[0])
        rd, rv = ev(expr.children[1])
        ct = np.promote_types(ld.dtype, rd.dtype)
        ld, rd = ld.astype(ct), rd.astype(ct)
        zero = rd == 0
        safe = np.where(zero, 1, rd)
        out = np.sign(ld) * (np.abs(ld) % np.abs(safe))
        return out.astype(ct), _and(_and(lv, rv), ~zero)
    if isinstance(expr, E.UnaryMinus):
        d, v = ev(expr.children[0])
        return -d, v
    if isinstance(expr, E.Abs):
        d, v = ev(expr.children[0])
        return np.abs(d), v

    if isinstance(expr, E.EqualNullSafe):
        ld, lv = ev(expr.children[0])
        rd, rv = ev(expr.children[1])
        ln = np.zeros(n, dtype=bool) if lv is None else ~lv
        rn = np.zeros(n, dtype=bool) if rv is None else ~rv
        eq = _compare(ld, rd, np.equal, expr.children[0].dtype,
                      expr.children[1].dtype) & ~ln & ~rn
        return eq | (ln & rn), None
    if isinstance(expr, E.BinaryComparison):
        ld, lv = ev(expr.children[0])
        rd, rv = ev(expr.children[1])
        ops = {E.EqualTo: np.equal, E.LessThan: np.less,
               E.LessThanOrEqual: np.less_equal, E.GreaterThan: np.greater,
               E.GreaterThanOrEqual: np.greater_equal}
        return (_compare(ld, rd, ops[type(expr)], expr.children[0].dtype,
                         expr.children[1].dtype), _and(lv, rv))

    if isinstance(expr, E.Not):
        d, v = ev(expr.children[0])
        return ~d, v
    if isinstance(expr, E.And):
        ld, lv = ev(expr.children[0])
        rd, rv = ev(expr.children[1])
        if lv is None and rv is None:
            return ld & rd, None
        lt = ld if lv is None else (ld & lv)
        rt = rd if rv is None else (rd & rv)
        lf = (~ld) if lv is None else ((~ld) & lv)
        rf = (~rd) if rv is None else ((~rd) & rv)
        return lt & rt, lf | rf | (lt & rt)
    if isinstance(expr, E.Or):
        ld, lv = ev(expr.children[0])
        rd, rv = ev(expr.children[1])
        if lv is None and rv is None:
            return ld | rd, None
        lt = ld if lv is None else (ld & lv)
        rt = rd if rv is None else (rd & rv)
        vl = np.ones(n, dtype=bool) if lv is None else lv
        vr = np.ones(n, dtype=bool) if rv is None else rv
        return lt | rt, lt | rt | (vl & vr)

    if isinstance(expr, E.In):
        d, v = ev(expr.children[0])
        hit = np.zeros(n, dtype=bool)
        for val in expr.values:
            if val is None:
                continue
            hit |= _compare_scalar(d, val, expr.children[0].dtype)
        valid = v
        if any(x is None for x in expr.values):
            valid = _and(valid, hit)
        return hit, valid
    if isinstance(expr, E.IsNull):
        _, v = ev(expr.children[0])
        return (np.zeros(n, dtype=bool) if v is None else ~v), None
    if isinstance(expr, E.IsNotNull):
        _, v = ev(expr.children[0])
        return (np.ones(n, dtype=bool) if v is None else v.copy()), None
    if isinstance(expr, E.IsNan):
        d, v = ev(expr.children[0])
        nan = np.isnan(d) if d.dtype.kind == "f" else np.zeros(n, dtype=bool)
        if v is not None:
            nan &= v
        return nan, None

    if isinstance(expr, E.If):
        p, pv = ev(expr.children[0])
        td, tv = ev(expr.children[1])
        ed, evv = ev(expr.children[2])
        cond = p if pv is None else (p & pv)
        ct = _np_dtype(expr.dtype)
        if not expr.dtype.is_string:
            td, ed = td.astype(ct), ed.astype(ct)
        data = np.where(cond, td, ed)
        if tv is None and evv is None:
            return data, None
        tvv = tv if tv is not None else np.ones(n, dtype=bool)
        eev = evv if evv is not None else np.ones(n, dtype=bool)
        return data, np.where(cond, tvv, eev)
    if isinstance(expr, E.CaseWhen):
        ct = _np_dtype(expr.dtype)
        if expr.otherwise is not None:
            data, valid = ev(expr.otherwise)
            if not expr.dtype.is_string:
                data = data.astype(ct)
        else:
            data = np.zeros(n, dtype=ct if not expr.dtype.is_string else object)
            valid = np.zeros(n, dtype=bool)
        for cond_e, val_e in reversed(expr.branches):
            cd, cv = ev(cond_e)
            c = cd if cv is None else (cd & cv)
            vd, vv = ev(val_e)
            if not expr.dtype.is_string:
                vd = vd.astype(ct)
            data = np.where(c, vd, data)
            vvv = vv if vv is not None else np.ones(n, dtype=bool)
            ovv = valid if valid is not None else np.ones(n, dtype=bool)
            valid = np.where(c, vvv, ovv)
        return data, valid
    if isinstance(expr, E.Coalesce):
        ct = _np_dtype(expr.dtype)
        out_d = np.zeros(n, dtype=ct if not expr.dtype.is_string else object)
        out_v = np.zeros(n, dtype=bool)
        for c in reversed(expr.children):
            d, v = ev(c)
            if not expr.dtype.is_string:
                d = d.astype(ct)
            if v is None:
                out_d, out_v = d, np.ones(n, dtype=bool)
            else:
                out_d = np.where(v, d, out_d)
                out_v = out_v | v
        return out_d, (out_v if expr.nullable else None)

    # string expressions are registered lazily to avoid import cycles
    from . import string_eval
    handler = string_eval.HANDLERS.get(type(expr).__name__)
    if handler is not None:
        return handler(expr, ev, n)

    # math/datetime/string expression libraries carry their own CPU twin
    # (same _eval_impl as the device path, numpy instead of jax.numpy)
    if hasattr(expr, "eval_host"):
        return expr.eval_host(ev, n)

    raise NotImplementedError(f"cpu eval for {type(expr).__name__}")


def _np_dtype(dt: T.DataType):
    if dt.is_string:
        return object
    return dt.numpy_dtype


def _round_div(x: np.ndarray, d: int) -> np.ndarray:
    """Integer division rounding half away from zero (Spark decimal rounding);
    numpy twin of exprs._round_div."""
    sign = np.where(x >= 0, 1, -1)
    return sign * ((np.abs(x) + d // 2) // d)


def _half_up_obj(x: np.ndarray, k: int) -> np.ndarray:
    """Python-int array / 10^k, rounded HALF_UP (exact)."""
    if k <= 0:
        return x * 10 ** -k
    d = 10 ** k
    return np.array([(abs(v) * 2 + d) // (2 * d) * (1 if v >= 0 else -1)
                     for v in x], dtype=object)


def _decimal_arith_cpu(expr, left, right) -> Value:
    """CPU mirror of the device's decimal ``+ - *`` (exprs._DecimalArithmetic):
    exact in python ints, HALF_UP to the result scale, NULL (or the ANSI
    raise) past the result's precision."""
    (ld, lv), (rd, rv) = left, right
    dl, dr = expr.decimal_operands()
    res = expr.dtype
    a, b = ld.astype(object), rd.astype(object)
    if isinstance(expr, E.Multiply):
        out = _half_up_obj(a * b, dl.scale + dr.scale - res.scale)
    else:  # both operands cast to the result type first (Spark 3.x)
        a = _half_up_obj(a, dl.scale - res.scale)
        b = _half_up_obj(b, dr.scale - res.scale)
        out = a + b if isinstance(expr, E.Add) else a - b
    valid = _and(lv, rv)
    over = np.array([abs(v) >= 10 ** res.precision for v in out], dtype=bool)
    if over.any():
        live = np.ones(len(out), bool) if valid is None else valid
        if ansi_enabled() and bool((over & live).any()):
            raise ArithmeticError(
                f"ANSI mode: decimal overflow past {res}")
        out = np.where(over, 0, out)
        valid = ~over if valid is None else (valid & ~over)
    return out.astype(_np_dtype(res) if res.precision <= 18 else object), \
        valid


def _promote_cpu(data: np.ndarray, src: T.DataType, dst: T.DataType) -> np.ndarray:
    """CPU mirror of exprs.promote_physical (decimal scale handling)."""
    np_dt = _np_dtype(dst)
    if src.is_decimal and dst.is_floating:
        return data.astype(np_dt) / 10.0 ** src.scale
    if src.is_decimal and dst.is_decimal:
        if dst.scale == src.scale:
            return data
        if dst.scale > src.scale:
            return data * np.int64(10 ** (dst.scale - src.scale))
        return _round_div(data, 10 ** (src.scale - dst.scale))
    if dst.is_decimal and not src.is_decimal:
        return data.astype(np_dt) * np.int64(10 ** dst.scale)
    return data.astype(np_dt) if data.dtype != np_dt else data


def _compare(ld, rd, op, lt: T.DataType, rt: T.DataType):
    if lt.is_string or rt.is_string:
        lmask = np.array([x is not None for x in ld]) if ld.dtype == object else None
        out = np.zeros(len(ld), dtype=bool)
        for i in range(len(ld)):
            a, b = ld[i], rd[i]
            if a is None or b is None:
                out[i] = False
            else:
                out[i] = bool(op(a, b))
        return out
    ct = T.common_type(lt, rt)
    return op(_promote_cpu(ld, lt, ct), _promote_cpu(rd, rt, ct))


def _compare_scalar(d, val, dt: T.DataType):
    if dt.is_string:
        return np.array([x == val for x in d], dtype=bool)
    return d == E.physical_literal(val, dt)


def n_of(d):
    return len(d)


def _cast_cpu(d, v, src: T.DataType, dst: T.DataType) -> Value:
    if src == dst:
        return d, v
    if dst.is_string:
        from .string_eval import cast_to_string
        return cast_to_string(d, v, src)
    if src.is_string:
        from .string_eval import cast_from_string
        od, ov = cast_from_string(d, v, dst)
        if ansi_enabled():
            before = np.ones(n_of(d), bool) if v is None else np.asarray(v)
            before = before & np.array([x is not None for x in d])
            after = np.ones(n_of(d), bool) if ov is None                 else np.asarray(ov, bool)
            if bool((before & ~after).any()):
                raise ArithmeticError(
                    "ANSI mode: invalid string cast to "
                    f"{dst} (sql.ansi.enabled=true raises)")
        return od, ov
    if ansi_enabled() and src.is_integral and dst.is_integral:
        info = np.iinfo(dst.numpy_dtype)
        live = np.ones(len(d), bool) if v is None else np.asarray(v, bool)
        if bool(((d < info.min) | (d > info.max))[live].any()):
            raise ArithmeticError(
                f"ANSI mode: integer overflow casting to {dst}")
    if dst.kind == T.TypeKind.BOOLEAN and src.is_numeric:
        return d != 0, v
    if src.is_floating and dst.is_integral:
        info = np.iinfo(dst.numpy_dtype)
        if ansi_enabled():
            live = np.ones(len(d), bool) if v is None else np.asarray(v, bool)
            bad = np.isnan(d) | (d < float(info.min)) | (d > float(info.max))
            if bool(bad[live].any()):
                raise ArithmeticError(
                    f"ANSI mode: invalid float cast to {dst}")
        x = np.nan_to_num(d, nan=0.0, posinf=float(info.max),
                          neginf=float(info.min))
        x = np.clip(np.trunc(x), float(info.min), float(info.max))
        return x.astype(dst.numpy_dtype), v
    if src.kind == T.TypeKind.DATE and dst.kind == T.TypeKind.TIMESTAMP:
        return d.astype(np.int64) * 86_400_000_000, v
    if src.kind == T.TypeKind.TIMESTAMP and dst.kind == T.TypeKind.DATE:
        return np.floor_divide(d, 86_400_000_000).astype(np.int32), v
    if src.is_decimal and dst.is_floating:
        return d.astype(dst.numpy_dtype) / 10 ** src.scale, v
    if src.is_integral and dst.is_decimal:
        return d.astype(np.int64) * 10 ** dst.scale, v
    return d.astype(_np_dtype(dst)), v
