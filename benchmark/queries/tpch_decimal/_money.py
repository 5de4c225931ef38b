"""The plain reference's view of ``lineitem`` with DECIMAL(15,2) money: each
column the queries read, converted once per table and shared by every
(query, parameter set) of a run.

Money is exact int64 cents with a validity mask, dates are numpy days,
the two flags are small codes.  The reference computes in int64 only
where :func:`int64_safe` proves that no product or sum can pass 2^63, and
in python ints otherwise; it never goes through the engine or through a
float.
"""

from __future__ import annotations

import decimal

import numpy as np

SCALE = 2                 # DECIMAL(15,2): the money columns' scale
MONEY = {"l_quantity", "l_extendedprice", "l_discount", "l_tax"}
DATES = {"l_shipdate"}
_CACHE = {}               # id(table) -> (table, {column: converted})
_EXACT = decimal.Context(prec=80)  # the default 28 digits would round


def _convert(name, series):
    """(values, valid): cents for money, days for a date, and for a flag
    its codes with the strings they stand for."""
    if name in MONEY:
        valid = np.array([x is not None for x in series], dtype=bool)
        cents = np.array([int(x.scaleb(SCALE, context=_EXACT))
                          if x is not None else 0 for x in series],
                         dtype=np.int64)
        return cents, valid
    if name in DATES:
        return (series.to_numpy(dtype="datetime64[D]"),
                series.notna().to_numpy())
    codes, uniques = series.factorize()
    return (codes, list(uniques)), codes >= 0


def columns(pdf, names):
    """{name: (values, valid)} for the table ``pdf``, converted once."""
    key = id(pdf)
    if key not in _CACHE or _CACHE[key][0] is not pdf:
        _CACHE[key] = (pdf, {})
    done = _CACHE[key][1]
    for name in names:
        if name not in done:
            done[name] = _convert(name, pdf[name])
    return {name: done[name] for name in names}


def int64_safe(*bounds: int, rows: int) -> bool:
    """Whether a product of factors bounded by ``bounds``, summed over
    ``rows`` rows, stays below 2^63."""
    total = rows
    for b in bounds:
        total *= b
    return total < 2 ** 63


def exact(values: np.ndarray, *bounds: int) -> np.ndarray:
    """``values`` as int64 where the bound holds, else python ints."""
    return values if int64_safe(*bounds, rows=len(values)) \
        else values.astype(object)


def at_scale(total, scale: int):
    """A python int of units of 10^-scale -> the Decimal Spark returns."""
    return decimal.Decimal(f"{int(total)}E-{scale}")


def spark_avg(total: int, count: int, scale: int, result_scale: int,
              divide_scale: int):
    """Spark's Average of a decimal: the sum (``total`` units of 10^-scale)
    over the count, HALF_UP to Divide's scale, then HALF_UP to the
    result's; None for an empty group."""
    if count == 0:
        return None
    ctx = decimal.Context(prec=80, rounding=decimal.ROUND_HALF_UP)
    q = ctx.divide(at_scale(total, scale), decimal.Decimal(count))
    q = q.quantize(decimal.Decimal(1).scaleb(-divide_scale), context=ctx)
    return q.quantize(decimal.Decimal(1).scaleb(-result_scale), context=ctx)
