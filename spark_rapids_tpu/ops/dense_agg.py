"""The dense (direct-address) aggregation's table update.

A bounded integer group key addresses domain-sized tables directly:
``acc.at[key - kmin].add/min/max``, no sort.  What that costs on one
TPU v5e is the scatter, and a scatter walks every SOURCE row, live or
not, at ten times the price for a 64-bit array (the prices are beside
:func:`batch_utils.scatter_rung`).  A batch behind a selective join
keeps the probe side's capacity and a mask, so :func:`update_tables`
counts the rows bound for the tables and, where that rule's rung
holds them, compacts them on the device first and scatters the rung
alone.  The choice is made inside the program, by ``lax.cond`` on the
count (one conditional a table): no fetch, one algorithm whose best
form depends on a number visible in its input.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import batch_utils
from .groupby import _SENTINELS

__all__ = ["sentinel", "empty_table", "update_tables"]


def sentinel(op: str, dtype):
    """The value a ``min`` / ``max`` table holds where no row came."""
    np_dt = np.dtype(dtype)
    kind = ("f" if np_dt.kind == "f"
            else "b" if np_dt == np.bool_ else "i")
    return np_dt.type(_SENTINELS[op][kind](np_dt))


def empty_table(op: str, slots: int, dtype) -> jax.Array:
    """A table of ``slots`` slots that no row has reached yet: zeros for
    a ``sum``, the sentinel for a ``min`` / ``max``."""
    if op == "sum":
        return jnp.zeros((slots,), dtype=dtype)
    return jnp.full((slots,), sentinel(op, dtype), dtype=dtype)


_SCATTERS = {"sum": lambda at: at.add, "min": lambda at: at.min,
             "max": lambda at: at.max}


def _updates(ops, in_dom, contribs, res_vals, accs, res, present):
    """What each table takes from rows of any length: one ``(table, op,
    data, mask, neutral)`` per table, the row's value being ``data``
    where ``mask`` and ``neutral`` elsewhere, in the order accumulators,
    the residual keys' ``(vmin, vmax, validmin, validmax)``,
    ``present``.  ``contribs`` is a ``(data, valid or None)`` per
    accumulator of ``accs`` (reduced by ``ops``: sum/min/max),
    ``res_vals`` one per residual key."""
    out = []
    for (cd, cv), acc, op in zip(contribs, accs, ops):
        out.append((acc, op, cd, in_dom if cv is None else (in_dom & cv),
                    jnp.zeros((), dtype=acc.dtype) if op == "sum"
                    else sentinel(op, acc.dtype)))
    for (rd, rv), (vmin, vmax, dmn, dmx) in zip(res_vals, res):
        r_ok = in_dom if rv is None else (in_dom & rv)
        v01 = r_ok.astype(jnp.int8)
        out += [(vmin, "min", rd, r_ok, sentinel("min", vmin.dtype)),
                (vmax, "max", rd, r_ok, sentinel("max", vmin.dtype)),
                # validmin over in-domain rows (1 outside so it never
                # spuriously reports a null)
                (dmn, "min", v01, in_dom, jnp.int8(1)),
                (dmx, "max", v01, in_dom, jnp.int8(0))]
    out.append((present, "max", jnp.int8(1), in_dom, jnp.int8(0)))
    return out


def _scatter(table, op, sidx, data, mask, neutral):
    """THE scatter of the dense tables, by rows of any length: into its
    slot ``sidx`` (the table's length: dropped) each row's value."""
    values = jnp.where(mask, jnp.asarray(data).astype(table.dtype), neutral)
    return _SCATTERS[op](table.at[sidx])(values, mode="drop")


def update_tables(sidx, in_dom, contribs, res_vals, accs, ops, res,
                  present):
    """One update of the dense tables by a batch's rows: each table's
    :func:`_scatter` over the ``in_dom`` rows alone where the rung of
    :func:`batch_utils.scatter_rung` holds them, over every row
    otherwise; traced inside a program.

    ``sidx`` is each row's slot (the tables' length for a row that is
    not ``in_dom``), the rest as :func:`_updates` takes them.  Where the
    rows fit the rung, the source row of each of its first live rows is
    found once (stable, so rows reach each slot in the order they would
    have) and the slot, every contribution and residual value with its
    validity are gathered by it.  Each table then has a ``lax.cond`` of
    its own between the two scatters: with all tables behind one
    conditional the TPU compiler left most of them outside the chip's
    fast memory in the full branch, 14.8% slower than the bare scatter
    at 75% live and 48% at 10% (PERF.md section 6, PR 30).  Returns
    ``(accs, res, present, compacted)``: ``compacted`` is an int32 1
    where the rung ran, 0 where the full path did."""
    cap, slots = sidx.shape[0], present.shape[0]
    tables = (accs, res, present)
    jobs = _updates(ops, in_dom, contribs, res_vals, *tables)
    n64 = sum(job[0].dtype.itemsize == 8 for job in jobs)
    rung = batch_utils.scatter_rung(cap, n64, len(jobs) - n64)
    if rung is None:
        new = [_scatter(t, op, sidx, *row) for t, op, *row in jobs]
        compacted = jnp.zeros((), jnp.int32)
    else:
        # the cumulative sum serves the count and the search; made here
        # and not in the branch, where the TPU compiler takes four times
        # as long over the program (PERF.md section 6, PR 30)
        csum = jnp.cumsum(in_dom.astype(jnp.int32))
        fits = csum[-1] <= rung

        def compact():
            src = batch_utils.live_sources(
                batch_utils._compact_form(cap, rung), in_dom, rung, csum)

            def take(x, fill):
                return None if x is None else jnp.take(
                    x, src, mode="fill", fill_value=fill)

            def pairs(vals):
                return tuple((take(d, 0), take(v, False)) for d, v in vals)

            # a slot past the last live row reads no source row: its
            # slot is the tables' length, so every scatter drops it
            return take(sidx, slots), pairs(contribs), pairs(res_vals)

        def idle():
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                jax.eval_shape(compact))

        sidx_r, contribs_r, res_vals_r = jax.lax.cond(fits, compact, idle)
        jobs_r = _updates(ops, sidx_r < slots, contribs_r, res_vals_r,
                          *tables)
        new = [jax.lax.cond(
            fits, lambda t, op=op, row=row_r: _scatter(t, op, sidx_r, *row),
            lambda t, op=op, row=row: _scatter(t, op, sidx, *row), t)
            for (t, op, *row), (_, _, *row_r) in zip(jobs, jobs_r)]
        compacted = fits.astype(jnp.int32)
    n_accs = len(accs)
    new_res = tuple(tuple(new[n_accs + 4 * i:n_accs + 4 * i + 4])
                    for i in range(len(res)))
    return tuple(new[:n_accs]), new_res, new[-1], compacted
