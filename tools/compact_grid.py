#!/usr/bin/env python3
"""Time batch compaction's forms over a grid of shapes, on the device.

    python3 tools/compact_grid.py [--out chiprun_out/compact_grid.jsonl]
        [--caps 17,20,22,24] [--ratios=-14,-10,-6,-3,-1,0] [--arrays 1,8]

The prices in ``ops/batch_utils._compact_form`` came from this grid on one
TPU v5e (PERF.md section 6, PR 27); run it again before trusting them on
another chip.  It times the two programs ``compact`` can choose
(``batch_utils.COMPACT_FORMS``) and, as the yardstick they replaced, one
``cap``-long scatter per array.  One JSON line per (cap, new_cap, arrays,
form): milliseconds a call (host clock around ``block_until_ready``, best
of the repeats) and whether the output equals the per-array scatter's.
``new_cap`` is ``cap * 2**ratio``, at least 1024.  1 array is one int32
column; 8 are int32, int64, float64, float64, each with validity.

    python3 tools/compact_grid.py --grid dense
        [--out chiprun_out/dense_grid.jsonl] [--caps 18,21]
        [--shares 0.0001,0.005,0.1,0.75] [--shifts 6]

The dense aggregation's update itself (``ops/dense_agg.update_tables``:
one float64 sum and one int64 residual key, so three 64-bit scatters and
three 8-bit ones into 4,194,304-slot tables), over batches of which a
given share is bound for the tables: the bare scatter (``full``: the
rule pinned to no rung), the program as it ships (``rule``: the rung of
``batch_utils.scatter_rung`` behind its ``lax.cond``) and, to price a
rung the rule does not offer, ``cap >> shift`` pinned for each of
``--shifts``: run it again before trusting the rule's prices on another
chip.  One JSON line per (cap, share, path): milliseconds a call, the
rung, the branch the program takes at that share, and whether the
tables equal the bare scatter's bit for bit.
"""
import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import spark_rapids_tpu  # noqa: E402,F401  (64-bit on before jax is used)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from spark_rapids_tpu.ops import batch_utils, dense_agg  # noqa: E402

SCATTER_EACH = "scatter_each"


def scatter_each(cap, new_cap):
    """The form PR 27 replaced: every array scattered by destination."""
    def f(cols, sel, num_rows):
        active = (jnp.arange(cap, dtype=jnp.int32) < num_rows) & sel
        dest = jnp.cumsum(active.astype(jnp.int32)) - 1
        idx = jnp.where(active, dest, new_cap)
        return tuple(
            tuple(None if a is None else jnp.zeros(
                (new_cap,), a.dtype).at[idx].set(a, mode="drop")
                for a in dv) for dv in cols)
    return jax.jit(f)


def make_cols(rng, cap, n_arrays):
    if n_arrays == 1:
        return ((jnp.asarray(rng.integers(0, 1 << 30, cap, dtype=np.int32)),
                 None),)
    cols = []
    for dt in (np.int32, np.int64, np.float64, np.float64)[: n_arrays // 2]:
        x = rng.integers(0, 1 << 30, cap) if np.issubdtype(dt, np.integer) \
            else rng.random(cap)
        cols.append((jnp.asarray(x.astype(dt)),
                     jnp.asarray(rng.random(cap) < 0.9)))
    return tuple(cols)


def timed(fn, args, min_s, max_reps=20):
    out = jax.block_until_ready(fn(*args))
    best, spent, reps = float("inf"), 0.0, 0
    while reps < max_reps and (spent < min_s or reps < 2):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        best, spent, reps = min(best, dt), spent + dt, reps + 1
    return out, best, reps


DENSE_SLOTS = 1 << 22


def dense_grid(a, fh):
    """``--grid dense``: the dense update, full path against each rung."""
    d = DENSE_SLOTS
    rng = np.random.default_rng(30)

    def tables():
        return ((jnp.zeros((d,), jnp.float64),),
                ((dense_agg.empty_table("min", d, np.int64),
                  dense_agg.empty_table("max", d, np.int64),
                  jnp.ones((d,), jnp.int8), jnp.zeros((d,), jnp.int8)),),
                jnp.zeros((d,), jnp.int8))

    def build():
        # a function of its own each time: jit keeps one trace a function
        def f(sidx, in_dom, cd, rd, accs, res, present):
            return dense_agg.update_tables(
                sidx, in_dom, [(cd, None)], [(rd, None)], accs, ("sum",),
                res, present)
        return jax.jit(f)

    for cap in (1 << int(c) for c in a.caps.split(",")):
        rule = batch_utils.scatter_rung(cap, 3, 3)
        paths = [("full", None), ("rule", rule)] + [
            (f"rung:{cap >> k}", cap >> k)
            for k in map(int, a.shifts.split(",")) if cap >> k != rule]
        slot = rng.integers(0, d, cap).astype(np.int64)
        cd = jnp.asarray(rng.random(cap))
        rd = jnp.asarray(slot * 3)
        # one program a path: the shares of a capacity share its compile
        fns = {}
        for path, rung in paths:
            with mock.patch.object(batch_utils, "scatter_rung",
                                   lambda *_, rung=rung: rung):
                fns[path] = build().lower(
                    jnp.asarray(slot), jnp.zeros((cap,), bool), cd, rd,
                    *tables()).compile()
        for share in map(float, a.shares.split(",")):
            n_live = int(cap * share)
            in_dom = np.zeros(cap, bool)
            in_dom[rng.choice(cap, n_live, replace=False)] = True
            args = (jnp.asarray(np.where(in_dom, slot, d)),
                    jnp.asarray(in_dom), cd, rd) + tables()
            ref = None
            for path, rung in paths:
                out, best, reps = timed(fns[path], args, a.min_s)
                flat = jax.tree_util.tree_leaves(out[:3])
                ref = ref or flat
                rec = {"grid": "dense", "cap": cap, "slots": d,
                       "share": share, "n_live": n_live, "path": path,
                       "rung": rung,
                       "takes": "rung" if int(out[3]) else "full",
                       "ms": round(best * 1e3, 4), "reps": reps,
                       "equal": all(bool(jnp.array_equal(x, y))
                                    for x, y in zip(ref, flat)),
                       "device": jax.devices()[0].device_kind}
                print(json.dumps(rec), flush=True)
                fh.write(json.dumps(rec) + "\n")
                if not rec["equal"]:
                    sys.exit(f"{path} differs from the full path")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--grid", choices=("compact", "dense"),
                    default="compact")
    ap.add_argument("--out")
    ap.add_argument("--shares", default="0.0001,0.005,0.1,0.75")
    ap.add_argument("--caps", help="log2 of the capacities; default "
                    "17,20,22,24 (compact), 18,21 (dense)")
    ap.add_argument("--shifts", default="6", help="dense: rungs to pin, "
                    "as cap >> shift, besides the rule's own")
    ap.add_argument("--ratios", default="-14,-10,-6,-3,-1,0")
    ap.add_argument("--arrays", default="1,8")
    ap.add_argument("--min-s", type=float, default=0.2)
    a = ap.parse_args()
    a.out = a.out or f"chiprun_out/{a.grid}_grid.jsonl"
    a.caps = a.caps or {"compact": "17,20,22,24", "dense": "18,21"}[a.grid]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    if a.grid == "dense":
        with open(a.out, "w") as fh:
            dense_grid(a, fh)
        return
    forms = (SCATTER_EACH,) + batch_utils.COMPACT_FORMS
    rng = np.random.default_rng(27)
    with open(a.out, "w") as fh:
        for cap in (1 << int(c) for c in a.caps.split(",")):
            for n_arrays in map(int, a.arrays.split(",")):
                cols = make_cols(rng, cap, n_arrays)
                spec = tuple(("d", d.dtype.name, v is not None, ())
                             for d, v in cols)
                points = sorted({max(1024, cap >> -int(r))
                                 for r in a.ratios.split(",")})
                sel0 = jnp.zeros((cap,), bool)

                def build(new_cap, form):
                    fn = scatter_each(cap, new_cap) if form == SCATTER_EACH \
                        else batch_utils._compact_program(
                            form, cap, new_cap, spec, True).call
                    return fn.lower(cols, sel0, np.int32(0)).compile()

                with ThreadPoolExecutor(8) as pool:  # compile side by side
                    progs = {(nc, form): pool.submit(build, nc, form)
                             for nc in points for form in forms}
                    progs = {k: f.result() for k, f in progs.items()}
                for new_cap in points:
                    num_rows = cap - cap // 16      # a padded tail too
                    mask = np.zeros(cap, bool)
                    mask[rng.choice(num_rows, (3 * new_cap) // 4,
                                    replace=False)] = True
                    mask[num_rows:] = True          # must stay out
                    args = (cols, jnp.asarray(mask), np.int32(num_rows))
                    ref = None
                    for form in forms:
                        out, best, reps = timed(progs[(new_cap, form)], args,
                                                a.min_s)
                        flat = [x for dv in out for x in dv if x is not None]
                        ref = ref or flat
                        rec = {"cap": cap, "new_cap": new_cap,
                               "arrays": n_arrays, "form": form,
                               "chosen": form == batch_utils._compact_form(
                                   cap, new_cap),
                               "ms": round(best * 1e3, 4), "reps": reps,
                               "equal": all(bool(jnp.array_equal(
                                   x, y, equal_nan=True))
                                   for x, y in zip(ref, flat)),
                               "device": jax.devices()[0].device_kind}
                        print(json.dumps(rec), flush=True)
                        fh.write(json.dumps(rec) + "\n")
                        if not rec["equal"]:
                            sys.exit(f"{form} differs from {SCATTER_EACH}")


if __name__ == "__main__":
    main()
