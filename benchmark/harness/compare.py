"""The comparison that decides ``correct``.

Every answer the timed path produced is compared with the plain reference's
rows for the same query and parameter set.  Two numbers come out, each held
to a limit the configuration's file states:

- ``answers_wrong``: answers that raised, or whose rows differ in shape, in
  a key, a count, a string or a date (limit 0: exact);
- ``max_rel_err``: the largest relative error of a float cell over all the
  other answers, as ``|got - want| / max(1, |want|)``.

Rows are matched after a canonical sort on their non-float cells (the
queries' group keys are unique), so the order of output is not compared:
two groups whose float sums tie would make an ordered comparison fail sound
runs.  Copied in spirit from ``models/tpch_suite.rows_rel_err``.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

STRUCTURAL = float("inf")


def _norm(row):
    """Numpy scalars as the Python values they hold, so that both sides
    sort and compare alike."""
    return tuple(x.item() if hasattr(x, "item") else x for x in row)


def _key(row):
    exact = tuple("" if isinstance(x, float) else repr(x) for x in row)
    floats = tuple(round(x, 6) for x in row if isinstance(x, float))
    return exact, floats


def rows_rel_err(got, want) -> float:
    """Largest relative error over float cells, or ``STRUCTURAL`` where the
    two row sets differ in anything else."""
    if got is None or len(got) != len(want):
        return STRUCTURAL
    err = 0.0
    for g, w in zip(sorted(map(_norm, got), key=_key),
                    sorted(map(_norm, want), key=_key)):
        if len(g) != len(w):
            return STRUCTURAL
        for a, b in zip(g, w):
            if a is None or b is None:
                if not (a is None and b is None):
                    return STRUCTURAL
            elif isinstance(b, float):
                if isinstance(a, bool) or not isinstance(a, (int, float)) \
                        or a != a:
                    return STRUCTURAL
                err = max(err, abs(float(a) - b) / max(1.0, abs(b)))
            elif a != b:
                return STRUCTURAL
    return err


def judge(answers: Iterable[Tuple[str, int, object]],
          reference: Dict[Tuple[str, int], list],
          limits: Dict[str, float]):
    """``answers``: (query, set index, rows or None).  Returns (correct,
    number failed, {name: {"value", "limit"}}, per-answer errors)."""
    wrong, worst, errs = 0, 0.0, []
    for query, k, rows in answers:
        e = rows_rel_err(rows, reference[(query, k)])
        errs.append(e)
        if e == STRUCTURAL:
            wrong += 1
        else:
            worst = max(worst, e)
    compared = {
        "answers_wrong": {"value": wrong, "limit": limits["answers_wrong"]},
        "max_rel_err": {"value": worst, "limit": limits["max_rel_err"]},
    }
    over = sum(1 for e in errs
               if e == STRUCTURAL or e > limits["max_rel_err"])
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return correct, over, compared, errs


def lower_precision(pds):
    """The control's tables: every float64 column computed in float32, the
    nearest precision below the float64 the configurations state."""
    import numpy as np
    out = {}
    for name, df in pds.items():
        cols = [c for c in df.columns if df[c].dtype == np.float64]
        out[name] = df.astype({c: np.float32 for c in cols})
    return out
