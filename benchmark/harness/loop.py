"""The load loops.  ``closed``: one client sends its next query when the
last has answered, round-robin over the mix and, within a query, over its
pool of parameter sets."""

from __future__ import annotations

import time
from typing import Callable, Dict, List, NamedTuple, Optional


class Answer(NamedTuple):
    query: str
    set_index: int
    seconds: float
    rows: Optional[list]   # None when the query raised
    error: Optional[str]


def schedule(mix: List[str], pool: int, i: int):
    """The ``i``-th query of the stream and which of its parameter sets."""
    return mix[i % len(mix)], (i // len(mix)) % pool


def closed(run_one: Callable[[str, int], list], mix: List[str], pool: int,
           seconds: Optional[float] = None, cycles: Optional[int] = None,
           start_at: int = 0, clock=time.perf_counter):
    """Drive ``run_one(query, set_index)`` until ``seconds`` are up or
    ``cycles`` passes over the mix are done.  The pass over the mix that is
    in flight at the bell finishes and counts, so that every window holds
    whole passes and a rate over it does not depend on which query the bell
    fell in; the rate divides by the time that really went by.  Returns
    (answers, seconds elapsed, next position in the stream)."""
    if (seconds is None) == (cycles is None):
        raise ValueError("closed loop takes seconds or cycles")
    answers: List[Answer] = []
    i = start_at
    last = None if cycles is None else start_at + cycles * len(mix)
    t0 = clock()
    while ((clock() - t0 < seconds or (i - start_at) % len(mix))
           if last is None else i < last):
        query, k = schedule(mix, pool, i)
        q0 = clock()
        try:
            rows, err = run_one(query, k), None
        except Exception as e:  # a failed query is counted, not fatal
            rows, err = None, f"{type(e).__name__}: {e}"[:300]
        answers.append(Answer(query, k, clock() - q0, rows, err))
        i += 1
    return answers, clock() - t0, i


LOOPS: Dict[str, Callable] = {"closed": closed}


def get(kind: str) -> Callable:
    if kind not in LOOPS:
        raise SystemExit(
            f"benchmark: loop kind {kind!r} has no code yet (known: "
            f"{sorted(LOOPS)}); a cell that needs it brings it")
    return LOOPS[kind]
