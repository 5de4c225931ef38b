"""Ends a run that hangs, with the name of the phase it hung in.

Copied from ``chip_smoke.py``: a compile that hangs cannot be interrupted
from Python, so the process exits from this thread, with no result line.
"""

from __future__ import annotations

import contextlib
import faulthandler
import os
import sys
import threading
import time


class Watchdog(threading.Thread):
    def __init__(self, budget_s: float):
        super().__init__(daemon=True, name="benchmark-watchdog")
        self._lock = threading.Lock()
        self._budget = budget_s
        self._end = time.monotonic() + budget_s
        self._phase = None  # (name, limit_s, deadline)

    @contextlib.contextmanager
    def phase(self, name: str, limit_s: float):
        with self._lock:
            self._phase = (name, limit_s,
                           min(self._end, time.monotonic() + limit_s))
        try:
            yield
        finally:
            with self._lock:
                self._phase = None

    def run(self) -> None:
        while True:
            time.sleep(0.5)
            with self._lock:
                ph = self._phase
            if ph is not None and time.monotonic() > ph[2]:
                print(f"benchmark: phase {ph[0]} passed its deadline "
                      f"({ph[1]:.0f} s, run budget {self._budget:.0f} s): "
                      f"no result", file=sys.stderr, flush=True)
                faulthandler.dump_traceback(file=sys.stderr)
                os._exit(4)
