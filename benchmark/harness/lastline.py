"""The one line the driver reads, and the numbers compared on stderr."""

from __future__ import annotations

import json
import sys
from typing import Dict, Optional


def build(correct: bool, attempted: int, failed: int, metrics: Dict,
          device: Dict, compared: Dict,
          breakdown: Optional[Dict] = None) -> Dict:
    """Exactly the contract's keys, ``compared`` last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return line


def emit(line: Dict) -> None:
    """Each number compared beside its limit as the last lines of stderr,
    then the result as the last line of stdout."""
    sys.stdout.flush()
    for name, c in line["compared"].items():
        print(f"compared {name} value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
