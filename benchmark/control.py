#!/usr/bin/env python3
"""The control of a cell's ``correct``, at the cell's own size.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed it makes the cell's data and parameter pools as a run does,
then puts the reference itself in the program's place, computed in the
nearest precision below the one the configuration states (float32 for
float64: ``compare.lower_precision``), and judges its answers against the
float64 reference by the run's own comparison.  The control has to come out
as not correct; its readings are the upper readings the limits in the
configuration's file were set under.  Touches no device: the benchmark's own
runs never run it.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys

import run as bench_run
from harness import compare


def control(workload: str, seed: int, root: str = None):
    cell = bench_run.Cell(workload, root)
    paths = cell.make_data(seed)
    pds = cell.reference_tables(paths)
    low = compare.lower_precision(pds)
    pools = cell.pools(seed)
    want, got = {}, []
    for q in cell.mix:
        for k, p in enumerate(pools[q]):
            want[(q, k)] = cell.queries[q].reference(pds, p)
            got.append((q, k, cell.queries[q].reference(low, p)))
    shutil.rmtree(cell.data_dir, ignore_errors=True)
    correct, failed, compared, errs = compare.judge(
        got, want, cell.config["limits"])
    return {"workload": workload, "seed": seed, "control_correct": correct,
            "failed": failed, "of": len(got), "compared": compared,
            "per_answer": {f"{q}/{k}": e for (q, k, _), e in zip(got, errs)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    ap.add_argument("--root", default=None)
    args = ap.parse_args(argv)
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = control(args.workload, seed, args.root)
        print(json.dumps(rec), flush=True)
        bad += rec["control_correct"]  # a control that passes is the fault
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
