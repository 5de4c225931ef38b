"""Fast-path selection audit (VERDICT r4 weak #7).

Runs every TPC-H suite query at the given scale factor and reports
which kernel paths fired, from operator metrics: dense broadcast joins
vs sorted/SMJ kernels, dense (single/multi-key) aggregations, residual
fallbacks, re-partitions, AQE shuffle→broadcast flips.

    JAX_PLATFORMS=cpu python tools/path_audit.py [SF]

(The PATH decisions are identical on the TPU backend; run on CPU for
speed.)  The end-of-round table lives in PERF.md.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.models import tpch_suite

    sf = float(sys.argv[1]) if len(sys.argv) > 1 else 1.0
    sess = srt.Session.get_or_create(settings={
        "spark.rapids.tpu.sql.fileCache.enabled": True})
    paths = tpch_suite.gen_db(sf, os.path.join(
        os.path.dirname(__file__), "..", ".bench_data"))
    print("| query | dense joins | SMJ | dense aggs | residual fb "
          "| agg repart | AQE flips |")
    print("|---|---|---|---|---|---|---|")
    for name in [f"q{i}" for i in range(1, 23)]:
        runner, _ = tpch_suite.QUERIES[name]
        dfs = {t: sess.read_parquet(paths[t])
               for t in tpch_suite.TABLES[name]}
        runner(dfs)
        ctx = sess.last_exec_context()
        tot: dict = {}
        dense_j = smj = 0
        for op, ms in ctx.metrics.items():
            ms._resolve()
            for k, v in ms.values.items():
                tot[k] = tot.get(k, 0) + v
            if "BroadcastJoin" in op or "SortMergeJoin" in op:
                if ms.values.get("numOutputBatches", 0) > 0:
                    dense_j += 1
                elif ms.values.get("numOutputRows", 0) > 0:
                    smj += 1
        print(f"| {name} | {dense_j} | {smj} "
              f"| {int(tot.get('aggDensePath', 0))} "
              f"| {int(tot.get('aggDenseResidualFallback', 0))} "
              f"| {int(tot.get('aggRepartitions', 0))} "
              f"| {int(tot.get('aqeShuffleToBroadcast', 0))} |")


if __name__ == "__main__":
    main()
