"""SF1-class NDS datapoint: store_sales ~3M rows, the
heaviest proven query shapes, honest wall-clock + peak-RSS record.

BASELINE config 2 is an SF100 power run; the differential proof runs at
~SF0.03 (100k store_sales). This tool takes the first step up the scale
ladder: ~SF1 data volume (3M store_sales rows, dimensions scaled by the
same generator), executing on the backend jax gives this process — the
chip where there is one, the CPU under JAX_PLATFORMS=cpu — with
"backend" in the record either way. One process owns the chip: start
nothing else that touches jax beside it.

Usage: python tools/sf1_bench.py [scale_rows] [out.json]
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time


#: the heaviest shapes the 100k differential proof covers: multi-join
#: aggregates, rollup, windows, set-ops, correlated subqueries
HEAVY = ["q4", "q11", "q14", "q23", "q31", "q33", "q47", "q56",
         "q74", "q78"]


def main():
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    scale = int(sys.argv[1]) if len(sys.argv) > 1 else 3_000_000
    out_path = sys.argv[2] if len(sys.argv) > 2 else "SF1.json"
    import jax
    from spark_rapids_tpu.conf import SrtConf
    from spark_rapids_tpu.models.nds import NDS_QUERIES, register_nds
    from spark_rapids_tpu.plan.session import TpuSession

    backend = jax.default_backend()
    sess = TpuSession(SrtConf({"srt.shuffle.partitions": 4}))
    t0 = time.time()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    register_nds(sess, os.path.join(root, ".bench_cache",
                                    f"nds_sf1_{scale}"),
                 scale_rows=scale)
    gen_s = round(time.time() - t0, 1)
    per = {}
    rec = {"scale_rows": scale, "backend": backend,
           "datagen_s": gen_s, "per_query_s": per}

    def persist():
        rec["peak_rss_gb"] = round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20, 2)
        rec["total_s"] = round(time.time() - t0, 1)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)

    persist()
    for qid in HEAVY:
        tq = time.time()
        try:
            n = len(sess.sql(NDS_QUERIES[qid]).collect())
            per[qid] = {"s": round(time.time() - tq, 1), "rows": n}
        except Exception as e:
            per[qid] = {"s": round(time.time() - tq, 1),
                        "error": f"{type(e).__name__}: {e}"[:160]}
        print(f"{qid}: {per[qid]}", flush=True)
        persist()
    print(json.dumps({k: rec[k] for k in
                      ("scale_rows", "backend", "datagen_s", "total_s",
                       "peak_rss_gb")}))


if __name__ == "__main__":
    main()
