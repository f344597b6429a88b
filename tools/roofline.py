"""Per-kernel roofline table (VERDICT r3 #10): measure achieved GB/s
against the backend's measured copy peak for the hot kernels, print a
markdown table + one JSON line. Runs on the backend jax gives this
process and names it in the output; a CPU run's numbers are emulation
numbers, never device numbers.

Usage: python tools/roofline.py [rows]
"""

import json
import sys
import time

import numpy as np


def main() -> None:
    import spark_rapids_tpu  # noqa: F401 (enables x64)
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import kernels as K
    from spark_rapids_tpu.ops.pallas_kernels import (tile_group_reduce,
                                                     tile_reduce)
    from spark_rapids_tpu.columnar.vector import (ColumnVector,
                                                  ColumnarBatch,
                                                  compaction_indices)
    from spark_rapids_tpu.columnar import dtypes as dt

    backend = jax.default_backend()
    # interpret-mode pallas on the CPU lane is python-per-tile slow;
    # keep the documentation run small there
    default_n = (1 << 22) if backend == "tpu" else (1 << 19)
    n = int(sys.argv[1]) if len(sys.argv) > 1 else default_n
    rng = np.random.default_rng(0)

    def bench(fn, *args, iters=3):
        r = fn(*args)
        jax.block_until_ready(r)
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            r = fn(*args)
            jax.block_until_ready(r)
            best = min(best, time.perf_counter() - t0)
        return best

    # measured copy peak: the roofline denominator
    big = jnp.asarray(rng.random(n))
    peak_s = bench(jax.jit(lambda x: x + 1.0), big)
    peak_gbs = 2 * n * 8 / peak_s / 1e9

    f1 = jnp.asarray(rng.random(n))
    f2 = jnp.asarray(rng.random(n))
    i32 = jnp.asarray(rng.integers(0, 1000, n).astype(np.int32))
    gid = jnp.asarray(rng.integers(0, 40, n).astype(np.int32))
    live = jnp.asarray(np.ones(n, bool))

    rows = []

    def add(name, seconds, nbytes):
        gbs = nbytes / seconds / 1e9
        rows.append({"kernel": name, "bytes": nbytes,
                     "seconds": round(seconds, 5),
                     "gb_s": round(gbs, 2),
                     "pct_peak": round(100 * gbs / peak_gbs, 1)})

    # 1. pallas fused filter+sum (tile_reduce): 3 f64 in, scalars out
    def q6_like(blocks):
        a, b, m = blocks
        keep = (a > 0.2) & (b < 0.8) & m
        return [jnp.where(keep, a * b, 0.0),
                jnp.where(keep, 1.0, 0.0)]
    t = bench(lambda: tile_reduce([f1, f2, live], q6_like,
                                  ["sum", "sum"]))
    add("pallas tile_reduce (filter+2 sums)", t, 2 * n * 8 + n)

    # 2. pallas grouped one-hot matmul sum
    t = bench(lambda: tile_group_reduce(gid, [f1, f2]))
    add("pallas tile_group_reduce (2 cols, B=1024)", t,
        2 * n * 8 + n * 4)

    # 3. hash-claim grouping prelude (XLA)
    kb = ColumnarBatch([ColumnVector(i32, live, dt.INT32),
                        ColumnVector(f1, live, dt.FLOAT64)],
                       ["k", "v"], n)
    fn = jax.jit(lambda b: K._prelude_fast(
        b, [b.column("k")])[1][3])
    t = bench(fn, kb)
    add("hash-claim group prelude (1 int key)", t, n * 4 * 4)

    # 4. compaction (filter) via cumsum+scatter
    keep = jnp.asarray(rng.random(n) < 0.5)
    t = bench(jax.jit(compaction_indices), keep)
    add("compaction_indices", t, n * (1 + 4 + 4))

    # 5. sort (the exact-path fallback's core primitive)
    t = bench(jax.jit(lambda x: jnp.argsort(x, stable=True)), i32)
    add("stable argsort int32", t, n * 8)

    # 6. string repack (gather via scatter-max+cummax)
    from spark_rapids_tpu.columnar.vector import StringColumn
    offs = jnp.arange(n + 1, dtype=jnp.int32) * 4
    chars = jnp.asarray(rng.integers(65, 90, n * 4).astype(np.uint8))
    sc = StringColumn(offs, chars, live, pad_bucket=4)
    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    t = bench(jax.jit(lambda s, p: s.gather(p, live, unique=True).chars),
              sc, perm)
    add("string gather repack (4B rows)", t, 2 * n * 4 + n * 8)

    print(f"\n## Kernel roofline — backend={backend}, "
          f"rows={n}, measured peak {peak_gbs:.1f} GB/s\n")
    print("| kernel | bytes touched | wall | GB/s | % peak |")
    print("|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['kernel']} | {r['bytes']/1e6:.0f} MB | "
              f"{r['seconds']*1e3:.1f} ms | {r['gb_s']} | "
              f"{r['pct_peak']}% |")
    print()
    print(json.dumps({"backend": backend, "rows": n,
                      "peak_gb_s": round(peak_gbs, 1),
                      "kernels": rows}))


if __name__ == "__main__":
    main()
