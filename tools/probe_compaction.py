#!/usr/bin/env python3
"""Times a filter's compaction (``ops/kernels.py::compact``) and an exchange's
cut (``parallel/partition.py::cut_batch``) on the attached device, each both
ways: ``gather``, one sort of the positions and then every plane gathered by
the sorted positions (written out here), and ``carry``, the engine's own,
the fixed-width planes carried through the sort as payload
(``columnar/vector.py::sort_rows``: uint32 words and validity bits stacked
into rows of one two-operand sort, float64 rows in a second). Strings are
gathered either way.

    python3 tools/probe_compaction.py                 # every shape below
    python3 tools/probe_compaction.py q3_filter       # the shapes named

One JSON line a shape: for each formulation ``<name>_ms``, the host's
milliseconds a launch (the least of ``--repeats`` trains of ``--train``
launches enqueued back to back), ``<name>_device_ms``, the program's median
time on the device from a profiler trace of one more train (TPU only),
``<name>_first_call_s``, the first call's seconds (its compile, mostly), and
``<name>_equal``, whether it returned the batches ``gather`` returns, bit for
bit. One sort with every plane its own operand (the literal form) is not
here: a 2^20-row sort with eight payload operands compiles in 97 s for a
described v5e, the filter of Q3's lineitem 150 s.
Nothing of the engine or the benchmark imports this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from probe_first_kept import device_ms, time_launches  # noqa: E402
from spark_rapids_tpu.columnar import dtypes as dt  # noqa: E402
from spark_rapids_tpu.columnar.vector import (  # noqa: E402
    ColumnarBatch, column_from_numpy, compaction_indices)
from spark_rapids_tpu.ops import kernels as K  # noqa: E402
from spark_rapids_tpu.parallel.partition import cut_batch  # noqa: E402

#: name -> (capacity, live rows, one row kept in N (0: a cut), column types,
#: partitions of a cut and the capacity of each)
SHAPES = {
    # Q3's lineitem filter: l_shipdate > DATE keeps ~54% of a 2^20-row
    # batch of l_orderkey, l_extendedprice, l_discount, l_shipdate
    "q3_filter": (1 << 20, 1 << 20, 2, "INT64,FLOAT64,FLOAT64,DATE", None),
    # Q3's cut: the filtered lineitem side into 8 partitions of 2^17 slots
    "q3_cut": (1 << 20, 566_000, 0, "INT64,FLOAT64,FLOAT64", (8, 1 << 17)),
    # the star cell's dimension filters: date_dim's d_moy = 11 and item's
    # i_manufact_id = 7, a string column beside the keys
    "star_date": (1 << 18, 73_049, 12, "INT64,INT64,INT64", None),
    "star_item": (1 << 15, 18_000, 100, "INT64,INT64,STRING,INT64", None),
    # every other row of a batch of two keys, a price and a string
    "filter_2e16": (1 << 16, 1 << 16, 2, "INT64,INT64,FLOAT64,STRING",
                    None),
    "filter_2e15": (1 << 15, 1 << 15, 2, "INT64,INT64,FLOAT64,STRING",
                    None),
    # small batches: a dimension's filter beside a string, one of keys
    # alone, and a cut of 8 partitions
    "filter_2e14": (1 << 14, 1 << 14, 2, "INT64,INT64,FLOAT64,STRING",
                    None),
    "filter_2e12": (1 << 12, 1 << 12, 2, "INT64,INT64,FLOAT64,STRING",
                    None),
    "filter_2e10": (1 << 10, 1 << 10, 2, "INT64,INT64,FLOAT64,STRING",
                    None),
    "keys_2e12": (1 << 12, 3_000, 4, "INT64,INT64,INT64", None),
    "cut_2e14": (1 << 14, 1 << 14, 0, "INT64,FLOAT64,FLOAT64", (8, 1 << 12)),
    "cut_2e10": (1 << 10, 1 << 10, 0, "INT64,FLOAT64,FLOAT64", (8, 1 << 8)),
}


def make_batch(rng, cap: int, live: int, types: str) -> ColumnarBatch:
    cols = []
    for name in types.split(","):
        valid = rng.random(live) > 0.02
        if name == "STRING":
            values = np.array([f"brand#{v}" for v in
                               rng.integers(0, 1000, live)], dtype=object)
        elif name == "FLOAT64":
            values = rng.standard_normal(live)
        else:
            values = rng.integers(0, 1 << 30, live)
        cols.append(column_from_numpy(values, cap, getattr(dt, name),
                                      mask=valid))
    return ColumnarBatch(cols, [f"c{i}" for i in range(len(cols))], live)


def gather_compact(b: ColumnarBatch, keep) -> ColumnarBatch:
    """The compaction with every column gathered by the sorted positions."""
    keep = keep & b.live_mask()
    return b.gather(compaction_indices(keep), jnp.sum(keep).astype(jnp.int32),
                    unique=True)


def gather_cut(b: ColumnarBatch, part_ids, n_parts: int, slot: int):
    """The cut with every column of each slice gathered by the sorted
    positions (fixed-width columns only, as the cut shapes hold)."""
    cap = b.capacity
    pid = jnp.where(b.live_mask(), part_ids, n_parts)
    counts = jnp.zeros(n_parts + 1, jnp.int32).at[pid].add(1)[:n_parts]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(counts, dtype=jnp.int32)])
    order = jax.lax.sort(pid * cap + jnp.arange(cap, dtype=jnp.int32),
                         is_stable=False) % cap
    at = jnp.arange(slot, dtype=jnp.int32)
    out = []
    for p in range(n_parts):
        rows = jnp.take(order, jnp.clip(offsets[p] + at, 0, cap - 1))
        n = jnp.minimum(counts[p], slot)
        out.append(ColumnarBatch([c.gather(rows, at < n) for c in b.columns],
                                 b.names, n))
    return out


def programs(cap: int, one_in: int, parts, rng):
    """Formulation -> its program of (batch, keep or partition ids), and
    that second argument, made once: ``gather`` here, ``carry`` the
    engine's own ``compact`` or ``cut_batch``."""
    if one_in:
        arg = jnp.asarray(rng.integers(0, one_in, cap) == 0)
        steps = {"gather": gather_compact, "carry": K.compact}
    else:
        n_parts, slot = parts
        arg = jnp.asarray(rng.integers(0, n_parts, cap).astype(np.int32))
        steps = {"gather": lambda b, p: gather_cut(b, p, n_parts, slot),
                 "carry": lambda b, p: cut_batch(b, p, n_parts,
                                                 (slot,) * n_parts)}
    out = {}
    for name, step in steps.items():
        def fn(b, a, step=step):
            return step(b, a)
        # the trace names a program after its function
        fn.__name__ = f"probe_{name}"
        out[name] = jax.jit(fn)
    return out, arg


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("shapes", nargs="*", help="names from SHAPES")
    ap.add_argument("--train", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=39)
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind}),
          flush=True)
    rng = np.random.default_rng(args.seed)
    for shape in args.shapes or SHAPES:
        cap, live, one_in, types, parts = SHAPES[shape]
        batch = make_batch(rng, cap, live, types)
        jitted, arg = programs(cap, one_in, parts, rng)
        line = {"shape": shape, "cap": cap, "live": live, "types": types}
        want = None
        for name, fn in jitted.items():
            t0 = time.perf_counter()
            got = [np.asarray(x) for x in
                   jax.tree_util.tree_leaves(fn(batch, arg))]
            line[f"{name}_first_call_s"] = round(time.perf_counter() - t0, 2)
            want = got if want is None else want
            line[f"{name}_equal"] = len(got) == len(want) and all(
                x.dtype == y.dtype and x.shape == y.shape
                and x.tobytes() == y.tobytes() for x, y in zip(got, want))
            line[f"{name}_ms"] = round(time_launches(
                lambda a, fn=fn: fn(batch, a), arg, args.train,
                args.repeats), 4)
        if dev.platform == "tpu":
            with tempfile.TemporaryDirectory() as trace_dir:
                with jax.profiler.trace(trace_dir):
                    for fn in jitted.values():
                        jax.block_until_ready(
                            [fn(batch, arg) for _ in range(args.train)])
                spent = device_ms(trace_dir)
            for name in jitted:
                line[f"{name}_device_ms"] = round(
                    spent[f"jit_probe_{name}"], 4)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
