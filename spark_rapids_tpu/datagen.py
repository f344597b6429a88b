"""Scale-test data generation DSL.

Rebuild of the reference's datagen module (datagen/bigDataGen.scala +
ScaleTestDataGen.scala, SURVEY §2.8): declarative table specs with
per-column distributions, deterministic per-(table, column, chunk)
seeding so any chunk regenerates independently (the property the
reference's big-data gen is built around), chunked parquet output, and
canned TPC-H-shaped tables for benchmarks.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from .columnar import dtypes as dt
from .plan.host_table import HostColumn, HostTable


@dataclass
class ColumnSpec:
    name: str
    dtype: dt.DType
    dist: str = "uniform"     # uniform | normal | zipf | seq | choice
    lo: float = 0
    hi: float = 100
    mean: float = 0.0
    std: float = 1.0
    alpha: float = 1.5        # zipf skew
    cardinality: int = 1000   # zipf/choice key space
    choices: Optional[List] = None
    null_prob: float = 0.0
    fmt: Optional[str] = None  # string format template, {} = value


@dataclass
class TableSpec:
    name: str
    columns: List[ColumnSpec]
    num_rows: int


def _gen_column(spec: ColumnSpec, table: str, chunk: int, start_row: int,
                n: int) -> HostColumn:
    # deterministic per (table, column, chunk): regenerate any chunk
    # without generating its predecessors. crc32, NOT builtin hash() —
    # hash() is randomized per process (PYTHONHASHSEED) and would make
    # distributed/re-run generation inconsistent.
    import zlib
    seed = zlib.crc32(f"{table}\x00{spec.name}\x00{chunk}".encode())
    rng = np.random.default_rng(seed)
    if spec.dist == "seq":
        vals = np.arange(start_row, start_row + n, dtype=np.int64)
    elif spec.dist == "uniform":
        if getattr(spec.dtype, "is_integral", False) or \
                isinstance(spec.dtype, (dt.DateType, dt.TimestampType)):
            vals = rng.integers(int(spec.lo), int(spec.hi) + 1, n)
        else:
            vals = rng.uniform(spec.lo, spec.hi, n)
    elif spec.dist == "normal":
        vals = rng.normal(spec.mean, spec.std, n)
    elif spec.dist == "zipf":
        # bounded zipf over [0, cardinality)
        raw = rng.zipf(spec.alpha, n)
        vals = (raw - 1) % spec.cardinality
    elif spec.dist == "choice":
        idx = rng.integers(0, len(spec.choices), n)
        vals = np.array([spec.choices[i] for i in idx], dtype=object)
    else:
        raise ValueError(spec.dist)

    mask = np.ones(n, bool)
    if spec.null_prob > 0:
        mask = rng.random(n) >= spec.null_prob

    t = spec.dtype
    if t == dt.STRING:
        fmt = spec.fmt or "{}"
        out = np.array([fmt.format(v) for v in vals], dtype=object)
        return HostColumn(out, mask, t)
    phys = np.dtype(t.physical)
    if isinstance(t, dt.DecimalType):
        out = (np.asarray(vals, np.float64) * 10 ** t.scale).astype(
            np.int64)
    else:
        out = np.asarray(vals).astype(phys)
    out = np.where(mask, out, np.zeros(1, phys))
    return HostColumn(out, mask, t)


def generate_chunk(spec: TableSpec, chunk: int,
                   chunk_rows: int) -> HostTable:
    start = chunk * chunk_rows
    n = min(chunk_rows, spec.num_rows - start)
    cols = [_gen_column(c, spec.name, chunk, start, n)
            for c in spec.columns]
    return HostTable(cols, [c.name for c in spec.columns])


def generate_table(session, spec: TableSpec, out_dir: str,
                   chunk_rows: int = 1 << 20) -> List[str]:
    """Write the table as chunked parquet; returns file paths."""
    from .io.arrow_convert import host_table_to_arrow
    import pyarrow.parquet as pq
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    n_chunks = -(-spec.num_rows // chunk_rows)
    for c in range(n_chunks):
        table = generate_chunk(spec, c, chunk_rows)
        path = os.path.join(out_dir, f"{spec.name}-{c:05d}.parquet")
        pq.write_table(host_table_to_arrow(table), path)
        paths.append(path)
    return paths


# --- canned benchmark tables (TPC-H shapes) -----------

def lineitem_spec(scale_rows: int) -> TableSpec:
    """The q6/q1 workhorse table."""
    return TableSpec("lineitem", [
        ColumnSpec("l_orderkey", dt.INT64, "zipf", cardinality=scale_rows // 4 + 1),
        ColumnSpec("l_partkey", dt.INT64, "uniform", lo=1, hi=200_000),
        ColumnSpec("l_quantity", dt.FLOAT64, "uniform", lo=1, hi=50),
        ColumnSpec("l_extendedprice", dt.FLOAT64, "uniform", lo=900,
                   hi=105_000),
        ColumnSpec("l_discount", dt.FLOAT64, "choice",
                   choices=[round(x * 0.01, 2) for x in range(11)]),
        ColumnSpec("l_tax", dt.FLOAT64, "choice",
                   choices=[round(x * 0.01, 2) for x in range(9)]),
        ColumnSpec("l_returnflag", dt.STRING, "choice",
                   choices=["A", "N", "R"]),
        ColumnSpec("l_linestatus", dt.STRING, "choice",
                   choices=["O", "F"]),
        ColumnSpec("l_shipdate", dt.DATE, "uniform", lo=8036, hi=10561),
    ], scale_rows)


def orders_spec(scale_rows: int) -> TableSpec:
    return TableSpec("orders", [
        ColumnSpec("o_orderkey", dt.INT64, "seq"),
        ColumnSpec("o_custkey", dt.INT64, "zipf", cardinality=150_000),
        ColumnSpec("o_totalprice", dt.FLOAT64, "uniform", lo=800,
                   hi=600_000),
        ColumnSpec("o_orderdate", dt.DATE, "uniform", lo=8036, hi=10561),
        ColumnSpec("o_orderpriority", dt.STRING, "choice",
                   choices=["1-URGENT", "2-HIGH", "3-MEDIUM",
                            "4-NOT SPECIFIED", "5-LOW"]),
        ColumnSpec("o_shippriority", dt.INT32, "choice", choices=[0]),
    ], scale_rows)
