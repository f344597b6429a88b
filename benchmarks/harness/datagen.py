"""Seeded table generator: the benchmark's own copy of the repo's datagen
idea (declarative column specs, chunked parquet), with ``--seed`` mixed into
every (table, column, chunk) stream and no per-row Python.

A configuration's JSON file carries the specs (``tables``); this module turns
them into parquet files of ``chunk_rows`` rows. The same seed gives the same
bytes; another seed gives other values in tables of the same sizes, so every
seed is the same amount of work.

A table may name its ``seeded_columns``. Its other columns are then the same
multiset of rows for every seed, in an order the seed draws: dimension tables
and fact keys are fixed data in a deployment, and with them every filter, join
and group of a query has the same size in every run, so each run drives the
same compiled programs; the measures the queries add up still follow the seed.
"""

from __future__ import annotations

import os
import re
import zlib

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

NUMERIC_TYPES = {"INT64": pa.int64(), "INT32": pa.int32(), "FLOAT64": pa.float64()}
#: logical bytes of one value, for the least-bytes count (STRING: the spec's
#: own ``avg_bytes``)
LOGICAL_WIDTH = {"INT64": 8, "INT32": 4, "FLOAT64": 8, "DATE": 4}

_FMT = re.compile(r"^(?P<pre>[^{}]*)\{(?::0(?P<pad>\d+)d)?\}(?P<post>[^{}]*)$")


def _rng(seed: int, table: str, column: str, chunk: int) -> np.random.Generator:
    # crc32, not hash(): hash() of a str changes from process to process
    return np.random.default_rng([seed & 0xFFFFFFFFFFFFFFFF, zlib.crc32(table.encode()),
                                  zlib.crc32(column.encode()), chunk])


def _format_strings(values: np.ndarray, fmt: str | None) -> np.ndarray:
    text = values.astype(np.int64).astype(str)
    if fmt is None:
        return text
    m = _FMT.match(fmt)
    if m is None:
        raise ValueError(f"format {fmt!r}: one '{{}}' or '{{:0Nd}}' field is what the generator knows")
    if m["pad"]:
        text = np.char.zfill(text, int(m["pad"]))
    return np.char.add(np.char.add(m["pre"], text), m["post"])


#: the stream of columns that do not follow the seed, unless the configuration names another (``fixed_seed``)
FIXED_SEED = 0


def sparse_orderkey(index: np.ndarray) -> np.ndarray:
    """dbgen's order keys: the first 8 of every 32 whole numbers, from 1."""
    return index // 8 * 32 + index % 8 + 1


def _calendar(spec: dict, start: int, n: int) -> np.ndarray:
    """One row a day from ``first_day`` (days since 1970-01-01): the day itself or a part of its date."""
    days = np.arange(start, start + n, dtype=np.int64) + spec["first_day"]
    part = spec["part"]
    if part == "day":
        return days
    dates = days.astype("datetime64[D]")
    months = dates.astype("datetime64[M]")
    if part == "year":
        return dates.astype("datetime64[Y]").astype(np.int64) + 1970
    if part == "month":
        return months.astype(np.int64) % 12 + 1
    if part == "dom":
        return (dates - months).astype(np.int64) + 1
    if part == "quarter":
        return months.astype(np.int64) % 12 // 3 + 1
    if part == "dow":
        return (days + 4) % 7  # 1970-01-01 was a Thursday; 0 is Sunday
    raise ValueError(f"calendar part {part!r}")


def _order_lines(spec: dict, rng: np.random.Generator, start: int, n: int) -> np.ndarray:
    """dbgen's l_orderkey: rows [start, start + n) are the lines of the orders that fall to them, one to
    seven lines an order (the counts drawn, then moved by ones until they add up to ``n``), order by order."""
    rows, orders = spec["rows"], spec["orders"]
    first, last = start * orders // rows, (start + n) * orders // rows
    counts = rng.integers(1, 8, last - first)
    while (short := n - int(counts.sum())):
        room = np.flatnonzero(counts < 7 if short > 0 else counts > 1)
        counts[rng.choice(room, min(abs(short), len(room)), replace=False)] += 1 if short > 0 else -1
    return sparse_orderkey(np.repeat(np.arange(first, last, dtype=np.int64), counts))


def generate_column(spec: dict, seed: int, table: str, chunk: int, start: int, n: int,
                    order: np.ndarray | None = None) -> pa.Array:
    """One chunk of one column. ``order``, where given, permutes the chunk's rows."""
    # two columns that name one ``stream`` draw the same numbers: a brand's name follows its id
    rng = _rng(seed, table, spec.get("stream", spec["name"]), chunk)
    kind, dist = spec["type"], spec["dist"]
    integral = kind in ("INT64", "INT32", "DATE", "STRING")
    if dist == "seq":
        values = np.arange(start, start + n, dtype=np.int64) + spec.get("first", 0)
    elif dist == "sparse_orderkey":
        values = sparse_orderkey(np.arange(start, start + n, dtype=np.int64))
    elif dist == "calendar":
        values = _calendar(spec, start, n)
    elif dist == "order_lines":
        values = _order_lines(spec, rng, start, n)
    elif dist == "uniform":
        if integral or spec.get("whole"):  # ``whole``: a float column of whole numbers, as l_quantity is
            values = rng.integers(int(spec["lo"]), int(spec["hi"]) + 1, n)
        else:
            values = rng.uniform(spec["lo"], spec["hi"], n)
    elif dist == "normal":
        values = rng.normal(spec["mean"], spec["std"], n)
    elif dist == "zipf":
        values = (rng.zipf(spec["alpha"], n) - 1) % spec["cardinality"]
    elif dist == "skip_every":
        # dbgen's o_custkey: uniform over 1..hi without the multiples of ``every``
        per = spec["every"] - 1
        draw = rng.integers(0, spec["hi"] // spec["every"] * per + spec["hi"] % spec["every"], n)
        values = draw + draw // per + 1
    elif dist == "choice":
        values = rng.integers(0, len(spec["choices"]), n)
        if kind != "STRING":
            values = np.asarray(spec["choices"])[values]
    else:
        raise ValueError(f"{table}.{spec['name']}: unknown distribution {dist!r}")
    # the null draw comes after the values, so a column's values do not depend on its null share
    mask = rng.random(n) < spec["null_prob"] if spec.get("null_prob") else None
    if order is not None:
        values, mask = values[order], (mask[order] if mask is not None else None)
    if kind == "STRING":
        if dist == "choice":
            return pa.DictionaryArray.from_arrays(values.astype(np.int32), pa.array(spec["choices"], pa.string()),
                                                  mask=mask).cast(pa.string())
        return pa.array(_format_strings(values, spec.get("fmt")), type=pa.string(), mask=mask)
    if kind == "DATE":
        return pa.array(values.astype(np.int32), type=pa.int32(), mask=mask).cast(pa.date32())
    return pa.array(values.astype(NUMERIC_TYPES[kind].to_pandas_dtype()), type=NUMERIC_TYPES[kind], mask=mask)


def generate_table(name: str, spec: dict, seed: int, out_dir: str, chunk_rows: int,
                   fixed_seed: int = FIXED_SEED) -> int:
    """Writes ``out_dir/<name>-<chunk>.parquet``; returns the bytes written."""
    os.makedirs(out_dir, exist_ok=True)
    rows, written = spec["rows"], 0
    for chunk in range(-(-rows // chunk_rows)):
        start = chunk * chunk_rows
        n = min(chunk_rows, rows - start)
        seeded = spec.get("seeded_columns")
        order = None if seeded is None else _rng(seed, name, "", chunk).permutation(n)
        arrays = [generate_column(c, seed, name, chunk, start, n) if seeded is None or c["name"] in seeded
                  else generate_column(c, fixed_seed, name, chunk, start, n, order) for c in spec["columns"]]
        path = os.path.join(out_dir, f"{name}-{chunk:05d}.parquet")
        pq.write_table(pa.table(arrays, names=[c["name"] for c in spec["columns"]]), path)
        written += os.path.getsize(path)
    return written


def generate(config: dict, tables: list[str], seed: int, data_dir: str) -> tuple[dict, int]:
    """Generates the named tables of a configuration; returns their directories and bytes written."""
    paths, written = {}, 0
    for name in tables:
        paths[name] = os.path.join(data_dir, name)
        written += generate_table(name, config["tables"][name], seed, paths[name], config["chunk_rows"],
                                  config.get("fixed_seed", FIXED_SEED))
    return paths, written


def logical_bytes(config: dict, qid: str) -> int:
    """The least bytes query ``qid`` has to read: rows of each table it scans times the logical
    width of each column it references. From the configuration alone, whatever implements the query."""
    total = 0
    for table, columns in config["queries"][qid]["scans"].items():
        spec = config["tables"][table]
        by_name = {c["name"]: c for c in spec["columns"]}
        for name in columns:
            c = by_name[name]
            total += spec["rows"] * (c["avg_bytes"] if c["type"] == "STRING" else LOGICAL_WIDTH[c["type"]])
    return total


def fact_rows(config: dict, qid: str) -> int:
    q = config["queries"][qid]
    return config["tables"][q["fact"]]["rows"]
