"""What the readers of the shipping cell's metrics share: which device programs are the general hash join's
(the HASH path of exec/join.py, named apart from the lookup's), and the least bytes TPC-H Q3's hash join must
read, from the configuration alone.

Program names are the engine's registry labels (``jit_<label>``, ``jit_registry.py``); ``harness/xplane.py``
puts a program's name in front of each of its operations, up to the first ``/``.
"""

import datetime

from benchmarks.configs.tpch_shipping import DATE, SEGMENT
from benchmarks.harness import datagen
from benchmarks.layer_metrics.join_record import programs_device_ms

#: prefixes of the programs only the HASH path runs: a pair alone or with the suffix fused behind it (in Q3 the
#: partial aggregate), the build side's sorted hash index, the bloom filter's build and probe in front of the
#: pairs, and a sub-partitioned build's split and chunk programs. No LOOKUP program starts with one of these
#: (``jit__join_run_builder`` / ``jit__fused_join_builder`` / ``jit__lookup_*`` are the lookup's). The shuffle
#: exchange's partition programs are shared with every other exchange of the plan and are not counted.
HASH_JOIN_PROGRAMS = ("jit__hash_join_run_builder", "jit__fused_hash_join_builder", "jit__hash_index_builder",
                      "jit__bloom_", "jit__bucket_split_builder", "jit__chunk_slice_builder")

#: TPC-H Q3 (clause 2.4.3): the join's probe is lineitem shipped after DATE, its build the orders placed before
#: DATE by a customer of SEGMENT; the columns each side carries through the join
PROBE_COLUMNS = ("l_orderkey", "l_extendedprice", "l_discount")
BUILD_COLUMNS = ("o_orderkey", "o_orderdate", "o_shippriority")


def hash_join_device_ms(run):
    """Milliseconds of device time a traced query spent in ``HASH_JOIN_PROGRAMS``, loops and branches counted
    once: 0.0 where the trace holds none of them, ``None`` without a trace."""
    return programs_device_ms(run, HASH_JOIN_PROGRAMS)


def _date_share(column: dict, after: bool) -> float:
    """The share of a uniform DATE column's days after (or before) ``DATE``."""
    assert column["type"] == "DATE" and column["dist"] == "uniform", column
    day = (DATE - datetime.date(1970, 1, 1)).days
    lo, hi = int(column["lo"]), int(column["hi"])
    return (hi - day if after else day - lo) / (hi - lo + 1)


def _width(columns: dict, names) -> int:
    return sum(datagen.LOGICAL_WIDTH[columns[n]["type"]] for n in names)


def hash_join_bytes(config: dict) -> float:
    """The least bytes Q3's hash join must read a query: the probe rows the WHERE keeps of lineitem and the build
    rows it keeps of orders, each with the columns it carries through the join, at their logical widths. From the
    configuration's table specs alone, whatever implements the join."""
    tables = {name: {c["name"]: c for c in spec["columns"]} for name, spec in config["tables"].items()}
    lineitem, orders, customer = tables["lineitem"], tables["orders"], tables["customer"]
    segment = customer["c_mktsegment"]
    assert segment["dist"] == "choice" and SEGMENT in segment["choices"], segment
    probe_rows = config["tables"]["lineitem"]["rows"] * _date_share(lineitem["l_shipdate"], after=True)
    build_rows = (config["tables"]["orders"]["rows"] * _date_share(orders["o_orderdate"], after=False)
                  / len(segment["choices"]))
    return probe_rows * _width(lineitem, PROBE_COLUMNS) + build_rows * _width(orders, BUILD_COLUMNS)
