"""spark_rapids_tpu — a TPU-native columnar SQL acceleration framework.

A from-scratch rebuild of the capabilities of the RAPIDS Accelerator for
Apache Spark (NVnavkumar/spark-rapids) designed TPU-first: columnar batches
with static capacities living in TPU HBM, SQL operators compiled through
jax.jit/XLA (Pallas for the hot kernels), tiered HBM→host→disk spill with
split-and-retry OOM handling, and shuffle expressed as device-mesh
collectives over ICI/DCN instead of UCX p2p RDMA.

Layer map (mirrors SURVEY.md §1, re-architected for TPU):
  columnar/  — L2 columnar data representation (GpuColumnVector.java equiv)
  expr/      — L4 expression library (~250 exprs in the reference, §2.5)
  ops/       — L4 physical operators (GpuExec equivalents, §2.4)
  plan/      — L3 plan rewrite: DataFrame frontend, tag-then-convert
               overrides, type checks, fallback (GpuOverrides equiv, §2.2)
  memory/    — L1 device/memory mgmt: pool accounting, spill, retry (§2.3)
  parallel/  — L6 shuffle & distributed: mesh partitioning, collectives (§2.7)
  io/        — L5 data sources: parquet/orc/csv/json scans + writers (§2.6)
  models/    — benchmark workloads (TPC-H/TPC-DS pipelines, mortgage ETL)
  utils/     — metrics, tracing, resource management (§5)
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# SQL semantics (Spark bigint/double) require 64-bit lanes; TPU executes
# int64/float64 element-wise ops via 32-bit emulation, and the hot matmul
# paths stay in narrow types regardless.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compile cache: capacity buckets repeat across queries
# and sessions, and one stage program can take minutes to compile for
# the chip. (The reference's equivalent concern is cuDF JIT kernel
# caching.) One rule: where JAX_COMPILATION_CACHE_DIR is set jax reads
# it itself and nothing here names another directory; otherwise the
# cache lives at one fixed path inside the checkout — the path is part
# of what makes a later process find the entries again.
COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")
if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
    _jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
# persist EVERY compile: the engine builds fresh jit wrappers per query
# plan, so the in-memory pjit cache never carries across collect()
# calls — sub-0.5s compiles (most operator kernels) must round-trip the
# disk cache or every query pays full recompilation
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

from . import columnar  # noqa: F401,E402
