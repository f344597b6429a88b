"""Execution-context expressions: monotonically_increasing_id,
spark_partition_id, input_file_name / _block_start / _block_length,
uuid, raise_error, version.

Reference surface (SURVEY §2.5 misc exprs): miscExpressions.scala
(GpuMonotonicallyIncreasingID, GpuSparkPartitionID, GpuRaiseError),
GpuInputFileNameExpression / InputFileBlockRule (§2.2 #14), GpuUuid.

Two evaluation modes, both driven by the enclosing operator
(exec/basic.py Project/Filter):

- TRACED context (monotonically_increasing_id, spark_partition_id):
  the operator passes (row_offset, partition_id) as jit arguments and
  binds the tracers into a thread-local before evaluating the tree, so
  one compiled program serves every batch/partition. Outside any
  binding (e.g. mesh-lowered plans) they read as offset 0 / partition 0.

- EAGER host values (input_file_name/blocks, uuid, raise_error): these
  are nondeterministic or carry per-batch host state (the current scan
  file), so the operator evaluates the WHOLE projection un-jitted for
  batches of such trees — the reference pays an analogous cost by
  forcing the per-file reader via InputFileBlockRule (the planner here
  does the same; see overrides._force_perfile_for_input_file).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax.numpy as jnp
import numpy as np

from .. import __version__
from ..columnar import dtypes as dt
from .core import Expression, Schema, make_result

_CTX = threading.local()


# --- traced per-call context (set by Project/Filter inside jit) -----------

class traced_context:
    """Bind (row_offset, partition_id) tracers for one evaluation."""

    def __init__(self, row_offset, partition_id):
        self.vals = (row_offset, partition_id)

    def __enter__(self):
        self.prev = getattr(_CTX, "traced", None)
        _CTX.traced = self.vals
        return self

    def __exit__(self, *exc):
        _CTX.traced = self.prev


def _traced_vals():
    t = getattr(_CTX, "traced", None)
    if t is None:
        return jnp.int64(0), jnp.int32(0)
    return t


# --- host per-batch file context (set by the scan exec) -------------------

def set_input_file(name: Optional[str], block_start: int = 0,
                   block_length: int = 0) -> None:
    _CTX.input_file = (name, block_start, block_length)


def current_input_file():
    return getattr(_CTX, "input_file", None) or ("", 0, 0)


# --- expressions ----------------------------------------------------------

class MonotonicallyIncreasingID(Expression):
    """(partition_id << 33) | within-partition row position — Spark's
    exact layout (GpuMonotonicallyIncreasingID)."""

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.INT64

    def eval(self, batch):
        offset, pid = _traced_vals()
        idx = jnp.arange(batch.capacity, dtype=jnp.int64) + \
            jnp.int64(offset)
        data = (jnp.int64(pid) << 33) | idx
        return make_result(data, batch.live_mask(), dt.INT64)

    def __repr__(self):
        return "monotonically_increasing_id()"


class SparkPartitionID(Expression):
    def data_type(self, schema: Schema) -> dt.DType:
        return dt.INT32

    def eval(self, batch):
        _, pid = _traced_vals()
        data = jnp.full(batch.capacity, jnp.int32(pid), jnp.int32)
        return make_result(data, batch.live_mask(), dt.INT32)

    def __repr__(self):
        return "spark_partition_id()"


class _EagerExpression(Expression):
    """Marker: must evaluate OUTSIDE jit (host state / nondeterminism)."""


class InputFileName(_EagerExpression):
    """Current scan file path; empty string (never null) when no file
    context exists — Spark's input_file_name contract."""

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.STRING

    def eval(self, batch):
        from ..columnar.vector import column_from_numpy
        name, _, _ = current_input_file()
        cap = batch.capacity
        n = int(batch.num_rows)
        vals = np.array([name] * n + [""] * (cap - n), dtype=object)
        return column_from_numpy(vals, cap, dtype=dt.STRING,
                                 mask=np.arange(cap) < n)

    def __repr__(self):
        return "input_file_name()"


class _InputFileBlock(_EagerExpression):
    slot = 1

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.INT64

    def eval(self, batch):
        v = current_input_file()[self.slot]
        data = jnp.full(batch.capacity, v, jnp.int64)
        return make_result(data, batch.live_mask(), dt.INT64)


class InputFileBlockStart(_InputFileBlock):
    slot = 1

    def __repr__(self):
        return "input_file_block_start()"


class InputFileBlockLength(_InputFileBlock):
    slot = 2

    def __repr__(self):
        return "input_file_block_length()"


class Uuid(_EagerExpression):
    """Random v4 UUID string per row (GpuUuid; nondeterministic, so
    eager-only)."""

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.STRING

    def eval(self, batch):
        import uuid

        from ..columnar.vector import column_from_numpy
        cap = batch.capacity
        n = int(batch.num_rows)
        vals = np.array([str(uuid.uuid4()) for _ in range(n)] +
                        [""] * (cap - n), dtype=object)
        return column_from_numpy(vals, cap, dtype=dt.STRING,
                                 mask=np.arange(cap) < n)

    def __repr__(self):
        return "uuid()"


class RaiseErrorException(RuntimeError):
    pass


class RaiseError(_EagerExpression):
    """raise_error(msg): evaluating any live row throws
    (GpuRaiseError)."""

    def __init__(self, message: str):
        super().__init__()
        self.message = message

    def data_type(self, schema: Schema) -> dt.DType:
        # Spark types raise_error as NullType; STRING keeps every
        # downstream schema path happy and is unobservable (evaluation
        # always throws before a value escapes)
        return dt.STRING

    def eval(self, batch):
        if int(batch.num_rows) > 0:
            raise RaiseErrorException(self.message)
        from ..columnar.vector import column_from_numpy
        return column_from_numpy(np.array([], dtype=object),
                                 batch.capacity, dtype=dt.STRING,
                                 mask=np.zeros(0, bool))

    def __repr__(self):
        return f"raise_error({self.message!r})"


class Version(Expression):
    """version() -> engine version string literal."""

    def data_type(self, schema: Schema) -> dt.DType:
        return dt.STRING

    def eval(self, batch):
        from .core import Literal
        return Literal(f"spark_rapids_tpu {__version__}",
                       dt.STRING).eval(batch)

    def __repr__(self):
        return "version()"


def contains_eager(exprs) -> bool:
    """Does any tree hold an eager-only node? (operators use this to
    skip jit for the batch). ANSI-marked nodes are eager: their
    error guards host-sync and raise (expr/ansi.py)."""
    def walk(e):
        if isinstance(e, _EagerExpression) or getattr(e, "ansi", False):
            return True
        return any(walk(c) for c in e.children)
    return any(walk(e) for e in exprs)


def contains_input_file(exprs) -> bool:
    def walk(e):
        if isinstance(e, (InputFileName, _InputFileBlock)):
            return True
        return any(walk(c) for c in e.children)
    return any(walk(e) for e in exprs)


def fusion_blocked(exprs) -> bool:
    """Expressions a program fused from several operators cannot
    reproduce: eager trees (must evaluate un-jitted so data-dependent
    raises reach the caller) and partition-context expressions (read
    ``ctx.partition_id`` / the input-file TLS through
    ``traced_context``, which a fused program does not thread)."""
    if contains_eager(exprs):
        return True
    ctx_types = (InputFileName, _InputFileBlock, SparkPartitionID,
                 MonotonicallyIncreasingID)

    def walk(e) -> bool:
        if isinstance(e, ctx_types):
            return True
        return any(walk(c) for c in e.children)

    return any(walk(e) for e in exprs)


# --- user-facing constructors ---------------------------------------------

def monotonically_increasing_id() -> MonotonicallyIncreasingID:
    return MonotonicallyIncreasingID()


def spark_partition_id() -> SparkPartitionID:
    return SparkPartitionID()


def input_file_name() -> InputFileName:
    return InputFileName()


def input_file_block_start() -> InputFileBlockStart:
    return InputFileBlockStart()


def input_file_block_length() -> InputFileBlockLength:
    return InputFileBlockLength()


def uuid_expr() -> Uuid:
    return Uuid()


def raise_error(message: str) -> RaiseError:
    return RaiseError(message)


def version() -> Version:
    return Version()
