"""Generate exec: explode/posexplode over list columns.

Rebuild of GpuGenerateExec.scala (SURVEY §2.4 Expand/Generate row): one
output row per array element, with the generating row's columns
replicated. The kernel (ops/kernels.py explode_batch) reports the true
required output size; on overflow the exec re-runs at the reported
size's capacity bucket — the same grow-and-retry contract the join
execs use instead of cuDF's dynamic allocations.
"""

from __future__ import annotations

from typing import Iterator, Optional

import jax

from ..columnar import dtypes as dt
from ..columnar.vector import ColumnarBatch, choose_capacity
from ..expr.collections import Explode
from ..jit_registry import read_int, shared_fn_jit
from ..ops import kernels as K
from .base import ExecContext, Metric, Schema, TpuExec

_MAX_GROWTH_STEPS = 4


def _explode_builder(generator, element_name, pos_name, out_cap):
    def run(batch: ColumnarBatch):
        lc = generator.children[0].eval(batch)
        return K.explode_batch(batch, lc, element_name, out_cap,
                               outer=generator.outer, pos_name=pos_name)
    return run


class GenerateExec(TpuExec):
    def __init__(self, child: TpuExec, generator: Explode,
                 element_name: str, pos_name: Optional[str] = None):
        super().__init__(child)
        self.generator = generator
        self.element_name = element_name
        self.pos_name = pos_name if generator.with_position else None
        in_schema = child.output_schema
        elem_t = generator.data_type(in_schema)
        self._schema = list(in_schema)
        if self.pos_name:
            self._schema.append((self.pos_name, dt.INT32))
        self._schema.append((element_name, elem_t))
        self._jit_cache = {}

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def _fn(self, out_cap: int):
        if out_cap not in self._jit_cache:
            from ..expr.misc import contains_eager
            if contains_eager([self.generator]):
                self._jit_cache[out_cap] = _explode_builder(
                    self.generator, self.element_name, self.pos_name,
                    out_cap)
            else:
                self._jit_cache[out_cap] = shared_fn_jit(
                    _explode_builder, self.generator, self.element_name,
                    self.pos_name, out_cap)
        return self._jit_cache[out_cap]

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        m = ctx.metrics_for(self.exec_id)
        retries = m.setdefault("generateOverflowRetries",
                               Metric("generateOverflowRetries",
                                      Metric.DEBUG))
        for batch in self.children[0].execute(ctx):
            if read_int(batch.num_rows) == 0:
                continue
            out_cap = choose_capacity(max(batch.capacity, 16))
            for _ in range(_MAX_GROWTH_STEPS + 1):
                with ctx.semaphore:
                    out, total = self._fn(out_cap)(batch)
                total = read_int(total)
                if total <= out_cap:
                    break
                retries.add(1)
                out_cap = choose_capacity(total)
            else:
                raise RuntimeError(
                    f"explode expansion {total} exceeded capacity after "
                    f"{_MAX_GROWTH_STEPS} growth steps")
            yield out

    def node_description(self) -> str:
        kind = "posexplode" if self.pos_name else "explode"
        outer = "_outer" if self.generator.outer else ""
        return f"Generate[{kind}{outer} -> {self.element_name}]"
