"""ArrowEvalPythonExec: scalar pandas UDFs over Arrow batches.

Rebuild of GpuArrowEvalPythonExec (sql-plugin/.../execution/python/
GpuArrowEvalPythonExec.scala): child batches pass through unchanged
with one appended column per UDF. The UDF argument expressions evaluate
on device (jit-projected), the argument columns cross host<->worker as
Arrow IPC via the pooled worker processes (udf/worker.py), and results
rejoin the device batch at the child's capacity — row alignment holds
because live rows are always the batch prefix."""

from __future__ import annotations

import io
from typing import Iterator, List, Tuple

from ..columnar.vector import ColumnarBatch
from ..jit_registry import named_jit
from .base import ExecContext, Metric, NvtxTimer, Schema, TpuExec


class ArrowEvalPythonExec(TpuExec):
    def __init__(self, child: TpuExec, udfs: List[Tuple["PandasUDF", str]]):
        super().__init__(child)
        self.udfs = list(udfs)
        in_schema = child.output_schema
        self._out_schema = list(in_schema) + \
            [(name, u.return_type) for u, name in self.udfs]

        def project_inputs(batch: ColumnarBatch) -> ColumnarBatch:
            cols, names = [], []
            for i, (u, _) in enumerate(self.udfs):
                for j, ce in enumerate(u.children):
                    cols.append(ce.eval(batch))
                    names.append(f"in{i}_{j}")
            return ColumnarBatch(cols, names, batch.num_rows)

        self._jit_inputs = named_jit(
            project_inputs, "ArrowEvalPythonExec.project_inputs")

    @property
    def output_schema(self) -> Schema:
        return self._out_schema

    def _job_spec(self) -> bytes:
        import pyarrow as pa

        from ..io.arrow_convert import dtype_to_arrow_type
        from ..udf.worker import make_job_spec
        return make_job_spec(
            [(u.fn, len(u.children),
              pa.field(name, dtype_to_arrow_type(u.return_type)))
             for u, name in self.udfs])

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        import pyarrow as pa

        from ..io.arrow_convert import (arrow_to_host_table,
                                        host_table_to_arrow)
        from ..plan.host_table import batch_to_table, table_to_batch
        from ..udf.worker import worker_pool
        m = ctx.metrics_for(self.exec_id)
        udf_time = m.setdefault("pythonUdfTime",
                                Metric("pythonUdfTime", Metric.MODERATE,
                                       "ns"))
        nbatches = m.setdefault("pythonBatches",
                                Metric("pythonBatches", Metric.DEBUG))
        spec = self._job_spec()
        pool = worker_pool()
        names = [n for n, _ in self._out_schema]
        for batch in self.children[0].execute(ctx):
            if int(batch.num_rows) == 0:
                continue
            with ctx.semaphore:
                inputs = self._jit_inputs(batch)
            with NvtxTimer(udf_time, "python.udf"):
                arrow = host_table_to_arrow(batch_to_table(inputs))
                sink = io.BytesIO()
                with pa.ipc.new_stream(sink, arrow.schema) as wr:
                    wr.write_table(arrow)
                out_blob = pool.run_job(spec, sink.getvalue())
                with pa.ipc.open_stream(io.BytesIO(out_blob)) as rd:
                    result = rd.read_all()
            rbatch = table_to_batch(arrow_to_host_table(result),
                                    capacity=batch.capacity)
            nbatches.add(1)
            yield ColumnarBatch(list(batch.columns) + list(rbatch.columns),
                                names, batch.num_rows)

    def node_description(self) -> str:
        fns = ", ".join(getattr(u.fn, "__name__", "<fn>")
                        for u, _ in self.udfs)
        return f"ArrowEvalPython[{fns}]"
