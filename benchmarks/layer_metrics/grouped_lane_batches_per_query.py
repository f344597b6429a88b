"""operators: batches a query's grouped aggregates gave to the grouped Pallas lane (``phases.pallas_batches``,
the ``pallasBatches`` counter summed over the plan), mean a query of the window: six a Q1 query when the lane
engages, 0 when every batch fell to the XLA branch. ``None`` where the engine keeps no such phase (a parent
commit)."""

from benchmarks.layer_metrics.groupby_record import phase_count


def read(run):
    return phase_count(run, "pallas_batches")
