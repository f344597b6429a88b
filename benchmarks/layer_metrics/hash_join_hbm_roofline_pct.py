"""kernels: the least time the chip's HBM needs for what TPC-H Q3's hash join must read (``hash_join_record.
hash_join_bytes``: the probe rows lineitem keeps and the build rows orders keeps, with the columns each carries,
at their logical widths, from the configuration alone), as a share of the device time the hash join's programs
took (``hash_join_device_ms``). A share of those programs' own time, so under 100 by construction. Left out where
no hash pair ran."""

from benchmarks.harness import peaks
from benchmarks.layer_metrics.hash_join_record import hash_join_bytes, hash_join_device_ms


def read(run):
    per_query_ms = hash_join_device_ms(run)
    if not per_query_ms:
        return None
    least_ms = hash_join_bytes(run.config) / peaks.peak(run.device_kind, "hbm_bytes_per_s") * 1e3
    return 100.0 * least_ms / per_query_ms
