"""operators: milliseconds of device time a traced query spends in the general hash join's programs
(``hash_join_record.HASH_JOIN_PROGRAMS``: the HASH pairs, fused or not, with the partial aggregate fused behind
them; the hash index; the bloom filter in front; a sub-partitioned build's programs); loops and branches counted
once. 0.0 where no hash pair ran."""

from benchmarks.layer_metrics.hash_join_record import hash_join_device_ms


def read(run):
    return hash_join_device_ms(run)
