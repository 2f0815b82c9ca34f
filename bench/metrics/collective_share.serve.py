"""Share of the device time of the decode-chunk, prefill-chunk and
remainder programs spent in collective operations (bench/trace_reduce.py
`COLLECTIVES`), both summed over the chips, in %. A cell on one chip runs
none: nothing to read."""
from bench.trace_reduce import seconds_matching

MODULES = ("jit__lambda", "jit__pool_prefill_chunk_impl",
           "jit__pool_prefill_remainder_impl")


def read(rec):
    t = rec["trace"]
    dev = seconds_matching(t["module_s"], MODULES)
    if t["collective_s"] <= 0 or dev <= 0:
        return None
    return 100.0 * t["collective_s"] / dev
