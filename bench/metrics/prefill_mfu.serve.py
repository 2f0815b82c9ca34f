"""FLOPs the real (unpadded) prompt tokens need, over the device time of
the prefill-chunk and remainder programs at the chip's peak, in %."""
from bench.trace_reduce import seconds_matching

MODULES = ("jit__pool_prefill_chunk_impl", "jit__pool_prefill_remainder_impl")


def read(rec):
    dev = seconds_matching(rec["trace"]["module_s"], MODULES)
    if dev <= 0:
        return None
    return 100.0 * rec["work"]["prefill_flops"] / (
        dev * rec["peak"]["flops_per_s"])
