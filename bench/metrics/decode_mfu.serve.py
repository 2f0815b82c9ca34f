"""Roofline share of the decode chunks: the least time the chip could
take for their live rows' steps (the larger of FLOPs at peak and of the
weights once per step plus each row's filled cache at peak bandwidth),
over the device time of the decode-chunk programs, in %."""
from bench.trace_reduce import seconds_matching

MODULES = ("jit__lambda",)


def read(rec):
    dev = seconds_matching(rec["trace"]["module_s"], MODULES)
    if dev <= 0:
        return None
    return 100.0 * rec["work"]["decode_roofline_s"] / dev
