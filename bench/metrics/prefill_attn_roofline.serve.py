"""Roofline share of the chunk-prefill attention kernel
(kernels/blockwise_causal_attn.py): its least time for the real rows'
FLOPs and bytes, over its traced device time, in %."""
from bench.trace_reduce import seconds_matching

OPS = ("fused_chunk_prefill_attention",)


def read(rec):
    dev = seconds_matching(rec["trace"]["op_s"], OPS)
    if dev <= 0:
        return None
    return 100.0 * rec["work"]["prefill_kernel_roofline_s"] / dev
