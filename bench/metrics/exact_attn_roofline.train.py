"""Roofline share of the exact-form forward kernels (kernels/
linformer_attn.py attention over k slots and kernels/seq_projection.py):
their least time for the steps' FLOPs and bytes, over their traced device
time, in %."""
from bench.trace_reduce import seconds_matching

OPS = ("fused_seq_projection", "fused_linformer_attention")


def read(rec):
    dev = seconds_matching(rec["trace"]["op_s"], OPS)
    if dev <= 0:
        return None
    return 100.0 * rec["work"]["exact_kernel_roofline_s"] / dev
