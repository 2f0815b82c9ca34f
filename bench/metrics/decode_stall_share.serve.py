"""Share of the scheduler rounds that have decoding rows spent outside
the decode chunk (admission, prefill launches, host work): from each
`scheduler_round` mark with `decoding` > 0 to the next, the wall time
outside `decode_chunk` spans, in %, with the runner's waits for arrivals
left out."""
from bench import program_trace


def read(rec):
    return program_trace.decode_stall_share(rec)
