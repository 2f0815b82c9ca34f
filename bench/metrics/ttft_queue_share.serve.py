"""Share of the time to first streamed token spent queued, before a slot
is claimed: over the requests whose `request_queued` and
`request_first_streamed` marks both lie in the traced window,
Σ(admitted − queued) / Σ(first_streamed − queued), in %."""
from bench import program_trace


def read(rec):
    p = program_trace.ttft_parts(rec)
    return None if p is None else 100.0 * p["queue"] / p["total"]
