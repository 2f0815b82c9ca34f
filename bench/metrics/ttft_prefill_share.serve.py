"""Share of the time to first streamed token spent from admission to the
first token's sampling (prefill launches with other rows' decode chunks
between them): over the requests whose `request_queued` and
`request_first_streamed` marks both lie in the traced window,
Σ(first_token − admitted) / Σ(first_streamed − queued), in %. The rest
after this share and the queue share is the wait from sampling the first
token to streaming it."""
from bench import program_trace


def read(rec):
    p = program_trace.ttft_parts(rec)
    return None if p is None else 100.0 * p["prefill"] / p["total"]
