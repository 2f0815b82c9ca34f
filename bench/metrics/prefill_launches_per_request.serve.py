"""Prefill launches (chunk forwards and remainder programs, the
scheduler's `prefill_forwards` counter) per request that has its first
token, both counted from the window's start until the trace stops (the
stall of writing the trace would change how the scheduler packs the
requests due during it)."""


def read(rec):
    n = rec["launches"]["first_tokens"]
    if not n:
        return None
    return rec["launches"]["prefill_forwards"] / n
