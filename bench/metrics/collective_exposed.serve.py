"""Share of the collective operations' device time during which no other
operation runs on that chip (nothing hides the exchange), summed over the
chips, in %. A cell on one chip runs no collective: nothing to read."""


def read(rec):
    t = rec["trace"]
    if t["collective_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["collective_s"]
