"""The program's own annotations in a traced serving window.

While a profiler session captures, the program's telemetry writes its
scheduler's request lifecycle as zero-length annotations (marks)
`request_<event>` with the request's `rid` among their arguments, and a
`scheduler_round` mark at the top of each scheduler round with the
`waiting`, `prefilling` and `decoding` counts. They share the device
trace's clock. The serving runner's own annotations, `decode_chunk` and
`waiting_for_arrivals`, sit on the same host plane.

The runner keeps the cell's trace under `harness.WORK_DIR/trace-<cell>`
until the per-layer readers have run. `events(rec)` takes the newest
`.xplane.pb` there whose `bench_window` lasts `rec["trace"]["window_s"]`
and loads its host events with their arguments once. A trace from a
program without these marks reads as nothing (`None`), not as zero.
"""
from __future__ import annotations

import glob
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench import harness, trace_reduce
from bench.cells.serve import WAIT

ROUND = "scheduler_round"
DECODE = "decode_chunk"
REQUEST = "request_"

Event = Tuple[str, float, float, Dict]     # name, start s, end s, args

_loaded: Dict[Tuple, Tuple] = {}


def _kept(name: str) -> bool:
    return name.startswith(REQUEST) or name in (ROUND, DECODE, WAIT)


def _load(path: str) -> Tuple[Optional[Tuple[float, float]], List[Event]]:
    """The window and the kept host events of one trace, read once."""
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _loaded:
        from jax.profiler import ProfileData
        window, events = None, []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    s, t = e.start_ns * 1e-9, e.end_ns * 1e-9
                    if e.name == trace_reduce.WINDOW:
                        window = (s, t)
                    elif _kept(e.name):
                        events.append((e.name, s, t, dict(e.stats)))
        events.sort(key=lambda ev: ev[1])
        _loaded[key] = (window, events)
    return _loaded[key]


def events(rec: Dict) -> Optional[List[Event]]:
    """The kept events that start inside the traced window of `rec`, or
    None where no trace with that window is found."""
    want = rec.get("trace", {}).get("window_s")
    if not want:
        return None
    paths = glob.glob(os.path.join(harness.WORK_DIR, "trace-*", "**",
                                   "*.xplane.pb"), recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        window, evs = _load(path)
        if window is None or not math.isclose(window[1] - window[0], want,
                                              rel_tol=0, abs_tol=1e-6):
            continue
        lo, hi = window
        return [ev for ev in evs if lo <= ev[1] <= hi]
    return None


def ttft_parts(rec: Dict) -> Optional[Dict[str, float]]:
    """Over the requests whose `queued` and `first_streamed` marks both
    lie in the window: the seconds summed from queued to admitted
    (`queue`), admitted to first token sampled (`prefill`), and queued to
    first token streamed (`total`)."""
    evs = events(rec)
    if evs is None:
        return None
    first: Dict[Tuple, float] = {}
    for name, s, _, args in evs:
        if name.startswith(REQUEST):
            first.setdefault((args.get("rid"), name[len(REQUEST):]), s)
    out = {"queue": 0.0, "prefill": 0.0, "total": 0.0}
    for (rid, ev), queued in first.items():
        if ev != "queued":
            continue
        t = [first.get((rid, e)) for e in
             ("admitted", "first_token", "first_streamed")]
        if None in t:
            continue
        admitted, sampled, streamed = t
        out["queue"] += admitted - queued
        out["prefill"] += sampled - admitted
        out["total"] += streamed - queued
    return out if out["total"] > 0 else None


def _overlap(intervals: Sequence[Tuple[float, float]], lo: float,
             hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def decode_stall_share(rec: Dict) -> Optional[float]:
    """From each `scheduler_round` mark with decoding rows to the next
    mark, the share of the time outside `decode_chunk` spans, in %. Time
    the runner spends waiting for arrivals (the pool empty) is left out."""
    evs = events(rec)
    if evs is None:
        return None
    rounds = [(s, args) for name, s, _, args in evs if name == ROUND]
    decode = trace_reduce.union([(s, e) for n, s, e, _ in evs if n == DECODE])
    wait = trace_reduce.union([(s, e) for n, s, e, _ in evs if n == WAIT])
    total = stalled = 0.0
    for (t0, args), (t1, _) in zip(rounds, rounds[1:]):
        if int(args.get("decoding", 0)) <= 0:
            continue
        span = t1 - t0 - _overlap(wait, t0, t1)
        total += span
        stalled += span - _overlap(decode, t0, t1)
    return 100.0 * stalled / total if total > 0 else None
