"""The three readers of the program's own marks (bench/program_trace.py),
fed a tiny trace recorded here on the CPU with hand-made marks and spans:
their shares against the same sums taken straight from the events; a
trace whose window has another length, and one with no marks (a program
without the profiler sink), read as nothing."""
import glob
import os
import time

import jax
import pytest
from jax.profiler import TraceAnnotation

from bench import harness, trace_reduce
from repro.telemetry.trace import mark

READERS = ("ttft_queue_share.serve", "ttft_prefill_share.serve",
           "decode_stall_share.serve")


def _span(name, seconds):
    with TraceAnnotation(name):
        time.sleep(seconds)


def _served():
    """Two requests streamed inside the window, a third only after it;
    rounds with decoding rows, a prefill launch between decode chunks and
    a wait for arrivals."""
    mark("request_queued", rid=0, tick=0)
    mark("request_queued", rid=1, tick=0)
    mark("scheduler_round", waiting=2, prefilling=0, decoding=0)
    time.sleep(0.01)
    mark("request_admitted", rid=0, tick=0, row=0)
    _span("prefill_chunk_forward", 0.01)
    mark("request_admitted", rid=1, tick=0, row=1)
    _span("prefill_chunk_forward", 0.02)
    mark("request_first_token", rid=0, tick=0)
    mark("request_first_token", rid=1, tick=0)
    _span("decode_chunk", 0.02)
    mark("request_first_streamed", rid=0, tick=1)
    mark("request_first_streamed", rid=1, tick=1)
    mark("scheduler_round", waiting=0, prefilling=0, decoding=2)
    _span("prefill_remainder_forward", 0.01)
    _span("decode_chunk", 0.02)
    mark("scheduler_round", waiting=0, prefilling=0, decoding=2)
    _span("decode_chunk", 0.01)
    _span("waiting_for_arrivals", 0.02)
    mark("scheduler_round", waiting=1, prefilling=0, decoding=0)
    mark("request_queued", rid=2, tick=3)
    time.sleep(0.005)


def _record(work_dir, body, after=None):
    trace_dir = os.path.join(work_dir, "trace-smoke.serve")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        with TraceAnnotation(trace_reduce.WINDOW):
            body()
        if after:
            after()
    path = trace_reduce.find_xplane(trace_dir)
    return path, {"trace": trace_reduce.reduce(trace_reduce.load(path))}


def _events(path):
    """name -> [(start s, end s, args)] over the host plane."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, e.end_ns * 1e-9, dict(e.stats)))
    return {k: sorted(v, key=lambda x: x[0]) for k, v in out.items()}


@pytest.fixture
def work_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    return str(tmp_path)


def _read(rec):
    return {m: harness.load_reader(m)(rec) for m in READERS}


def test_shares_of_hand_made_marks(work_dir):
    path, rec = _record(work_dir, _served, after=lambda: mark(
        "request_first_streamed", rid=2, tick=4))
    ev = _events(path)

    def at(name, rid):
        return next(s for s, _, a in ev[name] if a["rid"] == rid)

    queue = prefill = total = 0.0
    for rid in (0, 1):
        q = at("request_queued", rid)
        queue += at("request_admitted", rid) - q
        prefill += at("request_first_token", rid) - at("request_admitted",
                                                       rid)
        total += at("request_first_streamed", rid) - q
    r0, r1, r2, r3 = (s for s, _, _ in ev["scheduler_round"])
    (d0, d1), (d2, d3), (d4, d5) = ((s, e) for s, e, _ in
                                    ev["decode_chunk"])
    (w0, w1), = ((s, e) for s, e, _ in ev["waiting_for_arrivals"])
    # rounds 1 and 2 have decoding rows; round 1's interval holds the
    # second decode chunk, round 2's the third and the wait
    span = (r2 - r1) + (r3 - r2 - (w1 - w0))
    stall = span - (d3 - d2) - (d5 - d4)

    got = _read(rec)
    assert got["ttft_queue_share.serve"] == pytest.approx(
        100 * queue / total)
    assert got["ttft_prefill_share.serve"] == pytest.approx(
        100 * prefill / total)
    assert got["decode_stall_share.serve"] == pytest.approx(
        100 * stall / span)
    assert got["ttft_queue_share.serve"] + got["ttft_prefill_share.serve"] \
        < 100
    assert 0 < got["decode_stall_share.serve"] < 100


def test_another_window_reads_nothing(work_dir):
    _, rec = _record(work_dir, _served)
    rec["trace"]["window_s"] += 0.5
    assert _read(rec) == {m: None for m in READERS}


def test_a_program_without_marks_reads_nothing(work_dir):
    def unmarked():
        _span("decode_chunk", 0.01)
        _span("prefill_chunk_forward", 0.01)

    _, rec = _record(work_dir, unmarked)
    assert rec["trace"]["window_s"] > 0
    assert _read(rec) == {m: None for m in READERS}
    assert glob.glob(os.path.join(work_dir, "trace-*"))
