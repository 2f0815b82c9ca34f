"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on a TPU v5e (bench/testdata:
inside the window, three rounds of one 2048² matmul program, a 20 ms host
sleep annotated `host_sleep`, and a small reduction program)."""
import glob
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_union_and_gaps():
    busy = tr.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.gaps(busy, (-1.0, 5.0)) == [(-1.0, 0.0), (2.0, 3.0),
                                          (4.0, 5.0)]


def test_reduce_on_made_up_events():
    t = tr.Trace(
        window=(0.0, 10.0),
        ops={"/device:TPU:0": [("while", 1.0, 4.0), ("fusion", 1.0, 2.0),
                               ("fused_decode_attention", 2.0, 4.0),
                               ("fusion", 7.0, 8.0), ("fusion", 9.5, 11.0)]},
        modules={"/device:TPU:0": [("jit__lambda", 1.0, 4.0)]},
        host=[("decode_chunk", 0.5, 4.2), ("waiting_for_arrivals", 4.2, 7.0)])
    r = tr.reduce(t)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(3.0 + 1.0 + 0.5)
    # the container `while` adds to busy time, not to time by name
    assert r["op_s"] == {"fusion": pytest.approx(2.5),
                         "fused_decode_attention": 2.0}
    assert r["module_s"] == {"jit__lambda": 3.0}
    assert r["module_n"] == {"jit__lambda": 1}
    assert r["idle_gaps"][0] == ["waiting_for_arrivals", 3.0]
    assert r["idle_gaps"][1][1] == pytest.approx(1.5)
    assert tr.seconds_matching(r["op_s"], ["decode_attention"]) == 2.0


def test_names_are_normalised():
    assert tr.op_name("%fused_chunk_prefill_attention.9 = bf16[4] "
                      "custom-call(...)") == "fused_chunk_prefill_attention"
    assert tr.op_name("%copy-start.28 = (s32[16])") == "copy-start"
    assert tr.module_name("jit__lambda(4653617379863840252)") == \
        "jit__lambda"


def test_recorded_trace():
    paths = glob.glob(os.path.join(DATA, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, "bench/testdata holds no trace"
    r = tr.reduce(tr.load(paths[0]))
    assert 0.06 < r["window_s"] < 1.0
    assert 0.0 < r["busy_s"] < r["window_s"]
    names = set(r["module_s"])
    assert any("lambda" in n for n in names)
    # the three host sleeps are the longest idle gaps, 20 ms or more each
    sleeps = [g for g in r["idle_gaps"] if g[0] == "host_sleep"]
    assert len(sleeps) == 3 and all(g[1] >= 0.019 for g in sleeps)
