"""The telemetry's profiler sink (docs/observability.md §Profiler sink):
spans, instants and request-lifecycle stamps reach a JAX profiler trace
while a session captures, read back here with `ProfileData`; with no
session the disabled contract holds. Also pins the names of the serving
engine's programs, which the benchmark's trace readers match."""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.telemetry import NULL_TIMELINES, Telemetry, Tracer
from repro.telemetry.trace import _NULL_SPAN, capturing, encode_arg

LIFECYCLE = ("queued", "admitted", "first_token", "first_streamed",
             "retired")


def _capture(tmp_path, fn):
    """Run `fn` under a profiler session; the host events of its trace
    as (name, start_ns, args), in time order."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = fn()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend((e.name, e.start_ns, dict(e.stats))
                              for e in line.events)
    events.sort(key=lambda e: e[1])
    return out, events


def _named(events, name):
    return [e for e in events if e[0] == name]


class TestDisabledContract:
    def test_disabled_span_is_the_null_span_without_a_session(self):
        assert not capturing()
        tr = Tracer(enabled=False)
        assert tr.span("a", rows=1) is _NULL_SPAN
        assert Telemetry(enabled=False).span("b") is _NULL_SPAN
        NULL_TIMELINES.stamp(0, "queued", 0, priority=0)
        tr.instant("c", tick=1)
        assert tr.events() == []

    def test_disabled_tracer_annotates_while_a_session_captures(
            self, tmp_path):
        tr = Tracer(enabled=False)

        def body():
            assert capturing()
            with tr.span("op", rows=2) as sp:
                assert sp is not _NULL_SPAN
                sp.annotate(tokens=7)
            tr.instant("point", tick=3)

        _, events = _capture(tmp_path, body)
        (op,) = _named(events, "op")
        assert op[2] == {"rows": 2, "tokens": 7}
        assert _named(events, "point")[0][2] == {"tick": 3}
        assert tr.events() == []        # the ring stays behind `enabled`

    def test_enabled_tracer_fills_its_ring_and_the_trace(self, tmp_path):
        tr = Tracer()

        def body():
            with tr.span("op", rows=2):
                pass
            tr.instant("point", tick=3)

        _, events = _capture(tmp_path, body)
        assert [e["name"] for e in tr.chrome_events()] == ["op", "point"]
        assert len(_named(events, "op")) == 1
        assert len(_named(events, "point")) == 1


class TestArgEncoding:
    def test_values_carry_no_annotation_separators(self):
        assert encode_arg([0, 512, 7]) == "0 512 7"
        assert encode_arg(("0:512", "512:16")) == "0:512 512:16"
        for ch in ",#=":
            assert ch not in encode_arg(f"a{ch}b")
        assert encode_arg(None) == "None"

    def test_a_list_round_trips_through_the_stats(self, tmp_path):
        def body():
            with Tracer(enabled=False).span(
                    "op", rids=[4, 9, 11], chunks=["0:512", "512:16"],
                    note="a,b=c#d"):
                pass

        _, events = _capture(tmp_path, body)
        (op,) = _named(events, "op")
        assert op[2]["rids"] == "4 9 11"
        assert op[2]["chunks"] == "0:512 512:16"
        assert op[2]["note"] == "a;b:c;d"


def _smoke_engine(prefill_chunk=32, max_seq=256):
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving import ServingEngine
    cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return ServingEngine(params, cfg, max_seq=max_seq,
                         cache_dtype=jnp.float32, decode_chunk=4,
                         prefill_chunk=prefill_chunk)


def _smoke_requests(n=7):
    from repro.serving import Request
    rng = np.random.default_rng(11)
    return [Request(rid=i, tokens=tuple(int(t) for t in rng.integers(
                        3, 500, int(rng.choice([16, 33, 48, 70])))),
                    max_new_tokens=int(rng.choice([3, 6, 9])))
            for i in range(n)]


class TestSchedulerMarks:
    """A SMOKE-size scheduler under a profiler session, its telemetry
    disabled (the benchmark's case): every request's lifecycle and every
    round reach the trace."""

    @pytest.fixture(scope="class")
    def engine(self):
        return _smoke_engine()

    def _serve(self, engine, tmp_path, telemetry=None):
        from repro.serving import Scheduler

        def body():
            sched = Scheduler(engine, 3, telemetry=telemetry)
            for req in _smoke_requests():
                sched.submit(req)
            streamed = []
            out = sched.run(on_token=lambda rid, tok: streamed.append(rid))
            return sched, out, streamed

        (sched, out, streamed), events = _capture(tmp_path, body)
        return sched, out, streamed, events

    def test_every_request_is_marked_in_order(self, engine, tmp_path):
        sched, out, streamed, events = self._serve(engine, tmp_path)
        assert all(out[r.rid] for r in _smoke_requests()), \
            "each request must stream a token for its lifecycle to finish"
        for req in _smoke_requests():
            mine = [e for e in events if e[0].startswith("request_")
                    and e[2].get("rid") == req.rid]
            order = [e[0][len("request_"):] for e in mine
                     if e[0][len("request_"):] in LIFECYCLE]
            assert order == list(LIFECYCLE), (req.rid, order)
            assert all(e[2]["tick"] >= 0 for e in mine)
        queued = _named(events, "request_queued")
        assert {e[2]["rid"] for e in queued} == set(streamed)

    def test_rounds_carry_their_counts(self, engine, tmp_path):
        sched, _, _, events = self._serve(engine, tmp_path)
        rounds = _named(events, "scheduler_round")
        assert len(rounds) == sched.stats.ticks
        first = rounds[0][2]
        assert first == {"waiting": len(_smoke_requests()), "prefilling": 0,
                         "decoding": 0}
        for _, _, c in rounds:
            assert c["prefilling"] + c["decoding"] <= 3
            assert c["waiting"] >= 0
        assert any(c["decoding"] > 0 for _, _, c in rounds)

    def test_operator_spans_carry_rows(self, engine, tmp_path):
        sched, _, _, events = self._serve(engine, tmp_path)
        decode = _named(events, "decode_chunk")
        assert len(decode) == sched.stats.chunks
        for _, _, a in decode:
            assert len(str(a["rids"]).split()) == a["rows"]
        prefill = _named(events, "prefill_chunk_forward")
        assert prefill
        for _, _, a in prefill:
            chunks = str(a["chunks"]).split()
            assert len(str(a["rids"]).split()) == len(chunks) == a["rows"]
            assert sum(int(c.split(":")[1]) for c in chunks) == a["tokens"]

    def test_an_enabled_facade_gives_one_mark_per_stamp(self, engine,
                                                        tmp_path):
        tel = Telemetry()
        sched, _, _, events = self._serve(engine, tmp_path, telemetry=tel)
        stamps = sum(len(sched.timelines.stamps(r))
                     for r in sched.timelines.rids())
        marks = [e for e in events if e[0].startswith("request_")]
        assert len(marks) == stamps
        ring = [e for e in tel.tracer.chrome_events()
                if e["name"].startswith("request_")]
        assert len(ring) == stamps


class TestProgramNames:
    """The benchmark reads the decode chunk as module `jit__lambda` and the
    pool prefill as `jit__pool_prefill_chunk_impl` and
    `jit__pool_prefill_remainder_impl` (bench/metrics, bench/cells/serve.py)."""

    def test_engine_programs_keep_their_module_names(self):
        eng = _smoke_engine(prefill_chunk=32, max_seq=128)
        B = 3
        pool = eng.init_pool_cache(B)
        rows = jnp.arange(B, dtype=jnp.int32)
        cur = jnp.zeros((B,), jnp.int32)
        fin = jnp.zeros((B,), bool)
        lowered = {
            "jit__lambda": eng.pool_chunk_fn(4).lower(
                eng.params, cur, fin, pool, jax.random.PRNGKey(0)),
            "jit__pool_prefill_chunk_impl": eng._pool_prefill_chunk.lower(
                eng.params, pool, jnp.zeros((B, 32), jnp.int32),
                jnp.full((B,), 32, jnp.int32), rows),
            "jit__pool_prefill_remainder_impl":
                eng._pool_prefill_remainder.lower(
                    eng.params, pool, jnp.zeros((B, 5), jnp.int32), rows),
        }
        for name, low in lowered.items():
            head = low.as_text().split("\n", 1)[0]
            assert head.startswith(f"module @{name} "), (name, head)
