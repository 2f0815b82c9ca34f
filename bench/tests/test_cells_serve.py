"""The serving cell, driven at the program's SMOKE size on the CPU, with
its device check skipped: a sound run is correct; a run whose tokens are
altered where the decode chunk produces them is not; nor is the fp8
control put in the reference's place."""
import numpy as np

from bench.tests import cells_smoke as cs


def test_sound_run_is_correct():
    out = cs.run_serve(seed=4_000_000_123, seconds=1.5)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 10
    assert out["notes"]["compiles_in_window"] == 0
    m = out["metrics"]
    assert m["ttft_p90_ms"]["value"] > 0 and m["tpot_p90_ms"]["value"] > 0


def test_altered_tokens_are_caught(monkeypatch):
    from repro.serving.scheduler import SlotPool
    real = SlotPool.decode_chunk

    def altered(self, n, rng):
        toks, bad, rng = real(self, n, rng)
        return (toks + 1) % cs.QWEN_SMOKE["vocab_size"], bad, rng

    monkeypatch.setattr(SlotPool, "decode_chunk", altered)
    out = cs.run_serve(seed=4_000_000_123, seconds=1.0)
    assert not out["correct"]
    assert out["checks"]["gap_max"]["value"] > out["checks"]["gap_max"][
        "limit"]


def test_fp8_control_fails_the_limit():
    """The reference in fp8 in the program's place: the gap of the token
    it puts first, read at every position of served requests."""
    from bench import reference
    from bench.cells import serve
    cell = cs.serve_cell()
    params, engine = serve.setup(cell, 17)
    outs = engine.serve([list(range(5, 5 + 37)), list(range(9, 9 + 70))],
                        [12, 12], max_batch=4)
    model = reference.model(cell.config)
    worst = 0.0
    for p, o in zip([list(range(5, 42)), list(range(9, 79))], outs):
        seq = p + o[:-1]
        pos = list(range(len(p) - 1, len(seq)))
        ref = model.logits(params, cell.config, seq, pos)
        low = model.logits(params, cell.config, seq, pos, reference.FP8)
        assert np.max(reference.served_gaps(ref, o)) <= \
            cell.limits["gap_max"]
        worst = max(worst, float(np.max(reference.control_gaps(ref, low))))
    assert worst > cell.limits["gap_max"]


def test_an_id_past_the_vocabulary_reads_an_infinite_gap():
    from bench import reference
    logits = np.zeros((3, 8), np.float32)
    logits[:, 2] = 1.0
    g = reference.served_gaps(logits, [2, 9, 0])
    assert g[0] == 0.0 and g[1] == np.inf and g[2] == 1.0


def test_served_padding_ids_are_caught(monkeypatch):
    """A vocabulary of 500 ids padded to 512, with values planted in the
    LM head's padded columns: the program serves padded ids, and the run
    comes out not correct (rather than failing in the check)."""
    import dataclasses

    import jax
    from bench import harness, weights
    real_cfg, real_make = harness.model_config, weights.make
    monkeypatch.setattr(harness, "model_config", lambda cfg: dataclasses.
                        replace(real_cfg(dict(cfg, vocab_size=512)),
                                vocab_size=500))

    def planted(shapes, seed, vocab, shardings=None):
        p = real_make(shapes, seed, vocab, shardings)
        pad = p["lm_head"].shape[1] - vocab
        noise = 3.0 * jax.random.normal(jax.random.PRNGKey(seed),
                                        (p["lm_head"].shape[0], pad))
        p["lm_head"] = p["lm_head"].at[:, vocab:].set(noise)
        return p

    monkeypatch.setattr(weights, "make", planted)
    out = cs.run_serve(seed=4_000_000_123, seconds=1.0,
                       config=dict(cs.QWEN_SMOKE, vocab_size=500))
    assert not out["correct"]
    assert out["checks"]["gap_max"]["value"] == np.inf


def test_work_count_follows_the_scheduler(monkeypatch):
    """Every span of a busy window counted as a traced run counts it: the
    rows and tokens agree with what the scheduler reports."""
    from bench.cells import serve
    cell = cs.serve_cell()
    params, engine = serve.setup(cell, 11)
    win = serve._Window(cell, engine, 11, 1.5)
    win.tracing = True
    monkeypatch.setattr(win, "stop_trace", lambda: None)
    win.run(cs.DEVICE["kind"])
    assert win.work["decode_chunks"] > 0 and win.work["prefill_flops"] > 0


def test_a_count_that_departs_from_the_scheduler_fails():
    import pytest
    from bench.cells import serve
    serve._agree("prefill_chunk_forward", {"rows": 2, "tokens": 64}, 2, 64)
    serve._agree("decode_chunk", {"rows": 3, "chunk": 8}, 3, None)
    with pytest.raises(serve.WorkMismatch):
        serve._agree("prefill_chunk_forward", {"rows": 2, "tokens": 64},
                     2, 48)
    with pytest.raises(serve.WorkMismatch):
        serve._agree("decode_chunk", {"rows": 3, "chunk": 8}, 2, None)


def test_calibration_holds_each_reading_to_the_limits(capsys):
    import json
    from bench.cells import serve
    serve.calibrate(cs.serve_cell(), [31], {31}, 1.0, [], cs.DEVICE)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [(x["kind"], x["correct"]) for x in lines] == [
        ("program", True), ("control", False)]
    assert lines[1]["checks"]["gap_max"]["value"] > cs.serve_cell().limits[
        "gap_max"]
