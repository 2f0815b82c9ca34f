"""The harness refuses to measure anywhere but on a TPU it knows."""
import io
import json
import math
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_cpu_device_is_refused():
    with pytest.raises(harness.NoDevice):
        harness.check_device(1)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.NoDevice):
        harness.peaks("TPU v99")
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_cli_exits_nonzero_and_prints_no_result_without_a_tpu():
    from bench import run
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", "qwen3-8b.serve-mixed", "--seed", "3",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0 and out.getvalue() == ""


def test_every_cell_finds_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        runner = harness.runner(cell)
        assert callable(runner.run) and callable(runner.calibrate)
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(harness.load_reader(m["name"]))


def test_program_config_matches_each_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        mc = harness.model_config(cfg)
        assert mc.num_layers == cfg["num_hidden_layers"]


def test_a_checkout_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".work",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "-m", "bench.run", "--workload",
                        "qwen3-8b.serve-mixed", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_the_result_line_is_standard_json_when_a_number_is_infinite():
    out = io.StringIO()
    with redirect_stdout(out):
        harness.emit(correct=False, attempted=3, failed=1,
                     metrics={"ttft_p90_ms": {"value": math.inf,
                                              "unit": "ms"}},
                     device={"platform": "tpu"},
                     checks={"gap_max": {"value": math.inf, "limit": 3.25}})
    text = out.getvalue().strip().splitlines()[-1]
    line = json.loads(text, parse_constant=lambda c: pytest.fail(c))
    assert line["checks"]["gap_max"] == {"value": "inf", "limit": 3.25}
    assert list(line)[-1] == "checks"


def test_launches_per_request_are_read_at_the_trace_stop():
    read = harness.load_reader("prefill_launches_per_request.serve")
    rec = {"launches": {"prefill_forwards": 30, "first_tokens": 12}}
    assert read(rec) == 2.5
    rec["launches"]["first_tokens"] = 0
    assert read(rec) is None
