"""What every cell shares: finding its files by name, the device check,
the compilation cache, the metric readers and the result line.

A cell is one entry of BENCHMARK.json's `workloads`. Its configuration is
bench/configs/<config>.json (which names its plain reference in
bench/reference), its traffic mix bench/traffic/<traffic>.json (whose
`kind` names the runner bench/cells/<kind>.py), its correctness limits
bench/limits/<cell>.json, and each per-layer metric a reader
bench/metrics/<metric>.py with `read(record) -> float | None`. A new cell,
mix or metric adds files; none of these is edited for it.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")
WORK_DIR = os.path.join(HERE, ".work")


class NoDevice(SystemExit):
    """The run cannot be measured here; exits non-zero, prints no result."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    limits: Dict
    per_layer: List[Dict]


def _json(*parts) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec = next((w for w in bench["workloads"] if w["name"] == name), None)
    if spec is None:
        raise SystemExit(f"unknown workload {name!r}")
    return Cell(
        name=name, chips=spec["chips"],
        config=_json("configs", f"{spec['config']}.json"),
        mix=_json("traffic", f"{spec['traffic']}.json"),
        limits=_json("limits", f"{name}.json"),
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def runner(cell: Cell):
    """The module that runs cells of the mix's kind: bench/cells/<kind>.py,
    with `run(cell, seed, seconds, trace, device, t_start)` and
    `calibrate(cell, seeds, control_seeds, seconds, sweep, device)`."""
    return importlib.import_module(f"bench.cells.{cell.mix['kind']}")


def peaks(kind: str) -> Dict:
    table = _json("peaks.json")
    if kind not in table or kind == "source":
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check_device(chips: int) -> Dict:
    """The devices JAX found, refused unless they are TPUs of a kind in
    the peak table and at least `chips` of them."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"first device is {d.platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoDevice(f"{chips} chips asked for, {len(devs)} found")
    peaks(d.device_kind)
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, so only a cell's first run compiles."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def count_compiles() -> Callable[[], int]:
    """A counter of XLA compilations from now on (a cache hit is none)."""
    import jax
    n = [0]

    def listen(event, *args, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: n[0]


def memory_peak_bytes(chips: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def model_config(cfg: Dict):
    """The program's model configuration for a bench/configs file: the
    registry entry (its small test variant where the file says `smoke`),
    cut to the file's depth and dtype, and checked against every width the
    file states."""
    from repro.configs import get_config, get_smoke_config
    mc = (get_smoke_config if cfg.get("smoke") else get_config)(
        cfg["registry"])
    mc = dataclasses.replace(mc, num_layers=cfg["num_hidden_layers"],
                             dtype=cfg["dtype"],
                             remat=cfg.get("remat", mc.remat))
    a = mc.attention
    want = {
        "hidden_size": mc.d_model, "intermediate_size": mc.mlp.d_ff,
        "num_attention_heads": a.num_heads,
        "num_key_value_heads": a.num_kv_heads, "head_dim": a.head_dim,
        "vocab_size": mc.vocab_size,
    }
    if a.kind == "linformer_causal":
        want.update(linformer_block_size=a.linformer.block_size,
                    linformer_block_slots=a.linformer.block_slots,
                    rope_theta=a.rope_theta)
    else:
        want.update(linformer_k=a.linformer.k,
                    max_position_embeddings=mc.max_seq_len)
    bad = {k: (cfg[k], v) for k, v in want.items() if cfg[k] != v}
    if bad:
        raise ValueError(f"{cfg['name']}: file and program disagree: {bad}")
    return mc


def load_reader(metric: str):
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_values(cell: Cell, record: Dict) -> Dict:
    out = {}
    for m in cell.per_layer:
        v = load_reader(m["name"])(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def checks_ok(checks: Dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def judged(values: Dict[str, float], limits: Dict) -> Dict:
    """Each number that has a limit in `limits`, beside its limit."""
    return {k: {"value": values[k], "limit": v}
            for k, v in limits.items() if k in values}


def plain(x):
    """JSON without the non-standard Infinity/NaN: a number that is not
    finite is written as the string "inf", "-inf" or "nan"."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: plain(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [plain(v) for v in x]
    return x


def emit(*, correct: bool, attempted: int, failed: int, metrics: Dict,
         device: Dict, checks: Dict, breakdown: Optional[Dict] = None,
         notes: Optional[Dict] = None) -> None:
    """The numbers compared, beside their limits, as the last lines of
    standard error; then the result as the last line of standard output,
    with `checks` as its last key."""
    for k, v in (notes or {}).items():
        print(f"[note] {k}: {v}", file=sys.stderr)
    for k, c in checks.items():
        print(f"[check] {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(plain(line), allow_nan=False), flush=True)
