"""Small cells for driving the cell functions on the CPU: the program's
SMOKE configurations, written as bench/configs files are."""
import time

from bench import harness

QWEN_SMOKE = {
    "name": "qwen3-8b-smoke", "registry": "qwen3-8b", "smoke": True,
    "reference": "linformer_decoder",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "hidden_act": "silu", "rope_theta": 10000.0,
    "dtype": "float32", "linformer_block_size": 16,
    "linformer_block_slots": 4}

PAPER_SMOKE = {
    "name": "linformer-paper-smoke", "registry": "linformer-paper",
    "smoke": True, "reference": "linformer_encoder", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 512, "hidden_act": "gelu",
    "max_position_embeddings": 128, "linformer_k": 16, "dtype": "float32"}

SERVE_MIX = {
    "kind": "serve", "pool_rows": 4, "max_seq": 512,
    "prefill_chunk": 32, "decode_chunk": 4,
    "arrivals": {"process": "poisson", "rate_per_s": 12.0},
    "shape_seed": 7,
    "prompt": {"dist": "lognormal", "median": 48, "sigma": 0.8, "min": 16,
               "max": 160, "block": 16, "remainders": [0, 1, 5]},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 24},
    "drain_limit_s": 120, "trace_seconds": 1, "check_requests": 4}

TRAIN_MIX = {
    "kind": "train", "batches": "mlm", "batch": 4, "seq_len": 128, "mask_prob": 0.15,
    "optimizer": {"lr": 3e-4, "warmup_steps": 1, "total_steps": 100000,
                  "schedule": "cosine", "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                  "weight_decay": 0.1, "grad_clip": 1.0,
                  "moment_dtype": "float32"},
    "check_steps": 3, "trace_seconds": 1}

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def serve_cell(limits=None, config=None):
    return harness.Cell(
        name="smoke.serve", chips=1, config=config or QWEN_SMOKE,
        mix=SERVE_MIX,
        limits=limits or {"gap_max": 1e-3, "min_tokens": 8},
        per_layer=[])


def train_cell(limits=None):
    return harness.Cell(
        name="smoke.train", chips=1, config=PAPER_SMOKE, mix=TRAIN_MIX,
        limits=limits or {"loss_gap": 1e-3, "grad_gap": 1e-3,
                          "change_gap": 1e-2},
        per_layer=[])


def run_serve(seed=5, seconds=2.0, limits=None, config=None):
    from bench.cells import serve
    return serve.run(serve_cell(limits, config), seed, seconds, False, DEVICE,
                     time.perf_counter())


def run_train(seed=5, seconds=2.0, limits=None):
    from bench.cells import train
    return train.run(train_cell(limits), seed, seconds, False, DEVICE,
                     time.perf_counter())
