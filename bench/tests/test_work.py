"""The required-work arithmetic against hand reckonings."""
import json
import os

import pytest

from bench import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _shapes(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return work.shapes_from_config(json.load(f))


QWEN = _shapes("qwen3-8b")
PAPER = _shapes("linformer-paper")


def test_qwen_weights_per_decode_step():
    # 8 layers x 192.9M + LM head 4096 x 151936, in bf16
    assert work.weight_bytes(QWEN) == pytest.approx(4.33e9, rel=1e-3)


@pytest.mark.parametrize("max_seq,mbytes", [(65536, 142.6), (32768, 75.5)])
def test_cache_bytes_per_row(max_seq, mbytes):
    # M = max_seq/256*16 slots and a 256-token raw block, K and V, 8 layers
    assert work.cache_bytes_allocated(QWEN, max_seq) / 1e6 == \
        pytest.approx(mbytes, abs=0.05)


def test_filled_cache_is_what_position_reaches():
    per_slot = 2 * 8 * 128 * 2 * 8
    assert work.cache_bytes(QWEN, 0) == per_slot
    assert work.cache_bytes(QWEN, 256) == per_slot * (16 + 1)
    assert work.cache_bytes(QWEN, 1000) == per_slot * (3 * 16 + 1000 % 256 + 1)


@pytest.mark.parametrize("t0,n", [(0, 1), (0, 512), (256, 512), (300, 77),
                                  (4096, 1000)])
def test_attended_sum_matches_brute_force(t0, n):
    assert work.attended_sum(QWEN, t0, n) == sum(
        work.attended(QWEN, t) for t in range(t0, t0 + n))


def test_decode_step_is_weight_bound_at_16_rows():
    fl, by = work.decode_step(QWEN, [32768 - 1] * 16)
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    assert by / peak["bytes_per_s"] > fl / peak["flops_per_s"]
    # weights and 16 rows at 32k: 2288 filled slots of 32 KiB each, 5.5 GB
    assert by / 1e9 == pytest.approx(4.33 + 16 * 0.0750, rel=0.01)


def test_prefill_counts_real_tokens_and_one_head_per_ending_row():
    fl1, _ = work.prefill(QWEN, [(0, 512)], 0)
    fl2, _ = work.prefill(QWEN, [(0, 512)], 1)
    assert fl2 - fl1 == 2 * 4096 * 151936
    dense = 2 * 512 * 8 * QWEN.layer_params
    assert dense < fl1 < 1.05 * dense


def test_paper_train_flops_per_token():
    # forward: 12 x 7.08M params x 2, the shared projection and attention
    # over k=128, the LM head at the 15% masked positions; x3 for training
    B, S = 64, 512
    per_tok = work.train_step(PAPER, B, S, int(0.15 * B * S)) / (B * S)
    assert per_tok / 1e9 == pytest.approx(0.575, rel=0.01)


def test_exact_kernels_flops():
    fl, by = work.exact_attention_kernels(PAPER, 1, 512)
    # per layer: 2 projections 2*512*128*768, attention 4*512*128*768
    assert fl == 12 * (2 * 2 * 512 * 128 * 768 + 4 * 512 * 128 * 768)
    assert by > 0


def test_roofline_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.roofline_s(1000, 50, peak) == 10.0
    assert work.roofline_s(100, 50, peak) == 5.0
