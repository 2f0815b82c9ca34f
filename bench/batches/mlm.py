"""Masked-language-model batches (BERT-style), one fixed shape.

Mix keys: `batch`, `seq_len`, `mask_prob`.
"""
from typing import Dict

import numpy as np

RESERVED = 4             # token ids below this are special (pad, bos, eos, mask)
BOS, MASK = 1, 3


def make(mix: Dict, seed: int, step: int, vocab: int
         ) -> Dict[str, np.ndarray]:
    """Batch `step` of a run: uniform ids with BOS first; each position is
    picked with the mix's mask_prob and then replaced by MASK (80%), a
    random id (10%) or kept (10%). labels are the original ids and
    loss_mask marks the picked positions."""
    B, S = mix["batch"], mix["seq_len"]
    rng = np.random.default_rng([seed, 2, step])
    toks = rng.integers(RESERVED, vocab, (B, S), dtype=np.int32)
    toks[:, 0] = BOS
    picked = rng.random((B, S)) < mix["mask_prob"]
    picked[:, 0] = False
    roll = rng.random((B, S))
    inp = toks.copy()
    inp[picked & (roll < 0.8)] = MASK
    rnd = rng.integers(RESERVED, vocab, (B, S), dtype=np.int32)
    swap = picked & (roll >= 0.8) & (roll < 0.9)
    inp[swap] = rnd[swap]
    return {"tokens": inp, "labels": toks,
            "loss_mask": picked.astype(np.int32)}
