"""Plain references of the benchmark's models, and their fp8 controls.

Each configuration names its reference in its bench/configs file
(`reference`), a module of this package written from the model's published
equations in straightforward jax.numpy, importing nothing of the program.
A reference reads the weights that the benchmark made from the seed
(bench/weights.py), laid out as the program's parameter tree, and computes
in float32 at "highest" matmul precision. A served model's reference has
`logits(params, cfg, tokens, positions, prec)`; a trained one's
`loss_and_grad(params, batch, cfg, prec)`.

This module holds what they share: the arithmetic of the matrix products,
the norms, the gaps that the serving check compares, and AdamW as the
training mixes configure it.

`Prec` decides the arithmetic of every matrix product. `FP32` is the
reference; `FP8` rounds both operands of each product to float8 e4m3 (one
scale per tensor), the precision below the served bfloat16: it is the
control that the correctness check has to reject.
"""
from __future__ import annotations

import importlib
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG = -1e30


class Prec:
    """Arithmetic of the matrix products."""

    def __init__(self, name: str):
        self.name = name

    def __hash__(self):
        return hash(self.name)

    def __eq__(self, other):
        return isinstance(other, Prec) and other.name == self.name

    def cast(self, x):
        x = x.astype(F32)
        if self.name == "fp32":
            return x
        amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s

    def mm(self, a, b):
        return jnp.matmul(self.cast(a), self.cast(b),
                          precision=jax.lax.Precision.HIGHEST)

    def ein(self, spec, a, b):
        return jnp.einsum(spec, self.cast(a), self.cast(b),
                          precision=jax.lax.Precision.HIGHEST)


FP32 = Prec("fp32")
FP8 = Prec("fp8")


def rms(x, scale, eps: float = 1e-6):
    x = x.astype(F32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * scale.astype(F32)


def rope(x, pos, theta: float):
    """x (S, H, Dh); pos (S,). Half rotation: pairs (i, i + Dh/2)."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def model(cfg: Dict):
    """The reference module that the configuration file names."""
    return importlib.import_module(f"bench.reference.{cfg['reference']}")


def served_gaps(ref_logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at that position. An id outside the configuration's
    vocabulary (the program's padded ids, say) has no reference logit: its
    gap is +inf."""
    ref = np.asarray(ref_logits, np.float32)
    ids = np.asarray(served, np.int64)
    ok = (ids >= 0) & (ids < ref.shape[-1])
    got = ref[np.arange(len(ids)), np.where(ok, ids, 0)]
    return np.where(ok, ref.max(axis=-1) - got, np.inf)


def control_gaps(ref_logits: np.ndarray, low_logits: np.ndarray
                 ) -> np.ndarray:
    """Per position: how far the token that the lower precision puts first
    lies below the reference's best."""
    return served_gaps(ref_logits, np.asarray(low_logits).argmax(-1))


# ---------------------------------------------------------------------------
# Training: AdamW as configured, and the reference run of the first steps
# ---------------------------------------------------------------------------


def lr_at(opt: Dict, step: int) -> float:
    """The configured schedule at optimizer step `step` (0 before the first
    update): linear warmup, then cosine or linear decay or constant."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0), 1)
    decay = {"constant": 1.0, "linear": 1.0 - frac,
             "cosine": 0.5 * (1.0 + math.cos(math.pi * frac))}[
        opt["schedule"]]
    return opt["lr"] * warm * decay


def adamw_reference(params, grads, state: Dict, opt: Dict,
                    store_dtype) -> Tuple[Dict, Dict]:
    """One AdamW step as configured: clip by the global norm, bias-corrected
    moments, decoupled weight decay on matrices; parameters are stored back
    in `store_dtype`, moments in float32."""
    gnorm = math.sqrt(sum(float(jnp.sum(g.astype(F32) ** 2))
                          for g in jax.tree.leaves(grads)))
    clip = min(1.0, opt["grad_clip"] / max(gnorm, 1e-9))
    t = state["step"] + 1
    lr = lr_at(opt, state["step"])
    b1, b2 = opt["b1"], opt["b2"]

    def upd(p, g, mu, nu):
        g = g.astype(F32) * clip
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        delta = (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t))
                                        + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p.astype(F32)
        return (p.astype(F32) - lr * delta).astype(store_dtype), mu, nu

    out = jax.tree.map(upd, params, grads, state["mu"], state["nu"])
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"mu": pick(1), "nu": pick(2), "step": t}


def adamw_zero(params) -> Dict:
    z = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    return {"mu": z, "nu": z, "step": 0}


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(
        jnp.square(v.astype(F32))))) for k, v in flat}


def train_run(params, batches: List[Dict], cfg: Dict, opt: Dict,
              prec: Prec = FP32) -> Dict:
    """The configured training of the model `cfg` from `params` over
    `batches`: each step's loss, the first step's gradient as the optimizer
    gets it (clipped), and the parameters after the last step."""
    loss_and_grad = model(cfg).loss_and_grad
    state = adamw_zero(params)
    losses, first_grad = [], None
    store = jax.tree.leaves(params)[0].dtype
    for batch in batches:
        loss, grads = loss_and_grad(params, batch, cfg, prec)
        params, state = adamw_reference(params, grads, state, opt, store)
        if first_grad is None:
            first_grad = jax.tree.map(lambda m: m / (1 - opt["b1"]),
                                      state["mu"])
        losses.append(loss)
    return {"losses": losses, "first_grad": first_grad, "params": params}
