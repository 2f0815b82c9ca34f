"""Weights from the run's seed, made on the device in one jitted call.

The tree has the layout the program's model takes (its shapes come from
`jax.eval_shape` of the program's own initialiser, so nothing of the
program's values is used); every value is drawn here:

* norm scales: 1;
* token embeddings and learned positions: N(0, 0.02²);
* the Linformer projection E (last axis k or r): N(0, 1/k);
* every other matrix: N(0, 1/fan_in), fan_in its second-to-last axis;
* the rows of the token table and the columns of the LM head that the
  program adds past the configuration's vocabulary (it pads the vocabulary
  to a multiple of 256): zero. The configuration has no such ids, so they
  get no values of their own; a zero column's logit loses to the best real
  one, so greedy decoding never picks it. With random values there the
  program serves ids past the vocabulary, which the serving check reads as
  an infinite gap; training normalises its loss over the padded columns
  too, each adding exp(0) to the softmax's sum.

The same seed gives the same bits, so the reference can make them again
after the program's state is freed. Given shardings, each device draws only
its own shard of each leaf, and the bits are those of the unsharded draw
(JAX's partitionable threefry).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _leaf(key, path: str, sds, vocab: int):
    shape, dtype = sds.shape, sds.dtype
    if path.endswith("['scale']"):
        return jnp.ones(shape, dtype)
    if "['embed']" in path:
        std = 0.02
    elif path.endswith("['E']") or path.endswith("['F']"):
        std = shape[-1] ** -0.5
    else:
        std = shape[-2] ** -0.5
    x = jax.random.normal(key, shape, jnp.float32) * std
    if path == "['embed']['tok']":
        x = jnp.where(jnp.arange(shape[0])[:, None] < vocab, x, 0.0)
    elif path == "['lm_head']":
        x = jnp.where(jnp.arange(shape[1])[None, :] < vocab, x, 0.0)
    return x.astype(dtype)


def make(shapes, seed: int, vocab: int, shardings=None):
    """A tree of arrays shaped like `shapes` (ShapeDtypeStructs), from
    `seed`, for a model of `vocab` token ids; laid out as `shardings` (a
    tree like `shapes`) where given, else on the default device."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = tuple(jax.tree_util.keystr(k) for k, _ in flat)
    sds = tuple(v for _, v in flat)
    out = {} if shardings is None else {
        "out_shardings": jax.tree_util.tree_leaves(shardings)}

    @functools.partial(jax.jit, **out)
    def build(key):
        keys = jax.random.split(key, len(sds))
        return [_leaf(keys[i], paths[i], sds[i], vocab)
                for i in range(len(sds))]

    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (1 << 31))
    key = jax.random.fold_in(key, seed >> 31)
    return jax.tree_util.tree_unflatten(tree, build(key))
