"""Shared test fixtures. NOTE: no XLA_FLAGS here — tests must see 1 device;
the multi-device dry-run tests spawn subprocesses with their own flags."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def f32(cfg):
    """Smoke configs in float32 for numerically tight assertions."""
    return dataclasses.replace(cfg, dtype="float32")


def make_batch(cfg, B=2, S=32, seed=0):
    rng_ = jax.random.PRNGKey(seed)
    if cfg.embedding_inputs:
        return {
            "embeds": jax.random.normal(rng_, (B, S, cfg.d_model),
                                        jnp.float32),
            "labels": jnp.zeros((B, S), jnp.int32),
            "loss_mask": jnp.ones((B, S), jnp.int32),
        }
    text = S - cfg.frontend_embed_len
    toks = jax.random.randint(rng_, (B, text), 0, cfg.vocab_size)
    b = {
        "tokens": toks,
        "labels": toks,
        "loss_mask": jnp.ones((B, text), jnp.int32),
    }
    if cfg.frontend_embed_len:
        b["frontend_embeds"] = jax.random.normal(
            jax.random.fold_in(rng_, 1),
            (B, cfg.frontend_embed_len, cfg.d_model), jnp.float32)
    return b


def applied_step(step, *args, **kw):
    """Run a per-layer cache step of core/cache.py and apply the slots it
    writes to the layer cache it read (its fourth argument)."""
    from repro.core.cache import write_cache
    out, writes = step(*args, **kw)
    return out, write_cache(args[3], writes)


_HLO_COMPUTATION = re.compile(r"^(ENTRY )?%\S+ .*\{$")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+) ([\w-]+)\((.*)$")


def _hlo_shape(ty):
    m = re.match(r"[a-z]\w*\[([\d,]*)\]", ty)
    return tuple(int(d) for d in m.group(1).split(",") if d) if m else None


def hlo_whole_buffer_ops(hlo, whole):
    """Scan optimised HLO text for whole-buffer data movement: each
    `copy` or `select` whose result, and each `dynamic-update-slice` or
    `scatter` whose update (leading unit axes dropped), has a shape in
    `whole`. An in-place write's result is always the whole buffer it writes
    into, so a write is judged by what it writes. Returns (in_entry, opcode,
    shape) per hit; `in_entry` marks the entry computation, which runs once
    per call and not per loop step."""
    shapes, hits, entry = {}, [], False
    for line in hlo.splitlines():
        if _HLO_COMPUTATION.match(line):
            entry = line.startswith("ENTRY")
            continue
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        name, ty, op, args = m.groups()
        shapes[name] = _hlo_shape(ty)
        if op in ("copy", "select"):
            shape = shapes[name]
        elif op in ("dynamic-update-slice", "scatter"):
            operands = re.findall(r"%([\w.-]+)", args.split(")")[0])
            shape = shapes.get(operands[1 if op == "dynamic-update-slice"
                                        else -1])
            while shape and shape[0] == 1:
                shape = shape[1:]
        else:
            continue
        if shape in whole:
            hits.append((entry, op, shape))
    return hits
