"""Traffic: the one generator that reads a mix file under bench/traffic.

A mix is a JSON file of parameters, and every number of the traffic comes
from it. Its `kind` names the cell runner, bench/cells/<kind>.py. The
parts of a mix that differ in shape are found by name, each in a file of
its own, so a new shape of traffic only adds files:

* `arrivals.process`: bench/arrivals/<process>.py, `due(rng, n, seconds,
  spec)`, when each of a window's requests falls due;
* `prompt.dist`, `output.dist`: bench/lengths/<dist>.py, `draw(rng, spec,
  n)`, request sizes;
* `batches`: bench/batches/<name>.py, `make(mix, seed, step, vocab)`,
  training batches.

Serving: a window holds round(rate_per_s · seconds) requests. The schedule
(sizes and due times, in order) is drawn from the mix's `shape_seed`, so
every run does the same work; `--seed` decides the prompt tokens (and the
weights). Reordering the schedule by seed doubled the spread of the tails
between seeds on the chip, so it is not reordered. A prompt is whole
`block`s plus a remainder drawn from the mix's fixed set.

This module imports neither JAX nor the program.
"""
from __future__ import annotations

import importlib
import json
import math
import os
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
RESERVED = 4             # token ids below this are special (pad, bos, eos, mask)


def load_mix(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def part(kind: str, name: str):
    """The module of one named part of a mix: bench/<kind>/<name>.py."""
    return importlib.import_module(f"bench.{kind}.{name}")


@dataclass(frozen=True)
class Arrival:
    due_s: float             # seconds after the window opens
    prompt_len: int
    out_len: int


def _lengths(rng, spec: Dict, n: int) -> np.ndarray:
    return part("lengths", spec["dist"]).draw(rng, spec, n)


def request_sizes(mix: Dict, n: int) -> List[tuple]:
    """n (prompt_len, out_len) pairs from the mix's own seed."""
    rng = np.random.default_rng(mix["shape_seed"])
    p = mix["prompt"]
    blocks = np.maximum(1, (_lengths(rng, p, n) // p["block"]).astype(int))
    rems = rng.choice(np.asarray(p["remainders"]), n)
    prompts = blocks * p["block"] + rems
    outs = _lengths(rng, mix["output"], n).astype(int)
    return [(int(a), int(b)) for a, b in zip(prompts, outs)]


def arrivals(mix: Dict, seconds: float,
             rate_per_s: float = None) -> List[Arrival]:
    """The window's schedule: round(rate · seconds) requests, every one due
    inside the window, the same for every seed."""
    spec = mix["arrivals"]
    rate = rate_per_s if rate_per_s is not None else spec["rate_per_s"]
    n = max(1, round(rate * seconds))
    due = part("arrivals", spec["process"]).due(
        np.random.default_rng([mix["shape_seed"], 1]), n, seconds, spec)
    sizes = request_sizes(mix, n)
    return [Arrival(float(d), p, o) for d, (p, o) in zip(due, sizes)]


def prompt_tokens(seed: int, rid: int, length: int, vocab: int) -> List[int]:
    """Prompt `rid` of a run: random ids from the seed, no special ids."""
    rng = np.random.default_rng([seed, 1, rid])
    return rng.integers(RESERVED, vocab, length).tolist()


def batch(mix: Dict, seed: int, step: int, vocab: int
          ) -> Dict[str, np.ndarray]:
    """Training batch `step` of a run, from the mix's batch generator."""
    return part("batches", mix["batches"]).make(mix, seed, step, vocab)


# ---------------------------------------------------------------------------
# Request timing
# ---------------------------------------------------------------------------


@dataclass
class RequestTiming:
    """Host-clock stamps of one request, in seconds after the window
    opened. `first`/`last` are None until the request streams a token."""
    due: float
    first: float = None
    last: float = None
    n_tokens: int = 0
    done: bool = False

    def token(self, now: float) -> None:
        if self.first is None:
            self.first = now
        self.last = now
        self.n_tokens += 1

    @property
    def ttft(self) -> float:
        """Due time to first streamed token; +inf if none came."""
        return math.inf if self.first is None else self.first - self.due

    @property
    def tpot(self) -> float:
        """(last − first) / (n − 1); +inf for a request that did not
        finish, 0 for one that finished with a single token."""
        if not self.done or self.first is None:
            return math.inf
        if self.n_tokens < 2:
            return 0.0
        return (self.last - self.first) / (self.n_tokens - 1)


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) with linear interpolation between
    ranks, as numpy's default; +inf values sort last, and a percentile that
    touches one is +inf."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    if math.isinf(xs[hi]) and frac > 0 or math.isinf(xs[lo]):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * frac
