"""Lognormal lengths: `spec` keys `median`, `sigma`, `min`, `max`."""
import math

import numpy as np


def draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(x, spec["min"], spec["max"])
