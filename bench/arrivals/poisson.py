"""Poisson arrivals: independent users, exponential gaps between requests.

`spec` keys: `rate_per_s` (read by the general generator for the count).
The gaps are scaled so that all n requests fall due inside the window.
"""
import numpy as np


def due(rng: np.random.Generator, n: int, seconds: float,
        spec: dict) -> np.ndarray:
    """Due times (seconds after the window opens) of n requests, ascending."""
    gaps = rng.exponential(1.0, n)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    return np.cumsum(gaps)
