"""Integers 1..``max`` with P(k) ~ k^-``a`` (a truncated Zipf)."""

import numpy as np

import datagen


def make(rng, spec, n, ctx):
    w = np.arange(1, spec["max"] + 1, dtype=np.float64) ** -spec["a"]
    return datagen.weighted(rng, w, n).astype(np.int64) + 1
