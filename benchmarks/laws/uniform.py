"""Floats uniform in [``low``, ``high``), rounded to ``round`` decimals if
given."""

import numpy as np


def make(rng, spec, n, ctx):
    v = rng.uniform(spec["low"], spec["high"], n)
    return np.round(v, spec["round"]) if "round" in spec else v
