"""Gamma(``shape``, ``scale``) clipped to ``clip``, rounded to ``round``."""

import numpy as np


def make(rng, spec, n, ctx):
    v = np.clip(rng.gamma(spec["shape"], spec["scale"], n), *spec["clip"])
    return np.round(v, spec["round"])
