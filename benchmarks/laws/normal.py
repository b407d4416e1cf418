"""Normal(``mean``, ``sd``) clipped to ``clip``, full double precision."""

import numpy as np


def make(rng, spec, n, ctx):
    return np.clip(rng.normal(spec["mean"], spec["sd"], n), *spec["clip"])
