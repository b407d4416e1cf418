"""One of ``values`` per row with ``weights``: a string column when the
values are strings, numbers otherwise."""

import numpy as np

import datagen


def make(rng, spec, n, ctx):
    codes = datagen.weighted(rng, spec["weights"], n)
    values = spec["values"]
    if isinstance(values[0], str):
        return datagen.encode(values, codes)
    return np.asarray(values)[codes]
