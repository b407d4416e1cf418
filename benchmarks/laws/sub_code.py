"""A CAMEO-style child code of the string column ``of``: the parent's code
followed by one of ``count`` digits, drawn with weights falling as
1/(k+1); with ``bare`` that share of rows keeps the parent code itself (an
EventCode that is its own base code)."""

import numpy as np

import datagen


def make(rng, spec, n, ctx):
    codes, vocab = ctx.cols[spec["of"]]
    k = spec["count"]
    digit = datagen.weighted(rng, 1.0 / np.arange(1, k + 1), n)
    values = [str(p) + str(d) for p in vocab for d in range(k)]
    child = codes.astype(np.int64) * k + digit
    if spec.get("bare"):
        values += [str(p) for p in vocab]
        child = np.where(rng.random(n) < spec["bare"],
                         len(vocab) * k + codes, child)
    return datagen.encode(values, child)
