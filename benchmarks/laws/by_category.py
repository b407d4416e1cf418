"""A value drawn per row from a table keyed by the string column ``of``:
numbers (``integer`` for whole numbers), or strings."""

import numpy as np

import datagen


def make(rng, spec, n, ctx):
    codes, vocab = ctx.cols[spec["of"]]
    choices = [spec["table"][str(v)] for v in vocab]
    lens = np.array([len(c) for c in choices])
    pick = (rng.random(n) * lens[codes]).astype(np.int64)
    width = lens.max()
    if isinstance(choices[0][0], str):
        flat = sorted({s for c in choices for s in c})
        pos = {s: i for i, s in enumerate(flat)}
        table = np.array([[pos[s] for s in c] + [pos[c[0]]] * (width - len(c))
                          for c in choices], dtype=np.int32)
        return table[codes, pick], np.asarray(flat, dtype=object)
    table = np.array([c + c[:1] * (width - len(c)) for c in choices],
                     dtype=np.float64)
    v = table[codes, pick]
    return v.astype(np.int64) if spec.get("integer") else v
