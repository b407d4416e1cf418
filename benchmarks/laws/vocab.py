"""Strings from a made-up vocabulary of ``size`` values (``format`` applied
to 0..size-1; ``size_per_row`` scales it with the rows), Zipf(``zipf``)
popular (uniform for 0), and the empty string for ``empty`` of the rows, as
GDELT leaves a field it could not code empty."""

import numpy as np

import datagen


def make(rng, spec, n, ctx):
    size = int(spec.get("size") or max(1, n * spec["size_per_row"]))
    a = spec.get("zipf", 0)
    empty = spec.get("empty", 0)
    values = [spec["format"].format(i) for i in range(size)]
    if not a and not empty:
        codes = rng.integers(0, size, n, dtype=np.int32)
    else:
        w = np.arange(1, size + 1.0) ** -a
        w = np.concatenate([[empty], w / w.sum() * (1 - empty)])
        codes = datagen.weighted(rng, w, n) - 1
        if empty:
            values = [""] + values
            codes += 1
    vocab = np.asarray(values, dtype=object)
    if (vocab[:-1] < vocab[1:]).all():      # sorted as made: keep the codes
        return codes, vocab
    return datagen.encode(vocab, codes)
