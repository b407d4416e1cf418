"""Epoch milliseconds uniform over ``days`` from ``start``, at millisecond
resolution, or at midnight of each day with ``"resolution": "day"``."""

import datagen


def make(rng, spec, n, ctx):
    t0 = datagen.day_ms(spec["start"])
    if spec.get("resolution") == "day":
        return (t0 // datagen.MS_DAY
                + rng.integers(0, spec["days"], n)) * datagen.MS_DAY
    return rng.integers(t0, t0 + spec["days"] * datagen.MS_DAY, n)
