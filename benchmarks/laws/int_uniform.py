"""Integers uniform in [``low``, ``high``]."""


def make(rng, spec, n, ctx):
    return rng.integers(spec["low"], spec["high"] + 1, n)
