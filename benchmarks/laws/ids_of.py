"""A per-row entity id (a vessel's MMSI): ``count`` entities, id
``base + step * entity``, drawn uniformly per row."""


def make(rng, spec, n, ctx):
    return spec["base"] + spec["step"] * rng.integers(0, spec["count"], n)
