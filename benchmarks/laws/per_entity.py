"""A static field of the entity in column ``of`` (a vessel's name, type or
size, keyed by its MMSI ``base + step * entity`` of ``count`` entities): the
nested ``value`` law draws one value per entity, and each row takes its
entity's."""

import datagen


def make(rng, spec, n, ctx):
    entity = (ctx.cols[spec["of"]] - spec["base"]) // spec["step"]
    v = datagen.make(rng, spec["value"], spec["count"], ctx)
    if isinstance(v, tuple):
        codes, vocab = v
        return codes[entity], vocab
    return v[entity]
