"""Points: ``share`` of rows exactly on ``places`` fixed centroids
(``place_seed``; half along lanes, half uniform; rounded to ``decimals``)
with Zipf(``zipf_s``) popularity, the rest by the nested ``rest`` law."""

import numpy as np

import datagen


def make(rng, spec, n, ctx):
    k = spec["places"]
    fixed = np.random.default_rng(spec["place_seed"])
    cx, cy, _ = datagen.law("lanes_noise").make(
        fixed, dict(spec["rest"], lane_share=0.5), k, ctx)
    cx, cy = np.round(cx, spec["decimals"]), np.round(cy, spec["decimals"])
    n_place = int(n * spec["share"])
    which = datagen.weighted(rng, 1.0 / np.arange(1, k + 1) ** spec["zipf_s"],
                             n_place)
    rx, ry, rest = datagen.make(rng, spec["rest"], n - n_place, ctx)
    groups = {"place": (0, n_place)}
    groups.update({g: (a + n_place, b + n_place)
                   for g, (a, b) in rest.items()})
    return (np.concatenate([cx[which], rx]), np.concatenate([cy[which], ry]),
            groups)
