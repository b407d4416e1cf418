"""Points: ``lane_share`` of rows along ``lanes`` fixed shipping lanes
(``lane_seed``; +-``half_length`` degrees long, normal spread ``sd``), the
rest uniform over the globe (``bench.big_points``' law)."""

import numpy as np


def lanes(spec: dict) -> tuple:
    r = np.random.default_rng(spec["lane_seed"])
    k = spec["lanes"]
    return (r.uniform(-170, 170, k), r.uniform(-80, 80, k),
            r.uniform(0, np.pi, k))


def lane_points(rng, spec: dict, n: int):
    lx, ly, ang = lanes(spec)
    lane = rng.integers(0, len(lx), n)
    t = rng.uniform(-spec["half_length"], spec["half_length"], n)
    x = lx[lane] + t * np.cos(ang[lane]) + rng.normal(0, spec["sd"], n)
    y = ly[lane] + t * np.sin(ang[lane]) + rng.normal(0, spec["sd"], n)
    return np.clip(x, -180, 180), np.clip(y, -90, 90)


def make(rng, spec, n, ctx):
    n_lane = int(n * spec["lane_share"])
    x = np.empty(n)
    y = np.empty(n)
    x[:n_lane], y[:n_lane] = lane_points(rng, spec, n_lane)
    x[n_lane:] = rng.uniform(-180, 180, n - n_lane)
    y[n_lane:] = rng.uniform(-90, 90, n - n_lane)
    return x, y, {"lane": (0, n_lane), "noise": (n_lane, n)}
