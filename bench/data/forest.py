"""The paper's synthetic deployment (arXiv:2002.00540, section 7.1).

10 quantitative and 2 qualitative attributes in the shape of the UCI
Covertype data (https://archive.ics.uci.edu/dataset/31/covertype,
``covtype.info``), duplicated ``n_dup`` times with each duplicate's rows
shuffled.  The construction and the query templates are copied from the
program's ``repro.columnar.forest`` and ``repro.columnar.queries``; the
marginals follow Covertype's own types and ranges (integers, 4 wilderness
areas, 40 soil types) where the program's draws continuous floats and 7
soil types.

Query family ``forest_templates``: random trees as in section 7.1 (root
AND or OR, 2-5 children per inner node, unbalanced), ``col < c`` on
quantitative attributes with ``c`` at a selectivity drawn per request from
{0.1, ..., 0.9} of the column's exact quantiles, ``col == v`` on
qualitative ones.  Each template reads distinct columns.  Which template a
request uses is drawn with Zipf popularity.
"""
from __future__ import annotations

import numpy as np

from bench.dataset import Dataset

#: Covertype's 10 quantitative attributes as UCI's covtype.info lists them:
#: integers (metres, degrees, a 0-255 index), drawn here from the published
#: summary statistics of the 581,012 rows (mean, standard deviation, least
#: and greatest value); a shape rather than the rows, since the benchmark
#: downloads nothing
QUANT = {
    # name: (distribution, mean, sd, least, greatest)
    "elevation": ("normal", 2959.37, 279.98, 1859, 3858),
    "aspect": ("uniform", 155.66, 111.91, 0, 360),
    "slope": ("normal", 14.10, 7.49, 0, 66),
    "h_dist_hydro": ("gamma", 269.43, 212.55, 0, 1397),
    "v_dist_hydro": ("normal", 46.42, 58.30, -173, 601),
    "h_dist_road": ("gamma", 2350.15, 1559.25, 0, 7117),
    "hillshade_9am": ("normal", 212.15, 26.77, 0, 254),
    "hillshade_noon": ("normal", 223.32, 19.77, 0, 254),
    "hillshade_3pm": ("normal", 142.53, 38.27, 0, 254),
    "h_dist_fire": ("gamma", 1980.29, 1324.20, 0, 7173),
}
QUANT_BASE = list(QUANT)
#: the 2 qualitative attributes: Wilderness_Area (4 designations) and
#: Soil_Type (40 types), each one code per row
QUAL_BASE = [("wilderness", 4), ("soil", 40)]
SELECTIVITIES = (0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9)


def _draw(rng: np.random.Generator, dist: str, mean: float, sd: float,
          n: int) -> np.ndarray:
    if dist == "normal":
        return rng.normal(mean, sd, n)
    if dist == "gamma":
        shape = (mean / sd) ** 2
        return rng.gamma(shape, mean / shape, n)
    return rng.uniform(0.0, 1.0, n)        # scaled to the range below


def base_columns(n: int, rng: np.random.Generator) -> dict:
    """One copy of the 12 attributes: integer values in each quantitative
    attribute's range (stored as float32, which holds them exactly), and
    skewed codes for the qualitative ones."""
    cols = {}
    for name, (dist, mean, sd, lo, hi) in QUANT.items():
        x = _draw(rng, dist, mean, sd, n)
        if dist == "uniform":
            x = lo + x * (hi - lo + 1) - 0.5
        cols[name] = np.clip(np.rint(x), lo, hi).astype(np.float32)
    for name, k in QUAL_BASE:
        p = rng.dirichlet(np.ones(k) * 0.8)
        cols[name] = rng.choice(k, size=n, p=p).astype(np.int32)
    return cols


class ForestData(Dataset):

    def __init__(self, config: dict, seed: int):
        rng = np.random.default_rng(seed)
        n = int(config["rows"])
        base = base_columns(n, rng)
        cols = {}
        self.base_of = {}
        for d in range(int(config["n_dup"])):
            perm = None if d == 0 else rng.permutation(n)
            for name, col in base.items():
                cols[f"{name}_{d}"] = col if perm is None else col[perm]
                self.base_of[f"{name}_{d}"] = name
        super().__init__(cols, n)
        self._base = base
        # exact quantiles of each quantitative marginal: the constant at
        # selectivity g is the value of rank floor(g * n), so `col < c`
        # selects that share of rows up to ties; every duplicate is a
        # permutation of its base column and shares its constants
        ranks = [int(g * n) for g in SELECTIVITIES]
        self.constants = {
            name: [float(v) for v in np.partition(base[name], ranks)[ranks]]
            for name in QUANT_BASE}
        self.qual_k = dict(QUAL_BASE)

    def distinct(self, column: str) -> int:
        return len(np.unique(self._base[self.base_of[column]]))

    # -- query family ----------------------------------------------------------
    def family(self, name: str, params: dict, rng: np.random.Generator):
        if name != "forest_templates":
            raise KeyError(f"forest has no query family {name!r}")
        n_t = int(params["templates"])
        lo_d, hi_d = params["depths"]
        lo_a, hi_a = params["atoms"]
        depths = list(range(lo_d, hi_d + 1))
        sizes = list(range(lo_a, hi_a + 1))
        # the sizes of the template at each popularity rank are the same
        # for every seed, so seeds change which columns and connectives a
        # template has, not how much work the mix holds
        templates = [_template(self, depths[i % len(depths)],
                               sizes[(i // len(depths)) % len(sizes)], rng)
                     for i in range(n_t)]
        w = 1.0 / np.arange(1, n_t + 1) ** float(params["zipf_s"])
        p = w / w.sum()

        def draw(r: np.random.Generator):
            t = templates[int(r.choice(n_t, p=p))]
            return self._instantiate(t, r)
        return draw

    def _instantiate(self, node, r):
        if node[0] == "slot":
            col = node[1]
            base = self.base_of[col]
            if base in self.constants:
                c = self.constants[base][int(r.integers(len(SELECTIVITIES)))]
                return ("atom", col, "lt", c)
            return ("atom", col, "eq", int(r.integers(self.qual_k[base])))
        return (node[0], tuple(self._instantiate(c, r) for c in node[1]))


def _partition(rng, quota: int, cap: int):
    """Split ``quota`` atoms into 2..5 parts of at most ``cap`` each (as
    ``repro.columnar.queries._partition``)."""
    kmin = max(2, -(-quota // cap))
    kmax = min(5, quota)
    k = int(rng.integers(kmin, kmax + 1)) if kmax > kmin else kmin
    parts = [1] * k
    rem = quota - k
    while rem > 0:
        j = int(rng.integers(k))
        if parts[j] < cap:
            parts[j] += 1
            rem -= 1
    return parts


def _depth(node) -> int:
    if node[0] == "slot":
        return 0
    return 1 + max(_depth(c) for c in node[1])


def _template(data: ForestData, depth: int, n_atoms: int, rng):
    """A random tree of exactly ``n_atoms`` slots and depth ``depth``, on
    distinct columns."""
    n_atoms = max(n_atoms, 2 ** (depth - 1))
    names = list(data.columns)
    for _ in range(200):
        cols = iter(rng.choice(len(names), size=n_atoms, replace=False))
        kind = "and" if rng.random() < 0.5 else "or"
        root = _build(rng, n_atoms, 1, depth, kind,
                      lambda: ("slot", names[int(next(cols))]))
        if _depth(root) == depth:
            return root
    raise RuntimeError(f"no depth-{depth} tree with {n_atoms} atoms")


def _build(rng, quota, level, depth, kind, leaf):
    if quota == 1:
        return leaf()
    cap = 5 ** (depth - level) if depth > level else 1
    sub = "or" if kind == "and" else "and"
    if level == depth:
        return (kind, tuple(leaf() for _ in range(quota)))
    return (kind, tuple(_build(rng, p, level + 1, depth, sub, leaf)
                        for p in _partition(rng, quota, cap)))


def build(config: dict, seed: int) -> ForestData:
    return ForestData(config, seed)
