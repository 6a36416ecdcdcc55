"""Seeded traffic for the benchmark: graph pools, fresh features, arrivals.

The graph generators are copies of the program's ``data/graphs.py``
(``molhiv_like``, ``hep_like``: the paper's Table IV statistics) and the
Poisson arrivals of ``stream_bench._arrival_times``, kept here so that a
change to the program cannot move the yardstick.

A traffic mix is a JSON file under ``mixes/``; everything in this module is
driven by its keys, and a cell's own data (``cells/<cell>.json``, such as
the open-loop ``rate_hz``) overrides them. Every draw but the arrival
schedule comes from the run's ``--seed`` through its own named stream, so
the same seed gives the same pool, order and features.
"""

from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional

import numpy as np

# Rows of the per-run feature bank: at most FEATURE_ROWS, and a power of
# two at least the number of requests. Request ``i`` reads its node
# features from the bank starting at row ``(i * FEATURE_STRIDE) % rows``;
# the stride is odd, so the first ``rows`` requests of a run all start at
# different rows and no two of them are equal.
FEATURE_ROWS = 1 << 21
FEATURE_STRIDE = 61


def stream(seed: int, purpose: str) -> np.random.Generator:
    """An independent generator for one use of the run's seed."""
    return np.random.default_rng(
        np.random.SeedSequence((int(seed), zlib.crc32(purpose.encode()))))


@dataclass(frozen=True)
class Structure:
    """One pool graph without its node features."""

    senders: np.ndarray         # (E,) int32
    receivers: np.ndarray       # (E,) int32
    edge_feat: np.ndarray       # (E, 3) float32
    node_pos: np.ndarray        # (N, 1) float32
    n_nodes: int

    @property
    def n_edges(self) -> int:
        return int(self.senders.shape[0])


# ---------------------------------------------------------------------------
# graph generators (copied from the program's data/graphs.py)
# ---------------------------------------------------------------------------

def _random_connected_graph(rng: np.random.Generator, n: int,
                            target_edges: int, node_dim: int, edge_dim: int):
    """Molecule-like sparse graph: random spanning tree + extra edges,
    symmetrized (undirected -> two directed edges), duplicate-free."""
    parents = np.array([rng.integers(0, i) for i in range(1, n)])
    src = np.concatenate([np.arange(1, n), parents])
    dst = np.concatenate([parents, np.arange(1, n)])
    pairs = set(zip(src.tolist(), dst.tolist()))
    n_extra = max(0, target_edges // 2 - (n - 1))
    tries = 0
    while n_extra > 0 and tries < 50 * n_extra:
        a, b = rng.integers(0, n, size=2)
        tries += 1
        if a == b or (int(a), int(b)) in pairs:
            continue
        pairs.add((int(a), int(b)))
        pairs.add((int(b), int(a)))
        n_extra -= 1
    arr = np.array(sorted(pairs), dtype=np.int32)
    senders, receivers = arr[:, 0], arr[:, 1]
    e = senders.shape[0]
    node_feat = rng.normal(size=(n, node_dim)).astype(np.float32)
    edge_feat = rng.normal(size=(e, edge_dim)).astype(np.float32)
    v = rng.normal(size=(n,)).astype(np.float32)
    deg = np.bincount(receivers, minlength=n).astype(np.float32) + 1.0
    for _ in range(3):
        agg = np.zeros(n, np.float32)
        np.add.at(agg, receivers, v[senders])
        v = agg / deg
        v = v - v.mean()
        v = v / (np.linalg.norm(v) + 1e-6)
    del node_feat   # drawn fresh per request instead
    return Structure(senders, receivers, edge_feat, v[:, None], n)


def molhiv_like(rng: np.random.Generator, n_graphs: int, node_dim: int = 9,
                edge_dim: int = 3) -> Iterator[Structure]:
    """OGB molhiv shapes: 25.3 +- 6 nodes, about 55.6 directed edges."""
    for _ in range(n_graphs):
        n = max(4, int(rng.normal(25.3, 6.0)))
        e = max(2 * (n - 1), int(rng.normal(55.6, 10.0)) // 2 * 2)
        yield _random_connected_graph(rng, n, e, node_dim, edge_dim)


def hep_like(rng: np.random.Generator, n_graphs: int, n_points: int = 49,
             k: int = 16, node_dim: int = 9, edge_dim: int = 3
             ) -> Iterator[Structure]:
    """EdgeConv-style kNN graphs over particle point clouds (k=16)."""
    del node_dim
    for _ in range(n_graphs):
        n = max(k + 1, int(rng.normal(n_points, 8.0)))
        pts = rng.normal(size=(n, 3)).astype(np.float32)
        d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
        np.fill_diagonal(d2, np.inf)
        nbr = np.argsort(d2, axis=1)[:, :k]
        receivers = np.repeat(np.arange(n, dtype=np.int32), k)
        senders = nbr.reshape(-1).astype(np.int32)
        edge_feat = rng.normal(size=(senders.shape[0], edge_dim)
                               ).astype(np.float32)
        v = pts[:, 0:1] - pts[:, 0:1].mean()
        yield Structure(senders, receivers, edge_feat, v, n)


GENERATORS = {"molhiv_like": molhiv_like, "hep_like": hep_like}


# ---------------------------------------------------------------------------
# a run's traffic
# ---------------------------------------------------------------------------

class Traffic:
    """The pool, the feature bank and the request order of one run.

    Request ``i`` is pool structure ``order[i]`` with node features read
    from the bank (see ``FEATURE_ROWS``). Open-loop mixes also carry
    ``due_s``: request ``i`` is due ``due_s[i]`` seconds after the start of
    the warm-up traffic, which lasts ``warm_s``; the measured window is
    ``[warm_s, warm_s + seconds)``.
    """

    def __init__(self, mix: Dict, seed: int, seconds: float,
                 node_dim: int = 9):
        self.mix = mix
        self.node_dim = node_dim
        gen = GENERATORS[mix["graphs"]]
        self.pool: List[Structure] = list(
            gen(stream(seed, "pool"), int(mix["pool"])))
        self.warm_s = float(mix["warm_s"])
        if mix["loop"] == "open":
            # one fixed Poisson schedule for every seed: where arrivals
            # cluster decides the latency tail, so a schedule drawn per
            # seed moved p90 by 50% from seed to seed; the seed still
            # draws the graphs, their order, features and weights
            self.due_s = poisson_arrivals(stream(0, "arrivals"),
                                          float(mix["rate_hz"]),
                                          self.warm_s + float(seconds))
            n_req = len(self.due_s)
            self.rows = min(FEATURE_ROWS, pad_pow2(n_req))
        elif mix["loop"] == "closed":
            self.due_s = None
            n_req = self.rows = FEATURE_ROWS
        else:
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.order = stream(seed, "order").integers(
            0, len(self.pool), size=n_req).astype(np.int32)
        self.bank = stream(seed, "features").standard_normal(
            (self.rows + max(s.n_nodes for s in self.pool), node_dim),
            dtype=np.float32)

    def __len__(self) -> int:
        return len(self.order)

    def structure(self, i: int) -> Structure:
        return self.pool[self.order[i]]

    def features(self, i: int) -> np.ndarray:
        s = self.structure(i)
        row = (i * FEATURE_STRIDE) % self.rows
        return self.bank[row:row + s.n_nodes]

    def request(self, i: int):
        """``GraphStreamEngine.submit``'s positional arguments."""
        s = self.structure(i)
        return (self.features(i), s.senders, s.receivers, s.edge_feat,
                s.node_pos)


def poisson_arrivals(rng: np.random.Generator, rate_hz: float,
                     duration_s: float) -> np.ndarray:
    """Homogeneous Poisson arrival times in ``[0, duration_s)``."""
    if rate_hz <= 0:
        raise ValueError("rate_hz must be > 0")
    # draw a little more than the expected count, extend if short
    out: List[np.ndarray] = []
    t = 0.0
    while True:
        n = int(rate_hz * duration_s * 1.1) + 16
        gaps = rng.exponential(1.0 / rate_hz, size=n)
        times = t + np.cumsum(gaps)
        out.append(times[times < duration_s])
        if times[-1] >= duration_s:
            return np.concatenate(out)
        t = float(times[-1])


def pad_pow2(n: int, least: int = 32) -> int:
    """Smallest power of two >= ``max(n, least)``."""
    return max(least, 1 << int(np.ceil(np.log2(max(n, 1)))))


def batch_buckets(max_batch: int, max_nodes: Optional[int],
                  max_edges: Optional[int], pool: List[Structure],
                  buckets) -> List[tuple]:
    """(node_pad, edge_pad) pairs the packer can seal for this traffic.

    At batch 1 these are the pool graphs' own buckets. Packed batches
    carry anything from one graph to ``max_batch`` (adaptive flushing
    seals partial batches whenever a device is idle), so every node bucket
    up to the node budget is paired with the edge buckets one, two and
    four times its size, up to the edge budget: a molecule has at least
    2(n - 1) and about 2.2 edges per node, so a batch lands at two or
    four, and a one- or two-graph batch of small molecules at one.
    """
    def pad(n):
        for b in buckets:
            if n <= b:
                return b
        return pad_pow2(n)

    if max_batch == 1:
        return sorted({(pad(s.n_nodes), pad(s.n_edges)) for s in pool})
    top_n = pad(max_nodes)
    top_e = pad(max_edges)
    pairs = []
    for nb in sorted(set(buckets) | {top_n}):
        if nb > top_n:
            continue
        for mult in (1, 2, 4):
            if nb * mult <= top_e:
                pairs.append((nb, pad(nb * mult)))
    return sorted(set(pairs))
