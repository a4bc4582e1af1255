"""Plain reference for d-dimensional L-inf-ball heavy hitters.

Python ints for every bound, a sort and ``bisect`` a dimension; imports
nothing of the program and takes nothing the program made.  A client at point
``p = (p_0 .. p_{d-1})`` stands for the saturating ball ``[p_j - ball, p_j +
ball]`` in every dimension; a node of the crawl at ``depth`` is one
``depth``-bit prefix ``q_j`` a dimension and stands for the box of the points
whose coordinate ``j`` starts with ``q_j``; it counts every client whose ball
touches the box, which it does iff it does in every dimension: ``box_lo_j -
ball <= p_j <= box_hi_j + ball`` (the clamp at 0 and at the top moves neither
end of a ball out of the domain, so the ends need no clamping here).  A crawl
must hold, after ``depth`` levels, exactly the nodes whose count reaches the
threshold, with those counts.

``frontiers`` goes by descent: a child's box lies inside its parent's, so its
clients are a subset of its parent's and the nodes that survive at ``depth +
1`` are children of those that survive at ``depth``.  Each dimension's values
are sorted once; an interval of values is then a range of ranks, found by two
bisections of the sorted Python ints, and a node keeps the indices of its
clients (a numpy array: the index sets are the only thing numpy holds).
``plain_count`` is the brute-force form: every distinct point's ball, every
box it touches.  ``benchmark/tests/test_reference_nd.py`` holds the two equal,
and this file equal to ``linf_ball_1d.py`` at ``d = 1``.
"""

from __future__ import annotations

import bisect
import collections
import itertools

import numpy as np


def points_to_ints(bits: np.ndarray) -> list:
    """bool[N, d, L] MSB-first -> N tuples of d Python ints."""
    bits = np.asarray(bits, bool)
    if bits.ndim != 3:
        raise ValueError(f"points are bool[N, d, L], got shape {bits.shape}")
    n, d, length = bits.shape
    pad = (-length) % 8
    if pad:
        bits = np.pad(bits, ((0, 0), (0, 0), (pad, 0)))
    packed = np.packbits(bits, axis=2)
    return [
        tuple(int.from_bytes(packed[i, j].tobytes(), "big") for j in range(d))
        for i in range(n)
    ]


def plain_count(points: np.ndarray, ball: int, depth: int, thresh: int) -> dict:
    """{(prefix of each dimension): count} over every node at ``depth`` that
    at least ``thresh`` clients' balls touch.  Brute force over the distinct
    points."""
    length = points.shape[-1]
    top = (1 << length) - 1
    shift = length - depth
    counts = collections.Counter()
    for p, k in collections.Counter(points_to_ints(points)).items():
        spans = [
            range(max(0, v - ball) >> shift, (min(top, v + ball) >> shift) + 1)
            for v in p
        ]
        for q in itertools.product(*spans):
            counts[q] += k
    return {q: k for q, k in counts.items() if k >= thresh}


def frontiers(points: np.ndarray, ball: int, thresh: int, max_depth: int) -> list:
    """``out[depth]`` = {(prefix of each dimension): count} a crawl must hold
    after ``depth`` levels, for depth 1..max_depth (``out[0]`` is the root,
    unthresholded)."""
    pts = points_to_ints(points)
    n, d, length = np.asarray(points).shape
    order = [sorted(range(n), key=lambda i, j=j: pts[i][j]) for j in range(d)]
    vals = [[pts[i][j] for i in order[j]] for j in range(d)]  # sorted Python ints
    rank = []  # rank[j][client]: where the client stands in dimension j's order
    for j in range(d):
        r = np.empty(n, np.int64)
        r[np.asarray(order[j], np.int64)] = np.arange(n)
        rank.append(r)

    def ranks_within(j: int, lo: int, hi: int) -> tuple:
        return bisect.bisect_left(vals[j], lo), bisect.bisect_right(vals[j], hi)

    out = [{(0,) * d: n}]
    held = {(0,) * d: np.arange(n)}  # node -> indices of the clients that touch it
    for depth in range(1, max_depth + 1):
        shift = length - depth
        level, below = {}, {}
        for parent, idx in held.items():
            halves = []  # per dimension: the two half-boxes' clients among idx
            for j in range(d):
                r = rank[j][idx]
                side = []
                for q in (2 * parent[j], 2 * parent[j] + 1):
                    a, b = ranks_within(j, (q << shift) - ball, ((q + 1) << shift) - 1 + ball)
                    side.append((q, (r >= a) & (r < b)))
                halves.append(side)
            for child in itertools.product(*halves):
                mask = child[0][1]
                for _, m in child[1:]:
                    mask = mask & m
                k = int(mask.sum())
                if k >= thresh:
                    q = tuple(q for q, _ in child)
                    level[q], below[q] = k, idx[mask]
        out.append(level)
        held = below
    return out


def crawl_frontier(paths, counts) -> dict:
    """What the crawl held: (paths bool[H, d, depth], counts[H]) ->
    {(prefix of each dimension): count}.  A duplicate path is an error of the
    crawl: it is returned under the key ``"duplicate"`` so that the
    comparison fails."""
    if paths is None or len(paths) == 0:
        return {}
    nodes = points_to_ints(np.asarray(paths, bool))
    out = dict(zip(nodes, (int(c) for c in counts)))
    if len(out) != len(nodes):
        out["duplicate"] = len(nodes) - len(out)
    return out
