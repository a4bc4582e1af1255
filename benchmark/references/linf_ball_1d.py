"""Plain reference for one-dimensional L-inf-ball heavy hitters.

Python ints, a sort and ``bisect``; imports nothing of the program and takes
nothing the program made.  A client at point ``p`` stands for the saturating
ball ``[p - ball, p + ball]``; a ``depth``-bit prefix ``q`` counts every client
whose ball touches the box ``[q << (L - depth), ((q + 1) << (L - depth)) - 1]``;
a crawl must hold, after ``depth`` levels, exactly the prefixes whose count
reaches the threshold, with those counts.

``plain_count`` is the brute-force form (copied from ``chip_smoke.py``, PR 25):
every distinct point's ball, every prefix it touches.  At N=131072, L=512 it
takes about 0.1 s a depth, longer than the window for the ~400 depths a window
reaches, so a run compares against ``frontiers``: the same counts by descent.
Counts only shrink down the tree, so the prefixes that survive at ``depth + 1``
are children of those that survive at ``depth``; a ball touches a box iff
``box_lo - ball <= p <= box_hi + ball`` (the clamp at 0 and at the top moves
neither end out of the domain), which two bisections of the sorted points
count.  ``tests/test_reference.py`` holds the two forms equal.
"""

from __future__ import annotations

import bisect
import collections

import numpy as np


def points_to_ints(bits: np.ndarray) -> list:
    """bool[N, 1, L] MSB-first (or bool[N, L]) -> Python ints."""
    bits = np.asarray(bits, bool)
    if bits.ndim == 3:
        if bits.shape[1] != 1:
            raise ValueError(f"one-dimensional reference, got n_dims={bits.shape[1]}")
        bits = bits[:, 0, :]
    pad = (-bits.shape[1]) % 8
    if pad:
        bits = np.pad(bits, ((0, 0), (pad, 0)))
    return [int.from_bytes(r.tobytes(), "big") for r in np.packbits(bits, axis=1)]


def plain_count(points: np.ndarray, ball: int, depth: int, thresh: int) -> dict:
    """{prefix: count} over every ``depth``-bit prefix that at least ``thresh``
    clients' balls touch.  Brute force over the distinct points."""
    L = points.shape[-1]
    top = (1 << L) - 1
    shift = L - depth
    counts = collections.Counter()
    for v, k in collections.Counter(points_to_ints(points)).items():
        lo, hi = max(0, v - ball) >> shift, min(top, v + ball) >> shift
        for q in range(lo, hi + 1):
            counts[q] += k
    return {q: k for q, k in counts.items() if k >= thresh}


def frontiers(points: np.ndarray, ball: int, thresh: int, max_depth: int) -> list:
    """``out[depth]`` = {prefix: count} a crawl must hold after ``depth``
    levels, for depth 1..max_depth (``out[0]`` is the root, unthresholded)."""
    L = points.shape[-1]
    vals = sorted(points_to_ints(points))
    out = [{0: len(vals)}]
    for depth in range(1, max_depth + 1):
        shift = L - depth
        level = {}
        for parent in out[-1]:
            for q in (2 * parent, 2 * parent + 1):
                lo = (q << shift) - ball
                hi = ((q + 1) << shift) - 1 + ball
                k = bisect.bisect_right(vals, hi) - bisect.bisect_left(vals, lo)
                if k >= thresh:
                    level[q] = k
        out.append(level)
    return out


def crawl_frontier(paths, counts) -> dict:
    """What the crawl held: (paths bool[H, 1, depth], counts[H]) ->
    {prefix: count}.  A duplicate path is an error of the crawl: it is
    returned under the key ``"duplicate"`` so that the comparison fails."""
    if paths is None or len(paths) == 0:
        return {}
    vals = points_to_ints(np.asarray(paths, bool))
    out = dict(zip(vals, (int(c) for c in counts)))
    if len(out) != len(vals):
        out["duplicate"] = len(vals) - len(out)
    return out
