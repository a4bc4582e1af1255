"""The d-dimensional plain reference against its own brute-force form at d =
1, 2 and 3, and against the one-dimensional reference at d = 1.  (A file of
its own beside ``test_reference.py``: a PR that adds a reference adds its
tests and edits none.)"""

import numpy as np
import pytest

import manifest
import traffic


def _config(reference: str, n_dims: int) -> dict:
    return {"reference": reference,
            "config": {"distribution": "zipf", "num_sites": 8, "data_len": 12,
                       "n_dims": n_dims, "zipf_exponent": 1.03}}


@pytest.fixture(scope="module")
def ref():
    return manifest.reference(_config("linf_ball_nd", 2))


def _points(n_dims: int, seed: int, n: int = 300) -> np.ndarray:
    pts = traffic.client_points(_config("linf_ball_nd", n_dims), n, np.random.default_rng(seed))
    # a few points at the ends of the domain, where the ball saturates, one
    # of them in a corner that is low in one dimension and high in the next
    pts[:3] = False
    pts[3:6] = True
    pts[6, ::2] = False
    pts[6, 1::2] = True
    return pts


def test_plain_count_is_a_product_of_saturating_balls(ref):
    # 3-bit domain, ball 1, two dimensions: (0, 7) -> [0,1] x [6,7]; (3, 3) -> [2,4] x [2,4]
    pts = np.array([[[0, 0, 0], [1, 1, 1]], [[0, 1, 1], [0, 1, 1]]], bool)
    got = ref.plain_count(pts, 1, 3, 1)
    assert got == {**{(a, b): 1 for a in (0, 1) for b in (6, 7)},
                   **{(a, b): 1 for a in (2, 3, 4) for b in (2, 3, 4)}}
    assert ref.plain_count(pts, 1, 1, 1) == {(0, 1): 2, (0, 0): 1, (1, 0): 1, (1, 1): 1}
    assert ref.plain_count(pts, 1, 3, 2) == {}


@pytest.mark.parametrize("n_dims", [1, 2, 3])
@pytest.mark.parametrize("seed", [0, 2**31 + 5])
# 0: points alone; 2: narrower than most boxes; 9: wider than a box of the last four depths
@pytest.mark.parametrize("ball", [0, 2, 9])
def test_descent_equals_brute_force_at_every_depth(ref, n_dims, seed, ball):
    n = 300 if n_dims < 3 else 100  # the brute force visits (2 * ball + 1) ** n_dims boxes a point
    pts = _points(n_dims, seed, n)
    got = {thresh: ref.frontiers(pts, ball, thresh, 12) for thresh in (1, 9, 40)}
    for depth in range(1, 13):
        brute = ref.plain_count(pts, ball, depth, 1)
        for thresh, levels in got.items():
            assert levels[0] == {(0,) * n_dims: n}
            assert levels[depth] == {q: k for q, k in brute.items() if k >= thresh}, (depth, thresh)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
@pytest.mark.parametrize("ball", [0, 2, 5])
def test_one_dimension_is_the_one_dimensional_reference(ref, seed, ball):
    one = manifest.reference(_config("linf_ball_1d", 1))
    pts = _points(1, seed)
    for thresh in (1, 9, 40):
        want = [{(q,): k for q, k in level.items()} for level in one.frontiers(pts, ball, thresh, 12)]
        assert ref.frontiers(pts, ball, thresh, 12) == want
    paths = pts[:5, :, :7]
    got = ref.crawl_frontier(paths, range(5))
    want = one.crawl_frontier(paths, range(5))
    assert {k if k == "duplicate" else (k,): v for k, v in want.items()} == got


def test_a_crawls_frontier_is_keyed_by_the_prefix_of_each_dimension(ref):
    paths = np.array([[[0, 1], [1, 1]], [[1, 0], [0, 0]]], bool)
    assert ref.crawl_frontier(paths, [5, 7]) == {(1, 3): 5, (2, 0): 7}
    assert ref.crawl_frontier(None, None) == {}
    assert ref.crawl_frontier(np.zeros((0, 2, 4), bool), []) == {}
    twice = np.zeros((2, 2, 4), bool)
    assert ref.crawl_frontier(twice, [5, 5]) != {(0, 0): 5}
    assert ref.crawl_frontier(twice, [5, 5])["duplicate"] == 1


def test_the_reference_refuses_points_of_another_shape(ref):
    with pytest.raises(ValueError, match="bool\\[N, d, L\\]"):
        ref.frontiers(np.zeros((4, 8), bool), 1, 1, 2)
