"""The plain reference against its brute-force form, and the traffic
generator against the seed."""

import numpy as np
import pytest

import manifest
import traffic

CONFIG = {"reference": "linf_ball_1d",
          "config": {"distribution": "zipf", "num_sites": 8, "data_len": 16,
                     "n_dims": 1, "zipf_exponent": 1.03}}


@pytest.fixture(scope="module")
def ref():
    return manifest.reference(CONFIG)


def test_plain_count_is_a_saturating_ball_count(ref):
    # 3-bit domain, ball 1: points 0, 0, 7, 3 -> [0,1] x2, [6,7], [2,4]
    pts = np.array([[0, 0, 0], [0, 0, 0], [1, 1, 1], [0, 1, 1]], bool)[:, None, :]
    assert ref.plain_count(pts, 1, 3, 1) == {0: 2, 1: 2, 6: 1, 7: 1, 2: 1, 3: 1, 4: 1}
    assert ref.plain_count(pts, 1, 3, 2) == {0: 2, 1: 2}
    assert ref.plain_count(pts, 1, 1, 1) == {0: 3, 1: 2}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
@pytest.mark.parametrize("ball", [0, 2, 5])
def test_descent_equals_brute_force_at_every_depth(ref, seed, ball):
    pts = traffic.client_points(CONFIG, 300, np.random.default_rng(seed))
    # a few points at the ends of the domain, where the ball saturates
    pts[:3] = False
    pts[3:6] = True
    for thresh in (1, 9, 40):
        got = ref.frontiers(pts, ball, thresh, 16)
        for depth in range(1, 17):
            assert got[depth] == ref.plain_count(pts, ball, depth, thresh), (depth, thresh)


def test_a_duplicate_path_never_compares_equal(ref):
    paths = np.zeros((2, 1, 4), bool)
    assert ref.crawl_frontier(paths, [5, 5]) != {0: 5}
    assert ref.crawl_frontier(None, None) == {}


def test_points_are_a_function_of_the_seed_alone():
    a = traffic.client_points(CONFIG, 64, np.random.default_rng(2**31 + 9))
    b = traffic.client_points(CONFIG, 64, np.random.default_rng(2**31 + 9))
    c = traffic.client_points(CONFIG, 64, np.random.default_rng(1))
    assert a.shape == (64, 1, 16) and (a == b).all() and not (a == c).all()


def test_the_generator_is_the_programs_draw_for_draw():
    """The copy under ``benchmark/`` and the program's sampler agree today;
    the benchmark uses its own copy, so they may part later."""
    from fuzzyheavyhitters_tpu.utils.config import Config
    from fuzzyheavyhitters_tpu.workloads import sample_points

    c = CONFIG["config"]
    cfg = Config(data_len=c["data_len"], n_dims=1, ball_size=2, addkey_batch_size=8,
                 num_sites=c["num_sites"], threshold=0.1, zipf_exponent=c["zipf_exponent"],
                 server0="", server1="", distribution="zipf")
    want = sample_points(cfg, 100, np.random.default_rng(12))
    assert (traffic.client_points(CONFIG, 100, np.random.default_rng(12)) == want).all()
