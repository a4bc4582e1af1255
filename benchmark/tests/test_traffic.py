"""The harness's samplers held to their originals in the program: each is a
copy, so that a later change to the program's sampler cannot change what a
cell is asked to do, and this file is where a copy that has drifted shows.
Every draw comes from the generator handed in (``--seed``)."""

import hashlib
import json
import os

import numpy as np
import pytest

import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _group(name: str) -> dict:
    """A shipped configuration of the reference (``configs/<name>.json``) as
    the ``config`` group a benchmark configuration would hold."""
    with open(os.path.join(ROOT, "configs", f"{name}.json"), encoding="utf-8") as f:
        return {"config": json.load(f)}


def _covid_group() -> dict:
    return {"config": dict(_group("amazon")["config"], distribution="covid")}


def test_rides_points_are_the_programs():
    """``workloads.sample_points`` seeds its rides sampler with 42 itself,
    whatever generator it is handed."""
    from fuzzyheavyhitters_tpu import workloads
    from fuzzyheavyhitters_tpu.utils.config import Config

    conf = _group("config")
    assert conf["config"]["distribution"] == "rides"
    want = workloads.sample_points(Config(**conf["config"]), 512, np.random.default_rng(0))
    got = traffic.client_points(conf, 512, np.random.default_rng(42))
    assert got.dtype == np.bool_ and got.shape == (512, 2, 16)
    assert np.array_equal(got, want)


def _centidegrees(points: np.ndarray) -> np.ndarray:
    """bool[N, 2, 16] offset binary, MSB first -> int[N, 2] centidegrees."""
    return np.packbits(points, axis=-1).view(">u2")[..., 0].astype(int) - 0x8000


def test_the_rides_hotspots_are_the_programs_own():
    """The one city is the one ``bin/leader`` is served with: the program's
    stand-in at the seed ``workloads.sample_points`` gives it, 42."""
    from fuzzyheavyhitters_tpu.workloads import rides

    hot = traffic.RIDES_HOTSPOTS
    assert hot.shape == (6, 2)
    # what the program's sampler clusters around: every hotspot is the most
    # frequent point of its neighbourhood, and no client lies far from all six
    theirs = rides.synthetic_austin_locations(8192, seed=42).astype(int)
    near = np.abs(theirs[:, None, :] - hot[None]).max(axis=-1)
    assert (near.min(axis=1) <= 5).all()
    for k, h in enumerate(hot):
        mine = theirs[near.argmin(axis=1) == k]
        vals, counts = np.unique(mine, axis=0, return_counts=True)
        assert np.array_equal(vals[counts.argmax()], h)


def test_rides_points_are_one_city_whatever_the_seed():
    """The seed draws the clients (which hotspot, the jitter), never the
    geography."""
    hot = traffic.RIDES_HOTSPOTS
    draws = [_centidegrees(traffic.client_points(_group("config"), 4096, np.random.default_rng(seed)))
             for seed in (0, 1, 2, 3, 42, 97531, 2**31 + 5, 2**32 + 1)]
    for pts in draws:
        near = np.abs(pts[:, None, :] - hot[None]).max(axis=-1)
        assert (near.min(axis=1) <= 5).all()
        assert len(set(near.argmin(axis=1).tolist())) == 6
    assert not np.array_equal(draws[0], draws[1])


def test_the_rides_frontier_is_the_same_work_on_every_seed():
    """What the fixed city is for: the nodes alive at every depth of the
    shipped rides deployment (ball 1, threshold 0.075), and so the bucket of
    every level of a crawl, do not depend on the seed."""
    import manifest

    ref = manifest.reference({"reference": "linf_ball_nd"})
    group, n = _group("config"), 32768
    ball, thresh = group["config"]["ball_size"], int(group["config"]["threshold"] * n)
    alive = {
        tuple(len(f) for f in ref.frontiers(
            traffic.client_points(group, n, np.random.default_rng(seed)), ball, thresh, 16))
        for seed in (0, 1, 2, 42, 2**31 + 5, 2**32 + 1)}
    assert len(alive) == 1
    by_depth = alive.pop()
    assert by_depth[1] == 1 and by_depth[16] == 54  # nine leaf boxes a hotspot


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_covid_points_are_the_programs(seed, tmp_path):
    """Against the program's sampler where the 9 GB case file is absent, on
    the shipped centroids: the one vectorised draw is the program's client
    by client, and the scalar half sides are its own to the bit."""
    from fuzzyheavyhitters_tpu import workloads
    from fuzzyheavyhitters_tpu.workloads import covid

    want = covid.sample_covid_locations(
        str(tmp_path / "absent.csv"), os.path.join(ROOT, workloads.CENTROIDS_CSV), 700,
        fuzz_factor=float(workloads.AUG_LEN), seed=seed)
    got = traffic.client_points(_covid_group(), 700, np.random.default_rng(seed))
    assert got.dtype == np.bool_ and got.shape == (700, 2, 64)
    assert np.array_equal(got, want)
    # the strings are f64s in the counties' range (American Samoa to Alaska,
    # the Aleutians to Guam)
    lat = np.packbits(got[:, 0], axis=-1).view(">f8")[:, 0]
    lon = np.packbits(got[:, 1], axis=-1).view(">f8")[:, 0]
    assert (-15 < lat).all() and (lat < 70).all() and (-172 < lon).all() and (lon < 146).all()


def test_the_centroids_are_the_shipped_file():
    from fuzzyheavyhitters_tpu import workloads

    with open(traffic.CENTROIDS_CSV, "rb") as mine, \
            open(os.path.join(ROOT, workloads.CENTROIDS_CSV), "rb") as shipped:
        assert mine.read() == shipped.read()
    assert traffic.AUG_LEN == workloads.AUG_LEN


@pytest.mark.parametrize("distribution, group", [("rides", _group("config")),
                                                 ("covid", _covid_group())])
def test_geo_points_come_from_the_seed_alone(distribution, group):
    assert group["config"]["distribution"] == distribution
    draw = lambda seed: traffic.client_points(group, 300, np.random.default_rng(seed))
    assert np.array_equal(draw(5), draw(5))
    assert not np.array_equal(draw(5), draw(6))
    for key, wrong in (("data_len", 32), ("n_dims", 1)):
        bad = {"config": dict(group["config"], **{key: wrong})}
        with pytest.raises(ValueError, match=distribution):
            traffic.client_points(bad, 8, np.random.default_rng(0))


def test_an_unknown_distribution_is_refused():
    bad = {"config": dict(_group("config")["config"], distribution="taxi")}
    with pytest.raises(ValueError, match="covid.*rides.*zipf"):
        traffic.client_points(bad, 8, np.random.default_rng(0))


# sha256 of ``np.packbits(points)`` as the PARENT's ``traffic.py`` (d6ba21e,
# zipf alone) drew them at N = 256: the five cells are asked what they were
@pytest.mark.parametrize("config, seed, shape, digest", [
    ("zipf-flagship-trusted", 3, (256, 1, 512),
     "2ceb1745c1f529b4565cb48a691d47058cf39aa3014af227f388f56dde8fa24e"),
    ("zipf-flagship-trusted", 2**31 + 11, (256, 1, 512),
     "bff2f9e1289e7fc6c2e1fb7c0c2ef9bd5536bb3ead0c3c286fa37b565e397fe7"),
    ("amazon-zipf-2d", 3, (256, 2, 64),
     "acbd54194cd5bbc1507e146c91af0550cec1e168008c7771d65ae2b423d1062a"),
    ("amazon-zipf-2d", 2**31 + 11, (256, 2, 64),
     "aac40212eed5dbbfcf95cde36dbf665b8c799c71a0f04265ea5107bf98231416"),
])
def test_zipf_points_are_what_they_were(config, seed, shape, digest):
    with open(os.path.join(BENCH, "configs", f"{config}.json"), encoding="utf-8") as f:
        conf = json.load(f)
    pts = traffic.client_points(conf, 256, np.random.default_rng(seed))
    assert pts.shape == shape
    assert hashlib.sha256(np.packbits(pts).tobytes()).hexdigest() == digest
