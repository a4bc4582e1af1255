"""The one general generator: client points from a configuration and a seed,
and the plan of warm-up, window and capture from a traffic mix's data file.

Each client simulation is a copy of the program's, so that a later change to
the program's sampler cannot change what a cell is asked to do, and every
draw comes from the run's ``rng`` (``--seed``), draw for draw as the program
draws them from its own generator:

- ``zipf``: ``fuzzyheavyhitters_tpu/workloads/strings.py`` + ``AUG_LEN`` of
  ``workloads/__init__.py`` (ref: leader.rs:38-66, 130-151): sites,
  popularity and low bits;
- ``rides``: ``workloads/rides.synthetic_austin_locations`` +
  ``utils/bits.i16_to_ob_bits`` (ref: sample_driving_data.rs; the RideAustin
  CSV is in neither tree, the clustered stand-in is the program's own), with
  the hotspots held to those of the one seed the program runs
  (``RIDES_HOTSPOTS``): the seed draws the clients, not the city;
- ``covid``: the branch of ``workloads/covid.sample_covid_locations`` that
  runs where the 9 GB case file is absent, as it is from the reference's
  tree (ref: sample_covid_data.rs), over ``benchmark/data/county_centroids.csv``,
  a copy of the shipped ``data/county_centroids.csv``.

The originals are listed in PERF.md; ``benchmark/tests/test_traffic.py`` holds
each copy to its original.
"""

from __future__ import annotations

import csv
import dataclasses
import os
import string

import numpy as np

AUG_LEN = 8  # per-request augmentation bits of the zipf workload (leader.rs:331)

_ALNUM = np.frombuffer(
    (string.ascii_uppercase + string.ascii_lowercase + string.digits).encode(),
    dtype=np.uint8,
)


def _string_bits(rng: np.random.Generator, nbits: int) -> np.ndarray:
    """Random alphanumeric string of ``nbits // 8`` chars as per-byte
    LSB-first bits, truncated to ``nbits``."""
    chars = rng.choice(_ALNUM, size=(nbits + 7) // 8)
    bits = np.unpackbits(chars[:, None], axis=1, bitorder="little").reshape(-1)
    return bits[:nbits].astype(bool)


def _sites(rng: np.random.Generator, num_sites: int, nbits: int, n_dims: int) -> np.ndarray:
    return np.stack([
        np.stack([_string_bits(rng, nbits) for _ in range(n_dims)])
        for _ in range(num_sites)
    ])


def _augment(rng: np.random.Generator, base: np.ndarray) -> np.ndarray:
    """``AUG_LEN`` random low bits a request, appended to its site string."""
    n, d, _ = base.shape
    nchars = (AUG_LEN + 7) // 8
    chars = rng.choice(_ALNUM, size=(n, d, nchars))
    aug = np.unpackbits(chars[..., None], axis=-1, bitorder="little")
    aug = aug.reshape(n, d, nchars * 8)[..., :AUG_LEN].astype(bool)
    return np.concatenate([base, aug], axis=-1)


def zipf_points(rng: np.random.Generator, *, num_sites: int, data_len: int,
                n_dims: int, zipf_exponent: float, clients: int) -> np.ndarray:
    """bool[clients, n_dims, data_len]: ``num_sites`` random site strings a
    dimension, bounded-zipf site popularity (P(k) ~ 1/(k+1)^exponent), and
    ``AUG_LEN`` random low bits a request, everything drawn from ``rng``."""
    w = 1.0 / np.arange(1, num_sites + 1, dtype=np.float64) ** zipf_exponent
    w /= w.sum()
    sites = _sites(rng, num_sites, data_len - AUG_LEN, n_dims)
    idx = rng.choice(num_sites, size=clients, p=w)
    return _augment(rng, sites[idx])


def _geo_only(name: str, data_len: int, n_dims: int, want_len: int) -> None:
    if (data_len, n_dims) != (want_len, 2):
        raise ValueError(
            f"{name} points are (latitude, longitude) of {want_len} bits each: "
            f"data_len {data_len}, n_dims {n_dims}")


def _msb_first(words: np.ndarray) -> np.ndarray:
    """Whole-byte words [...] -> bool[..., bits of a word], the most
    significant bit first."""
    big = words.astype(words.dtype.newbyteorder(">"))
    return np.unpackbits(big.view(np.uint8).reshape(*words.shape, -1), axis=-1).astype(bool)


RIDES_CENTRE = (3026, -9774)  # downtown Austin (30.26, -97.74) in centidegrees
# ONE city, whatever the seed.  The reference samples one data set (the
# RideAustin CSV, sample_driving_data.rs) and the program's stand-in is one
# city too: ``workloads.sample_points`` seeds its sampler with 42 on every run,
# so these six hotspots, that sampler's first draw, are the ones ``bin/leader``
# is served with: (2976, -9742), (3044, -9782), (3017, -9731), (2976, -9751),
# (2990, -9823), (3029, -9717).  Where they fall against the prefix grid
# decides the frontier of every level, so a city a seed would be other WORK a
# seed (58 to 88 bucket-units a crawl over twelve seeds at N = 131,072;
# PERF.md section 6, PR 44); the clients are the seed's.
RIDES_HOTSPOTS = np.array(RIDES_CENTRE) + np.random.default_rng(42).integers(-60, 60, size=(6, 2))


def rides_points(rng: np.random.Generator, *, data_len: int, n_dims: int,
                 clients: int, **_) -> np.ndarray:
    """bool[clients, 2, 16]: pickups clustered on the six ``RIDES_HOTSPOTS``
    (within 0.60 degrees of downtown Austin), a pickup its hotspot, drawn
    from ``rng``, plus a rounded normal of one centidegree (some 1.1 km), as
    i16 centidegrees in offset binary (the sign bit flipped, so that the
    strings sort as the values do), MSB first."""
    _geo_only("rides", data_len, n_dims, 16)
    hot = RIDES_HOTSPOTS
    # the program draws its hotspots first; the run's generator makes that
    # draw too and the result is not used, so that the stream stands where the
    # program's does: at seed 42 the clients are the program's point for point
    rng.integers(-60, 60, size=hot.shape)
    idx = rng.integers(0, len(hot), size=clients)
    pts = hot[idx] + rng.normal(0, 1.0, size=(clients, 2)).round().astype(int)
    pts = np.clip(pts, -32768, 32767).astype(np.int16)
    return _msb_first(pts.view(np.uint16) ^ np.uint16(0x8000))


CENTROIDS_CSV = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "county_centroids.csv")
KM_PER_DEGREE = 111.32


def _centroids() -> np.ndarray:
    """float64[counties, 2] (latitude, longitude), in the order of the FIPS
    codes (the file begins with a UTF-8 BOM; a code met twice keeps its last
    row, as the program's dict does)."""
    by_fips = {}
    with open(CENTROIDS_CSV, newline="", encoding="utf-8-sig") as f:
        for row in csv.DictReader(f):
            by_fips[row["fips_code"]] = (float(row["latitude"]), float(row["longitude"]))
    return np.array([by_fips[k] for k in sorted(by_fips)], dtype=np.float64)


def covid_points(rng: np.random.Generator, *, data_len: int, n_dims: int,
                 clients: int, **_) -> np.ndarray:
    """bool[clients, 2, 64]: a county drawn uniformly with replacement for
    each client, its centroid moved uniformly inside a square of ``AUG_LEN``
    km a side at that latitude, each coordinate as the 64 IEEE-754 bits of
    its f64, MSB first (the reference's tree domain for this workload is the
    raw float bit pattern).  One ``rng.uniform`` over ``[clients, 2]`` draws
    what the program draws client by client, latitude then longitude."""
    _geo_only("covid", data_len, n_dims, 64)
    centroids = _centroids()
    half_km = AUG_LEN / 2.0
    # a county's half sides in degrees, by the program's own scalar
    # expressions (an array cosine may round its last bit another way)
    half = np.array([(half_km / KM_PER_DEGREE,
                      half_km / (KM_PER_DEGREE * np.cos(np.radians(lat))))
                     for lat, _ in centroids.tolist()])
    take = rng.choice(len(centroids), size=clients, replace=True)
    moved = centroids[take] + rng.uniform(-half[take], half[take])
    moved = np.clip(moved, (-90.0, -180.0), (90.0, 180.0))
    return _msb_first(moved)


DISTRIBUTIONS = {"zipf": zipf_points, "rides": rides_points, "covid": covid_points}


def client_points(config: dict, clients: int, rng: np.random.Generator) -> np.ndarray:
    """The cell's clients, from the configuration's ``config`` group."""
    c = config["config"]
    try:
        draw = DISTRIBUTIONS[c["distribution"]]
    except KeyError:
        raise ValueError(
            f"no generator for distribution {c['distribution']!r}: "
            f"have {sorted(DISTRIBUTIONS)}"
        ) from None
    return draw(rng, num_sites=c["num_sites"], data_len=c["data_len"],
                n_dims=c["n_dims"], zipf_exponent=c["zipf_exponent"],
                clients=clients)


CLOSE_ON = ("level", "crawl")


@dataclasses.dataclass(frozen=True)
class Plan:
    """A traffic mix of kind ``crawl_levels``, as its data file gives it."""

    steady_levels: int
    min_levels: int
    max_levels: int
    restart_when_crawl_ends: bool
    close_on: str  # where the window's clock stops, one of CLOSE_ON
    tail_max_s: float  # 0: no tail
    trace_start_after_s: float
    trace_capture_s: float


def plan(mix: dict) -> Plan:
    if mix.get("kind") != "crawl_levels":
        raise ValueError(f"traffic kind {mix.get('kind')!r}: only 'crawl_levels' is generated")
    w, win, t = mix["warmup"], mix["window"], mix["trace"]
    close_on = win.get("close_on", "level")
    if close_on not in CLOSE_ON:
        raise ValueError(f"window.close_on {close_on!r}: one of {list(CLOSE_ON)}")
    return Plan(
        steady_levels=int(w["steady_levels"]), min_levels=int(w["min_levels"]),
        max_levels=int(w["max_levels"]),
        restart_when_crawl_ends=bool(win["restart_when_crawl_ends"]),
        close_on=close_on,
        tail_max_s=float(mix.get("tail", {}).get("max_s", 0.0)),
        trace_start_after_s=float(t["start_after_s"]),
        trace_capture_s=float(t["capture_s"]),
    )
