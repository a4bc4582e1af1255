"""The one general generator: client points from a configuration and a seed,
and the plan of warm-up, window and capture from a traffic mix's data file.

The zipf client simulation is a copy of the program's
(``fuzzyheavyhitters_tpu/workloads/strings.py`` + ``AUG_LEN`` of
``workloads/__init__.py``; ref: leader.rs:38-66, 130-151), so that a later
change to the program's sampler cannot change what a cell is asked to do:
sites, popularity and low bits are all drawn from ``--seed``, draw for draw as
the program draws them.  The original is listed in PERF.md.
"""

from __future__ import annotations

import dataclasses
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


DISTRIBUTIONS = {"zipf": zipf_points}


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


@dataclasses.dataclass(frozen=True)
class Plan:
    """A traffic mix of kind ``crawl_levels``, as its data file gives it."""

    steady_levels: int
    min_levels: int
    max_levels: int
    restart_when_crawl_ends: bool
    tail_max_s: float  # 0: no tail
    trace_start_after_s: float
    trace_capture_s: float


def plan(mix: dict) -> Plan:
    if mix.get("kind") != "crawl_levels":
        raise ValueError(f"traffic kind {mix.get('kind')!r}: only 'crawl_levels' is generated")
    w, t = mix["warmup"], mix["trace"]
    return Plan(
        steady_levels=int(w["steady_levels"]), min_levels=int(w["min_levels"]),
        max_levels=int(w["max_levels"]),
        restart_when_crawl_ends=bool(mix["window"]["restart_when_crawl_ends"]),
        tail_max_s=float(mix.get("tail", {}).get("max_s", 0.0)),
        trace_start_after_s=float(t["start_after_s"]),
        trace_capture_s=float(t["capture_s"]),
    )
