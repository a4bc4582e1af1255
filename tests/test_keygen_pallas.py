"""Fused Pallas keygen vs the NumPy mirror — bit-exact on the real chip.

Runs only where a TPU backend resolved (the Mosaic kernel has no CPU
compile path and interpret mode is orders of magnitude too slow for CI).
Tier-1 pins the CPU platform (conftest), so this module skips there: the
kernel's Mosaic compile is pinned by tests/test_chip_compile.py and its
output by chip_smoke.py on the chip.
"""

import numpy as np
import pytest

import jax


from conftest import has_tpu as _has_tpu


pytestmark = pytest.mark.skipif(not _has_tpu(), reason="needs a TPU backend")


@pytest.mark.parametrize("derived", [False, True])
def test_pallas_keygen_bit_exact(rng, derived):
    from fuzzyheavyhitters_tpu.ops import ibdcf, keygen_pallas

    N, L = 700, 9  # exercises client padding (700 % 1024) and level padding
    seeds = rng.integers(0, 2**32, size=(N, 2, 4), dtype=np.uint32)
    alpha = rng.integers(0, 2, size=(N, L)).astype(bool)
    side = rng.integers(0, 2, size=N).astype(bool)
    w0, w1 = ibdcf.gen_pair_np(seeds, alpha, side, derived_bits=derived)
    g0, g1 = keygen_pallas.gen_pair_pallas(
        seeds, alpha, side, derived_bits=derived
    )
    for want, got in ((w0, g0), (w1, g1)):
        np.testing.assert_array_equal(np.asarray(got.cw_seed), want.cw_seed)
        np.testing.assert_array_equal(np.asarray(got.cw_bits), want.cw_bits)
        np.testing.assert_array_equal(np.asarray(got.cw_y_bits), want.cw_y_bits)
        np.testing.assert_array_equal(np.asarray(got.root_seed), want.root_seed)


def test_pallas_engine_selectable(rng):
    from fuzzyheavyhitters_tpu.ops import ibdcf

    pts = rng.integers(0, 2, size=(5, 1, 9)).astype(bool)
    # identical rng streams -> identical seeds -> the engines must agree
    k0, _ = ibdcf.gen_l_inf_ball(pts, 1, np.random.default_rng(42), engine="pallas")
    w0, _ = ibdcf.gen_l_inf_ball(pts, 1, np.random.default_rng(42), engine="np")
    np.testing.assert_array_equal(np.asarray(k0.cw_seed), np.asarray(w0.cw_seed))
    np.testing.assert_array_equal(np.asarray(k0.cw_bits), np.asarray(w0.cw_bits))


@pytest.mark.parametrize("planar_engine", [False, True])
def test_reexpand_advance_matches_cache_advance(rng, planar_engine, monkeypatch):
    """The re-expanding fallback `collect.advance` (rpc.py's prune-without-
    crawl path) produces the same frontier as the cache-gather advance, in
    BOTH engine layouts — the fallback's layout conversions are pinned here
    (its former Pallas eval kernel was retired in round 5; git history has
    it)."""
    import jax.numpy as jnp

    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import collect

    monkeypatch.setattr(collect, "EXPAND_PALLAS", planar_engine)
    n, d, L, F = 300, 2, 8, 4
    pts = rng.integers(0, 2, size=(n, d, L)).astype(bool)
    k0, _ = ibdcf.gen_l_inf_ball(pts, 1, rng, engine="np")
    f = collect.tree_init(k0, F)
    parent = jnp.asarray(np.array([0, 2, 1, 0], np.int32))
    pat = jnp.asarray(rng.integers(0, 2, size=(F, d)).astype(bool))
    _, ch = collect.expand_share_bits(k0, f, 0)
    a = collect.advance_from_children(ch, parent, pat, 3)
    b = collect.advance(k0, f, 0, parent, pat, 3)
    for name in ("seed", "bit", "y_bit"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a.states, name)),
            np.asarray(getattr(b.states, name)),
        )
    np.testing.assert_array_equal(np.asarray(a.alive), np.asarray(b.alive))
