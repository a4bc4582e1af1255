"""Fused Pallas frontier expansion vs the XLA form — bit-exact on the
real chip (same TPU-only gating rationale as test_keygen_pallas.py).

The plane-major pack-in-kernel engine (ops/expand_pallas.py) is the
DEFAULT on real chips, so this parity test pins the whole pipeline —
packed share bits, child cache, gather-advance — against the XLA engine
at every step of a small crawl, in both PRG bit modes.  The shapes are
deliberately NOT multiples of the kernel group so the padded/broadcast
cw fallback path is the one under test; the N-periodic index-map path is
exercised by test_periodic_cw_path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


from conftest import has_tpu as _has_tpu


pytestmark = pytest.mark.skipif(not _has_tpu(), reason="needs a TPU backend")


def _seed_to_xla(planar):  # [4, d, 2, F, N] -> [F, N, d, 2, 4]
    return np.transpose(np.asarray(planar), (3, 4, 1, 2, 0))


def _bits_to_xla(planar):  # [d, 2, F, N] -> [F, N, d, 2]
    return np.transpose(np.asarray(planar), (2, 3, 0, 1))


def _check_children(ch_x, ch_p):
    """XLA EvalState cache vs PlanarChildren: same child states."""
    fl = np.asarray(ch_p.flags)
    for dir_, names in enumerate(
        [("bit", 0, "y_bit", 2), ("bit", 1, "y_bit", 3)]
    ):
        bname, bshift, yname, yshift = names
        np.testing.assert_array_equal(
            np.asarray(getattr(ch_x, bname))[..., dir_],
            _bits_to_xla((fl >> bshift) & 1) != 0,
        )
        np.testing.assert_array_equal(
            np.asarray(getattr(ch_x, yname))[..., dir_],
            _bits_to_xla((fl >> yshift) & 1) != 0,
        )
    # seed: planar [2, 4, d, 2, F, N] -> XLA [F, N, d, 2, dir, 4]
    sp = np.transpose(np.asarray(ch_p.seed), (4, 5, 2, 3, 0, 1))
    np.testing.assert_array_equal(np.asarray(ch_x.seed), sp)


@pytest.mark.parametrize("derived", [False, True])
def test_planar_engine_bit_exact(rng, derived):
    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import collect

    L, n, d = 12, 300, 2  # n*F not a multiple of the kernel group
    pts = rng.integers(0, 1 << L, size=(n, d))
    pts_bits = ((pts[..., None] >> np.arange(L - 1, -1, -1)) & 1) > 0
    k0, _ = ibdcf.gen_l_inf_ball(pts_bits, 3, rng, engine="np")
    f_x = collect.tree_init(k0, 4, planar=False)
    f_p = collect.tree_init(k0, 4, planar=True)
    np.testing.assert_array_equal(
        _seed_to_xla(f_p.states.seed), np.asarray(f_x.states.seed)
    )
    parent = jnp.asarray(np.array([0, 1, 3, 0], np.int32))
    pat = jnp.asarray(rng.integers(0, 2, size=(4, d)).astype(bool))
    for lvl in (0, 7):
        p_x, ch_x = collect._expand_share_bits_jit(k0, f_x, lvl, derived, True, False)
        p_p, ch_p = collect._expand_share_bits_jit(k0, f_p, lvl, derived, True, True)
        np.testing.assert_array_equal(np.asarray(p_x), np.asarray(p_p))
        _check_children(ch_x, ch_p)
        a_x = collect._advance_children_jit(ch_x, parent, pat, 3, planar=False)
        a_p = collect._advance_children_jit(ch_p, parent, pat, 3, planar=True)
        np.testing.assert_array_equal(
            np.asarray(a_x.states.seed), _seed_to_xla(a_p.states.seed)
        )
        np.testing.assert_array_equal(
            np.asarray(a_x.states.bit), _bits_to_xla(a_p.states.bit)
        )
        np.testing.assert_array_equal(
            np.asarray(a_x.states.y_bit), _bits_to_xla(a_p.states.y_bit)
        )
        np.testing.assert_array_equal(np.asarray(a_x.alive), np.asarray(a_p.alive))
        f_x, f_p = a_x, a_p  # crawl on from the advanced frontiers


def test_last_level_packed_only(rng):
    """want_children=False (the last level) returns identical packed bits
    and no cache on both engines."""
    from fuzzyheavyhitters_tpu.ops import ibdcf
    from fuzzyheavyhitters_tpu.protocol import collect

    L, n = 10, 257
    pts = rng.integers(0, 2, size=(n, 1, L)).astype(bool)
    k0, _ = ibdcf.gen_l_inf_ball(pts, 2, rng, engine="np")
    f_x = collect.tree_init(k0, 2, planar=False)
    f_p = collect.tree_init(k0, 2, planar=True)
    p_x, ch_x = collect._expand_share_bits_jit(k0, f_x, 3, False, False, False)
    p_p, ch_p = collect._expand_share_bits_jit(k0, f_p, 3, False, False, True)
    assert ch_x is None and ch_p is None
    np.testing.assert_array_equal(np.asarray(p_x), np.asarray(p_p))


def test_periodic_cw_path(rng):
    """N a multiple of the kernel row group -> the modular-index-map cw
    path must agree with the XLA engine (the production 131k-client shape
    takes this branch; the other tests exercise the broadcast fallback)."""
    from fuzzyheavyhitters_tpu.ops import expand_pallas, ibdcf
    from fuzzyheavyhitters_tpu.protocol import collect

    n = expand_pallas.R_BLK * expand_pallas.GROUP  # one full block per node
    L, d = 6, 1
    pts = rng.integers(0, 2, size=(n, d, L)).astype(bool)
    k0, _ = ibdcf.gen_l_inf_ball(pts, 1, rng, engine="np")
    f_x = collect.tree_init(k0, 2, planar=False)
    f_p = collect.tree_init(k0, 2, planar=True)
    p_x, ch_x = collect._expand_share_bits_jit(k0, f_x, 2, True, True, False)
    p_p, ch_p = collect._expand_share_bits_jit(k0, f_p, 2, True, True, True)
    np.testing.assert_array_equal(np.asarray(p_x), np.asarray(p_p))
    _check_children(ch_x, ch_p)
