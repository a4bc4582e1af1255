"""Fused Pallas keygen: the whole ibDCF level recurrence as ONE TPU kernel.

The ``lax.scan`` keygen (ops/ibdcf.py, ref: ibDCF.rs:138-164) is
latency-bound, not compute-bound: each of the ``data_len`` scan steps costs
a fixed XLA dispatch overhead that dwarfs its few microseconds of VPU work
(measured: 8192 keys x 512 levels ~= 0.22 ms/step, ~1% of HBM bound).  This
kernel runs the entire recurrence inside one ``pallas_call``:

- the per-client state (two parties' seeds + t-bits) lives in registers /
  VMEM across all levels — nothing round-trips to HBM between levels;
- clients are laid out as ``(8 sublanes, LANES lanes)`` tiles so every
  ChaCha word is a full native VPU vreg — the 16-word cipher state is 16
  register arrays and the diagonal round is pure variable renaming (the
  scalar-form ChaCha, but each "scalar" is a [8, LANES] vector);
- correction words stream out to VMEM blocks per level (dynamic stores on
  the untiled leading axis are cheap).

Bit-exactness is pinned against ``gen_pair_np`` (tests/test_ibdcf.py); the
public wrapper returns the same ``IbDcfKeyBatch`` pytrees as the scan
engine.  Select with ``engine="pallas"`` in the ibdcf keygen entry points.

Reference semantics carried over (same recurrence as ops/ibdcf.py):
``gen_cor_word`` per level (ibDCF.rs:84-119), party-0 t=0 / party-1 t=1
roots (ibDCF.rs:143-146), masked-seed expansion (prg.rs:97), and both bit
modes (the reference's constant-bit quirk and honest derived bits).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import prg
from .ibdcf import IbDcfKeyBatch

SUB = 8  # sublanes per client tile
LANES = 128  # lanes per client tile (SUB * LANES clients per grid step)
TILE = SUB * LANES
L_BLK = 64  # levels per grid step (bounds the VMEM block footprint)


_qr = prg._quarter_round  # one quarter-round implementation everywhere


def _chacha16(blk):
    """blk: list of 4 uint32 arrays (the input block words, any shape).

    Returns the 16 output words as register arrays — the scalar-form ChaCha
    (prg.chacha_block's math exactly), unrolled: inside one kernel there is
    no XLA-compile pressure, and renamed-variable diagonal rounds beat any
    roll/permute on the VPU.
    """
    shape = blk[0].shape
    x = [jnp.full(shape, w, jnp.uint32) for w in prg._SIGMA + prg._FIXED_KEY]
    x += list(blk)
    init = list(x)
    for _ in range(prg.N_ROUNDS // 2):
        x[0], x[4], x[8], x[12] = _qr(x[0], x[4], x[8], x[12])
        x[1], x[5], x[9], x[13] = _qr(x[1], x[5], x[9], x[13])
        x[2], x[6], x[10], x[14] = _qr(x[2], x[6], x[10], x[14])
        x[3], x[7], x[11], x[15] = _qr(x[3], x[7], x[11], x[15])
        x[0], x[5], x[10], x[15] = _qr(x[0], x[5], x[10], x[15])
        x[1], x[6], x[11], x[12] = _qr(x[1], x[6], x[11], x[12])
        x[2], x[7], x[8], x[13] = _qr(x[2], x[7], x[8], x[13])
        x[3], x[4], x[9], x[14] = _qr(x[3], x[4], x[9], x[14])
    return [a + b for a, b in zip(x, init)]


def _kernel(derived_bits: bool,
            seeds_ref, alpha_ref, side_ref,
            cw_seed_ref, cw_b_ref, cw_y_ref,
            seed_scr, tb_scr):
    """One (client tile, level block) grid step.

    Block shapes: seeds u32[2, 4, 8, LANES], alpha u32[L_BLK, 8, LANES]
    (0/1), side u32[8, LANES] (0/1) -> cw_seed u32[L_BLK, 4, 8, LANES],
    cw_b/cw_y u32[L_BLK, 2, 8, LANES] (0/1 words; the wrapper casts to
    bool).  The level axis rides grid dim 1 (fastest-iterating on TPU), and
    the recurrence state carries across level blocks in VMEM scratch
    (``seed_scr`` u32[2, 4, 8, LANES], ``tb_scr`` u32[2, 8, LANES]),
    re-initialized whenever a new client tile starts.

    Everything stays uint32 — bit flags as 0/1 words, selects as XOR-masks
    (``b ^ (mask & (a ^ b))`` with ``mask = 0 - flag``).  Mosaic's vector i1
    paths are what the compiler rejects, so no bool vectors appear.
    """
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _init_tile():
        seed_scr[...] = seeds_ref[...]
        tb_scr[0] = jnp.zeros((SUB, LANES), jnp.uint32)
        tb_scr[1] = jnp.ones((SUB, LANES), jnp.uint32)

    side = side_ref[...]  # 0/1
    one = jnp.uint32(1)

    def sel(flag01, a, b):
        """flag ? a : b, element-wise on u32 (flag is a 0/1 word)."""
        m = jnp.uint32(0) - flag01
        return b ^ (m & (a ^ b))

    def level(l, carry):
        seeds, tbits = carry  # u32[2, 4, 8, LANES], u32[2, 8, LANES]

        def expand(p):
            blk = [seeds[p, w] for w in range(4)]
            blk[0] = blk[0] & jnp.uint32(0xFFFFFFF0)  # prg.rs:97 mask
            out = _chacha16(blk)
            if derived_bits:
                w8 = out[8]
                bits = ((w8 & 1) ^ 1, ((w8 >> 1) & 1) ^ 1)
                ybits = (((w8 >> 2) & 1) ^ 1, ((w8 >> 3) & 1) ^ 1)
            else:  # the reference's masked-byte constants (prg.rs:103-104)
                o = jnp.full((SUB, LANES), 1, jnp.uint32)
                bits, ybits = (o, o), (o, o)
            return out[0:4], out[4:8], bits, ybits

        sl0, sr0, b0, y0 = expand(0)
        sl1, sr1, b1, y1 = expand(1)
        keep = alpha_ref[l]  # [8, LANES] 0/1

        cw_seed_w = [sel(keep, a ^ b, c ^ d)
                     for a, b, c, d in zip(sl0, sl1, sr0, sr1)]
        cw_b_l = b0[0] ^ b1[0] ^ keep ^ one
        cw_b_r = b0[1] ^ b1[1] ^ keep
        cw_y_l = y0[0] ^ y1[0] ^ (keep & (side ^ one))
        cw_y_r = y0[1] ^ y1[1] ^ ((keep ^ one) & side)

        for w in range(4):
            cw_seed_ref[l, w] = cw_seed_w[w]
        cw_b_ref[l, 0] = cw_b_l
        cw_b_ref[l, 1] = cw_b_r
        cw_y_ref[l, 0] = cw_y_l
        cw_y_ref[l, 1] = cw_y_r

        cw_keep = sel(keep, cw_b_r, cw_b_l)
        new_seeds = []
        new_tbits = []
        for p, (sl, sr, b) in enumerate(((sl0, sr0, b0), (sl1, sr1, b1))):
            t = tbits[p]  # 0/1
            tm = jnp.uint32(0) - t
            kept = [sel(keep, r, a) for a, r in zip(sl, sr)]
            ns = [k ^ (tm & c) for k, c in zip(kept, cw_seed_w)]
            kb = sel(keep, b[1], b[0])
            nt = kb ^ (t & cw_keep)
            new_seeds.append(jnp.stack(ns))
            new_tbits.append(nt)
        return jnp.stack(new_seeds), jnp.stack(new_tbits)

    # i32 bounds: the package enables jax_enable_x64, and Mosaic rejects the
    # i64 loop counter plain python ints would produce here
    new_seeds, new_tbits = jax.lax.fori_loop(
        np.int32(0), np.int32(L_BLK), level, (seed_scr[...], tb_scr[...])
    )
    seed_scr[...] = new_seeds
    tb_scr[...] = new_tbits


@partial(jax.jit, static_argnames=("derived_bits", "interpret"))
def _gen_pallas(init_seeds, alpha_bits, side, derived_bits, interpret=False):
    """init_seeds u32[N, 2, 4], alpha bool[N, L], side bool[N] ->
    (cw_seed u32[N, L, 4], cw_bits bool[N, L, 2], cw_y bool[N, L, 2])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, L = alpha_bits.shape
    pad = (-N) % TILE
    n_pad = N + pad
    l_pad = (-L) % L_BLK
    Lp = L + l_pad
    if pad:
        init_seeds = jnp.concatenate(
            [init_seeds, jnp.zeros((pad, 2, 4), jnp.uint32)]
        )
        alpha_bits = jnp.concatenate([alpha_bits, jnp.zeros((pad, L), bool)])
        side = jnp.concatenate([side, jnp.zeros((pad,), bool)])
    if l_pad:
        # padded levels advance the recurrence into rows the wrapper slices
        # off — the discarded state never feeds a kept output
        alpha_bits = jnp.concatenate(
            [alpha_bits, jnp.zeros((n_pad, l_pad), bool)], axis=1
        )
    tiles = n_pad // TILE
    l_blocks = Lp // L_BLK

    # client-minor relayout: [n_pad, ...] -> [tiles, ..., SUB, LANES]
    seeds_t = jnp.transpose(
        init_seeds.reshape(tiles, SUB, LANES, 2, 4), (0, 3, 4, 1, 2)
    )  # [tiles, 2, 4, SUB, LANES]
    alpha_t = jnp.transpose(
        alpha_bits.reshape(tiles, SUB, LANES, Lp), (0, 3, 1, 2)
    ).astype(jnp.uint32)  # [tiles, Lp, SUB, LANES]
    side_t = side.reshape(tiles, SUB, LANES).astype(jnp.uint32)

    # level blocks ride grid dim 1 (fastest on TPU), so each client tile
    # walks its levels in order with the recurrence state held in scratch
    grid = (tiles, l_blocks)
    kern = partial(_kernel, derived_bits)
    # index maps return i32 zeros: jax_enable_x64 is on package-wide, and
    # Mosaic rejects i64 block indices
    z = np.int32(0)
    cw_seed, cw_b, cw_y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 2, 4, SUB, LANES), lambda i, j: (i, z, z, z, z)),
            pl.BlockSpec((None, L_BLK, SUB, LANES), lambda i, j: (i, j, z, z)),
            pl.BlockSpec((None, SUB, LANES), lambda i, j: (i, z, z)),
        ],
        out_specs=[
            pl.BlockSpec((None, L_BLK, 4, SUB, LANES), lambda i, j: (i, j, z, z, z)),
            pl.BlockSpec((None, L_BLK, 2, SUB, LANES), lambda i, j: (i, j, z, z, z)),
            pl.BlockSpec((None, L_BLK, 2, SUB, LANES), lambda i, j: (i, j, z, z, z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles, Lp, 4, SUB, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((tiles, Lp, 2, SUB, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((tiles, Lp, 2, SUB, LANES), jnp.uint32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, 4, SUB, LANES), jnp.uint32),
            pltpu.VMEM((2, SUB, LANES), jnp.uint32),
        ],
        interpret=interpret,
    )(seeds_t, alpha_t, side_t)

    # back to the standard [N, L, k] layout
    def back(a, k):
        a = jnp.transpose(a, (0, 3, 4, 1, 2))  # [tiles, SUB, LANES, Lp, k]
        return a.reshape(n_pad, Lp, k)[:N, :L]

    return back(cw_seed, 4), back(cw_b, 2) != 0, back(cw_y, 2) != 0


def gen_pair_pallas(
    init_seeds, alpha_bits, side, derived_bits: bool | None = None,
    interpret: bool = False,
) -> tuple[IbDcfKeyBatch, IbDcfKeyBatch]:
    """Drop-in for :func:`ibdcf.gen_pair` with arbitrary batch dims.

    Flattens the batch to [N, L], runs the fused kernel, reshapes back.
    """
    if derived_bits is None:
        derived_bits = prg.DERIVED_BITS
    init_seeds = jnp.asarray(init_seeds, jnp.uint32)
    alpha = jnp.asarray(alpha_bits, bool)
    batch = alpha.shape[:-1]
    L = alpha.shape[-1]
    side_b = jnp.broadcast_to(jnp.asarray(side, bool), batch)
    n = int(np.prod(batch)) if batch else 1
    cw_seed, cw_b, cw_y = _gen_pallas(
        init_seeds.reshape(n, 2, 4), alpha.reshape(n, L),
        side_b.reshape(n), derived_bits, interpret,
    )

    def mk(p: int) -> IbDcfKeyBatch:
        return IbDcfKeyBatch(
            key_idx=jnp.broadcast_to(jnp.asarray(bool(p)), batch),
            root_seed=init_seeds[..., p, :],
            cw_seed=cw_seed.reshape(batch + (L, 4)),
            cw_bits=cw_b.reshape(batch + (L, 2)),
            cw_y_bits=cw_y.reshape(batch + (L, 2)),
        )

    return mk(0), mk(1)
