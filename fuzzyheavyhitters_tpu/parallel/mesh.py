"""The exact modular reduction over a mesh axis.

A multi-chip server shards one server's clients over its own chips
(``parallel/server_mesh.py``, inside ``CollectorServer``) and its secure
level by rows (``parallel/kernel_shard.py``); both sum their per-shard
field shares with :func:`field_psum` over their ``data`` axis before
anything reaches the wire.  Nothing else lives here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.fields import F255


def _psum_exact(x, axis_name):
    """Exact integer psum of u32/u64 values -> u64, through 32-bit
    collectives only: the TPU compiler lowers no 64-bit all-reduce
    (``UNIMPLEMENTED: Supported lowering only of Sum all reduce`` on a
    v5e).  16-bit limbs ride u32 lanes, which an axis of < 2^16 members
    cannot overflow; exact while the true sum is < 2^64."""
    x = jnp.asarray(x)
    n_limbs = 4 if x.dtype == jnp.uint64 else 2
    limbs = jnp.stack(
        [((x >> (16 * i)) & 0xFFFF).astype(jnp.uint32) for i in range(n_limbs)]
    )
    s = jax.lax.psum(limbs, axis_name).astype(jnp.uint64)
    out = s[0]
    for i in range(1, n_limbs):
        out = out + (s[i] << jnp.uint64(16 * i))
    return out


def field_psum(field, v, axis_name):
    """Modular psum: sum field elements over a mesh axis without overflow.

    FE62/U63 values are u64 scalars — a raw psum over k shards can exceed
    2^64; splitting into 32-bit halves keeps every partial sum exact, then
    recombines mod p (the collective twin of field.sum's split trick).
    F255 limbs sum into u64 so the k-way limb sums stay exact, then one
    carry chain + 2^256 === 38 fold renormalizes.  Every collective is
    32-bit (:func:`_psum_exact`)."""
    if field is F255:
        l64 = _psum_exact(jnp.asarray(v, jnp.uint32), axis_name)
        limbs, carry = F255._carry_chain(l64)
        for _ in range(2):  # settle 2^256 === 38 wraps (cf. F255.mul's tail)
            limbs, carry = F255._carry_chain(
                limbs.at[..., 0].add(carry * jnp.uint64(38))
            )
        limbs = limbs.astype(jnp.uint32)
        limbs = F255._sub_p_if(limbs, F255._geq_p(limbs))
        return F255._sub_p_if(limbs, F255._geq_p(limbs))
    mask32 = jnp.uint64(0xFFFFFFFF)
    v = jnp.asarray(v, jnp.uint64)
    lo = _psum_exact((v & mask32).astype(jnp.uint32), axis_name)
    hi = _psum_exact((v >> 32).astype(jnp.uint32), axis_name)
    return field.add(field.new(lo), field.mul(field.new(hi), field.from_int(1 << 32)))
