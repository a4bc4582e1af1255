"""Device-mesh execution of the two-server protocol.

The reference's distribution fabric is processes + sockets: two server
binaries joined by a TCP channel mesh carrying GC/OT traffic
(ref: server.rs:197-262), rayon threads inside each (SURVEY.md §2
parallelism table).  The TPU-native fabric is a 2-D ``jax.sharding.Mesh``:

- axis ``servers`` (size 2): the two-party MPC topology.  Party p's keys and
  frontier live on the devices of mesh row p; the only inter-party traffic —
  one packed uint32 of share bits per (node, client) per level — moves by a
  single ``ppermute`` swap across this axis (the ICI replacement for the
  reference's per-core TCP socket mesh).
- axis ``data`` (size k): client data parallelism.  The client batch ``N``
  is sharded k ways (the reference's rayon ``par_iter`` over clients,
  collect.rs:94-119, become per-shard tensor blocks); per-node counts
  finish with a ``psum`` over this axis.

Every collective rides the mesh; the host (leader) only sees final counts —
mirroring the reference's leader↔server RPC split where per-level counts are
the only thing returned (rpc.rs:60-61).  The sharded kernels are built and
jitted ONCE per runner; ``level`` and the survivor table are traced scalars,
so a full ``data_len``-level crawl compiles exactly two programs.
"""

from __future__ import annotations

import secrets as _secrets
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import metrics as obsmetrics
from ..ops import baseot, gc, otext, prg
from ..ops.fields import F255, FE62
from ..ops.ibdcf import IbDcfKeyBatch
from ..protocol import collect, secure
from ..protocol.collect import EvalState, Frontier

SERVERS = "servers"
DATA = "data"

# sharding spec of a party-stacked key batch [2, N, d, 2, ...]
_KEY_SPEC = IbDcfKeyBatch(
    key_idx=P(SERVERS, DATA),
    root_seed=P(SERVERS, DATA),
    cw_seed=P(SERVERS, DATA),
    cw_bits=P(SERVERS, DATA),
    cw_y_bits=P(SERVERS, DATA),
)


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


def init_distributed(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join a multi-host JAX runtime (the DCN scale-out entry point).

    After this, ``jax.devices()`` is the GLOBAL device list,
    :func:`make_mesh` accepts it, and the shard_mapped crawl programs
    compile for the multi-host mesh with XLA routing each collective over
    ICI within a slice and DCN across slices — the scale-out axis the
    reference covers with tarpc + TCP socket meshes (SURVEY.md §2
    "distributed communication backend").  Arguments default to JAX's
    standard env/cluster autodetection (``jax.distributed.initialize``
    semantics).

    Multi-process host seams (tests/test_mesh_multiprocess.py runs them
    for real with two processes): ingest via
    :meth:`MeshRunner.from_process_local` — each process supplies only
    its own mesh row's key batch, combined with
    ``jax.make_array_from_process_local_data`` — and secure-mode session
    material (base-OT seeds, session seed) is agreed from process 0 via
    ``broadcast_one_to_all``.  NB the mesh transport is a single TRUST
    domain (the runtime sees both parties' material; use the socket
    transport, protocol/rpc.py, for two-administrative-domain
    deployments); multi-host here is the SCALE axis.
    """
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """2 × (n/2) mesh: first axis the two servers, rest data parallel.

    ``devices`` may be local chips or (after :func:`init_distributed`) the
    global multi-host device list."""
    if devices is None:
        devices = jax.devices()[: n_devices or len(jax.devices())]
    n = len(devices)
    assert n % 2 == 0, f"need an even device count for the 2-server axis, got {n}"
    arr = np.asarray(devices).reshape(2, n // 2)
    return Mesh(arr, (SERVERS, DATA))


def _stack_parties(t0, t1):
    return jax.tree.map(lambda a, b: jnp.stack([jnp.asarray(a), jnp.asarray(b)]), t0, t1)


class MeshRunner:
    """Holds both parties' device-resident state, sharded over the mesh.

    Leading axes of every tensor: [party=2, ...] with party sharded over
    ``servers`` and the client axis sharded over ``data``.  The PRG bit mode
    (prg.DERIVED_BITS) is captured at construction; a runner never mixes
    modes mid-crawl.
    """

    def __init__(
        self,
        mesh: Mesh,
        keys0: IbDcfKeyBatch | None,
        keys1: IbDcfKeyBatch | None,
        f_max: int,
        secure_exchange: bool = False,
        min_bucket: int = 1,
        _global_keys: IbDcfKeyBatch | None = None,
    ):
        self.mesh = mesh
        self.f_max = f_max
        self.min_bucket = min_bucket  # pin >1 only on compile-bound hosts
        self.secure = secure_exchange
        self._derived = prg.DERIVED_BITS
        self._key_spec = _KEY_SPEC
        if _global_keys is not None:  # from_process_local path
            self.keys = _global_keys
        else:
            keys = _stack_parties(keys0, keys1)  # [2, N, d, 2, ...]
            self.keys = jax.tree.map(
                lambda a, s: self._host_put(a, s), keys, _KEY_SPEC
            )
        n = self.keys.cw_seed.shape[1]
        self.n_dims = self.keys.cw_seed.shape[2]
        self.data_len = self.keys.cw_seed.shape[-2]
        assert n % mesh.shape[DATA] == 0, (
            f"client count {n} must divide the data axis {mesh.shape[DATA]}"
        )
        self.alive_keys = self._host_put(np.ones((2, n), bool), P(SERVERS, DATA))
        self._frontier_spec = Frontier(
            states=EvalState(
                seed=P(SERVERS, None, DATA),
                bit=P(SERVERS, None, DATA),
                y_bit=P(SERVERS, None, DATA),
            ),
            alive=P(SERVERS, None),
        )
        # child-state cache [2, F, Nl, d, 2, 2(,4)]: party, node, client...
        self._child_spec = EvalState(
            seed=P(SERVERS, None, DATA),
            bit=P(SERVERS, None, DATA),
            y_bit=P(SERVERS, None, DATA),
        )
        self.frontier: Frontier | None = None
        self._children: EvalState | None = None
        self._masks = collect.pattern_masks(self.n_dims)
        self._kernel_cache: dict = {}
        self._build_kernels()
        if secure_exchange:
            self._setup_secure()

    def _host_put(self, arr, spec):
        """Place a host array onto the mesh.  Single-process: device_put.
        Multi-process: every process holds the same global host value
        (replicated or agreed-from-process-0 material) and materializes
        only its addressable shards via ``make_array_from_callback``."""
        sharding = NamedSharding(self.mesh, spec)
        arr = np.asarray(arr)
        if jax.process_count() == 1:
            return jax.device_put(arr, sharding)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    @classmethod
    def from_process_local(
        cls,
        mesh: Mesh,
        my_keys: IbDcfKeyBatch,
        f_max: int,
        secure_exchange: bool = False,
        min_bucket: int = 1,
    ) -> "MeshRunner":
        """Multi-process construction for the two-host deployment shape
        (``configs/amazon.json``): process p hosts mesh row p (party p's
        chips) and supplies ONLY its own party's key batch — the global
        party-stacked arrays are assembled from the process-local rows
        via ``jax.make_array_from_process_local_data``, so no process
        ever materializes the peer party's keys on its host."""
        assert jax.process_count() == 2, "from_process_local is the 2-host shape"
        local = jax.tree.map(lambda a: np.asarray(a)[None], my_keys)  # [1, N, ..]
        keys = jax.tree.map(
            lambda a, s: jax.make_array_from_process_local_data(
                NamedSharding(mesh, s), a
            ),
            local,
            _KEY_SPEC,
        )
        return cls(
            mesh, None, None, f_max,
            secure_exchange=secure_exchange, min_bucket=min_bucket,
            _global_keys=keys,
        )

    def _setup_secure(self):
        """Host-side base-OT setup for the on-mesh 2PC, one session per
        garbling DIRECTION so the leader can alternate the garbler per
        level (the reference's ``gc_sender`` flip, rpc.rs:20-23): in
        session ``g`` party ``g`` (garbler / extension sender) gets its
        ``s``-chosen seeds, the other party the seed-pair columns.  The
        stacked [2, ...] tensors put each party's material in its own
        mesh-row slot; the unused slots are zeros (SPMD runs both roles on
        both parties and discards the wrong-role half — branchless, like
        any 2-way-masked collective)."""
        z = np.zeros((otext.KAPPA, 4), np.uint32)
        host_mats = []
        for g in (0, 1):
            s_bits = otext.fresh_s_bits()
            seeds0, seeds1, chosen = baseot.exchange(s_bits)
            host_mats.append((s_bits, seeds0, seeds1, chosen))
        sec_seed = np.frombuffer(_secrets.token_bytes(16), "<u4").copy()
        if jax.process_count() > 1:
            # session material must be identical everywhere: agree from
            # process 0 (single trust domain — see init_distributed note)
            from jax.experimental import multihost_utils

            host_mats, sec_seed = multihost_utils.broadcast_one_to_all(
                (host_mats, sec_seed)
            )
            host_mats = jax.tree.map(np.asarray, host_mats)
            sec_seed = np.asarray(sec_seed)
        self._sec = {}
        for g, (s_bits, seeds0, seeds1, chosen) in enumerate(host_mats):
            # fhh-lint: disable=host-sync-in-hot-loop,chunked-device-readback (one-time session setup)
            s_bits = np.asarray(s_bits)
            zb = np.zeros_like(s_bits)
            rows = lambda a_g, a_e: np.stack([a_g, a_e] if g == 0 else [a_e, a_g])
            self._sec[g] = {
                "s_bits": self._host_put(rows(s_bits, zb), P(SERVERS, None)),
                "seeds_main": self._host_put(
                    rows(chosen, seeds0).astype(np.uint32), P(SERVERS, None, None)
                ),
                "seeds_aux": self._host_put(
                    rows(z, seeds1).astype(np.uint32), P(SERVERS, None, None)
                ),
                "blocks": 0,  # column-stream block offset (lockstep)
                "sent": 0,  # pad-tweak index base
            }
        self._sec_seed = sec_seed
        self._crawl_ctr = 0

    def _build_kernels(self):
        mesh, f_max, derived = self.mesh, self.f_max, self._derived
        masks = jnp.asarray(self._masks)
        kspec, fspec = self._key_spec, self._frontier_spec

        cspec = self._child_spec

        root_bucket = self.min_bucket

        def init_body(keys):
            keys = jax.tree.map(lambda a: a[0], keys)  # drop party block axis
            # the mesh bodies pin the XLA engine, so pin its layout too
            f = collect.tree_init(keys, root_bucket, planar=False)
            return jax.tree.map(lambda a: a[None], f)

        # fhh-lint: disable=recompile-churn (setup-time factory: built once per mesh)
        self._init_fn = jax.jit(
            jax.shard_map(init_body, mesh=mesh, in_specs=(kspec,), out_specs=fspec)
        )

        def make_counts_fn(want_children: bool):
            def counts_body(keys, frontier, alive_keys, level):
                keys = jax.tree.map(lambda a: a[0], keys)
                frontier = jax.tree.map(lambda a: a[0], frontier)
                alive = alive_keys[0]
                packed, children = collect._expand_share_bits_jit(
                    keys, frontier, level, derived, want_children
                )
                # one u32 per (node, client): the whole inter-party data plane
                peer = jax.lax.ppermute(packed, SERVERS, perm=[(0, 1), (1, 0)])
                cnt = collect.counts_by_pattern(
                    packed, peer, masks, alive, frontier.alive
                )
                cnt = jax.lax.psum(cnt, DATA)
                # both parties compute identical counts (the compare is
                # symmetric); psum/2 over servers makes replication explicit
                cnt = jax.lax.psum(cnt, SERVERS) // 2
                if not want_children:  # last level: nothing advances past it
                    return cnt
                return cnt, jax.tree.map(lambda a: a[None], children)

            # fhh-lint: disable=recompile-churn (setup-time factory: built once per mesh)
            return jax.jit(
                jax.shard_map(
                    counts_body,
                    mesh=mesh,
                    in_specs=(kspec, fspec, P(SERVERS, DATA), P()),
                    out_specs=(P(), cspec) if want_children else P(),
                )
            )

        self._counts_fn = make_counts_fn(True)
        self._counts_last_fn = make_counts_fn(False)

        def advc_body(children, parent, pat_bits, n_alive):
            ch = jax.tree.map(lambda a: a[0], children)
            new = collect._advance_children_jit(ch, parent, pat_bits, n_alive)
            return jax.tree.map(lambda a: a[None], new)

        # fhh-lint: disable=recompile-churn (setup-time factory: built once per mesh)
        self._advance_fn = jax.jit(
            jax.shard_map(
                advc_body,
                mesh=mesh,
                in_specs=(cspec, P(None), P(None, None), P()),
                out_specs=fspec,
            )
        )

    def _secure_counts_fn(self, field, garbler: int = 0, want_children: bool = True):
        """Build (and cache) the one-program secure level crawl for a
        (count field, garbler party) pair: the whole per-level 2PC —
        label extension, equality + b2a, alive-gated share sums — as a
        single shard_mapped program whose only inter-party traffic is
        ``ppermute`` transfers on the ``servers`` axis: the ICI twin of
        protocol/rpc.py's socket flow.  Crawls with S = 2·n_dims ≤
        secure.OT2S_MAX_S take the 1-of-2^S chosen-payload-OT fast path
        — no garbled circuit, TWO transfers per level (u-matrix, payload
        table); wider strings run the GC+OT form with seven (u-matrix,
        tables/labels/decode, b2a u-matrix, ciphertext pair).
        ``garbler`` is static per program
        (the perms are trace-time), two compiles per field.

        Per-data-shard uniqueness: every (0,j)<->(1,j) chip pair runs its
        own extension on the shared base seeds.  Reusing identical column
        streams / garbler randomness across shards would leak XORs of
        secrets between shards (u_A ^ u_B = r_A ^ r_B, and identical X0
        labels reveal x_A ^ x_B), so every seed is tweaked by the shard
        index inside the body — consistently on both parties."""
        key = ("secure", field.__name__, garbler, want_children,
               secure._ot4_use(2 * self.n_dims))
        if key not in self._kernel_cache:
            self._kernel_cache[key] = self._make_secure_body(
                field, garbler, want_children
            )
        return self._kernel_cache[key]

    def _make_secure_body(self, field, g: int, want_children: bool = True):
        mesh, derived, d = self.mesh, self._derived, self.n_dims
        kspec, fspec = self._key_spec, self._frontier_spec
        limb = field.limb_shape
        ev = 1 - g  # evaluator party of this direction

        def body(keys, frontier, alive_keys, s_bits, seeds_main, seeds_aux,
                 gc_seed, b2a_seed, off, sent, level):
            keys_l = jax.tree.map(lambda a: a[0], keys)
            frontier_l = jax.tree.map(lambda a: a[0], frontier)
            alive = alive_keys[0]
            s_bits_l, sm, sa = s_bits[0], seeds_main[0], seeds_aux[0]
            gseed, bseed = gc_seed[0], b2a_seed[0]
            # NB: never tweak word 0 — it is stream_blocks' CTR word, and a
            # small XOR there yields a block-SHIFTED identical stream, not an
            # independent one.  Word 3 is safe for the column seeds; the
            # garbler seeds use word 2 shifted clear of derive_seed's
            # purpose tag.
            shard = jax.lax.axis_index(DATA).astype(jnp.uint32)
            sm = sm.at[..., 3].set(sm[..., 3] ^ shard)
            sa = sa.at[..., 3].set(sa[..., 3] ^ shard)
            gseed = gseed.at[2].set(gseed[2] ^ (shard << 16))
            bseed = bseed.at[2].set(bseed[2] ^ (shard << 16))

            packed, children = collect._expand_share_bits_jit(
                keys_l, frontier_l, level, derived, want_children
            )
            strs = secure.child_strings(packed, d)  # [F, C, Nl, S]
            F_, C, Nl, S = strs.shape
            B = F_ * C * Nl
            m = B * S
            flat = strs.reshape(B, S)

            # label delivery: evaluator's u -> garbler; labels = Δ-OT rows
            u, t_rows = otext._receiver_extend(sm, sa, flat.reshape(m), off, m)
            u0 = jax.lax.ppermute(u, SERVERS, perm=[(ev, g)])
            q = otext._sender_extend(sm, s_bits_l, u0, off, m)
            s_block = otext.pack_bits(s_bits_l)
            if secure._ot4_use(S):
                # 1-of-2^S chosen-payload OT: no circuit, the payload
                # table IS the message — 2 ppermutes per level (u, cts)
                # instead of the GC path's 7 (see secure.py's fast path;
                # S <= secure.OT2S_MAX_S, i.e. n_dims <= 3)
                W = secure.payload_words(field)
                r1, w0, w1 = secure.b2a_payload_pair(field, bseed, B, g)
                cts_g = secure.ot4_encrypt(
                    q.reshape(B, S, 4), s_block, flat, w1, w0, W, sent
                )
                cts = jax.lax.ppermute(cts_g, SERVERS, perm=[(g, ev)])
                w_pay = secure.ot4_decrypt(
                    t_rows.reshape(B, S, 4), flat, cts, W, sent
                )
                v1 = secure.words_to_field(field, w_pay)
            else:
                batch, mask = gc.garble_equality_delta(
                    s_block, q.reshape(B, S, 4), gseed, flat
                )
                ev_batch = gc.GarbledEqBatch(
                    tables=jax.lax.ppermute(batch.tables, SERVERS, perm=[(g, ev)]),
                    gb_labels=jax.lax.ppermute(batch.gb_labels, SERVERS, perm=[(g, ev)]),
                    decode=jax.lax.ppermute(batch.decode, SERVERS, perm=[(g, ev)]),
                )
                e = gc.eval_equality(ev_batch, t_rows.reshape(B, S, 4))

                # b2a conversion (r1 - r0 = 1 trick) under chosen-payload pads
                w_cols = -(-m // 32)
                off2 = off + (-(-w_cols // 16))
                u2, t2_rows = otext._receiver_extend(sm, sa, e, off2, B)
                u2_0 = jax.lax.ppermute(u2, SERVERS, perm=[(ev, g)])
                q2 = otext._sender_extend(sm, s_bits_l, u2_0, off2, B)
                idx0 = sent + m
                c0g, c1g, r1 = secure.b2a_encrypt(
                    field, q2, s_block, mask, bseed, idx0, g
                )
                c0 = jax.lax.ppermute(c0g, SERVERS, perm=[(g, ev)])
                c1 = jax.lax.ppermute(c1g, SERVERS, perm=[(g, ev)])
                v1 = secure.b2a_decrypt(field, t2_rows, idx0, c0, c1, e)

            party = jax.lax.axis_index(SERVERS)
            vals = jnp.where(party == g, r1, v1)  # own additive share per test
            wgt = (
                frontier_l.alive[:, None, None]
                & alive[None, None, :]
            )
            wgt = jnp.broadcast_to(wgt, (F_, C, Nl))
            shares = secure.node_share_sums(
                field, vals.reshape((F_, C, Nl) + limb), wgt
            )
            shares = field_psum(field, shares, DATA)
            # exchange both parties' share rows so the output is REPLICATED
            # [2, F, C(, limbs)] — the leader-side reconstruction then reads
            # a fully-addressable array on every process.  One-hot expand +
            # psum (each slot has exactly one contributor) rather than
            # all_gather: psum's replication is statically certified.
            party_row = jax.lax.axis_index(SERVERS)
            expand = jnp.zeros((2,) + shares.shape, shares.dtype)
            expand = expand.at[party_row].set(shares)
            allsh = _psum_exact(expand, SERVERS).astype(shares.dtype)
            if not want_children:  # last level: nothing advances past it
                return allsh
            return allsh, jax.tree.map(lambda a: a[None], children)

        # fhh-lint: disable=recompile-churn (setup-time factory: built once per mesh)
        fn = jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    kspec, fspec, P(SERVERS, DATA), P(SERVERS, None),
                    P(SERVERS, None, None), P(SERVERS, None, None),
                    P(SERVERS, None), P(SERVERS, None), P(), P(), P(),
                ),
                out_specs=(
                    (P(), self._child_spec) if want_children else P()
                ),
            )
        )
        return fn

    # -- leader-facing ops --------------------------------------------------

    def tree_init(self):
        self.frontier = self._init_fn(self.keys)
        self._children = None

    def level_counts(self, level: int, last: bool = False) -> np.ndarray:
        """Crawl counts for every child of the current frontier: the
        expand → exchange(ppermute) → compare → psum pipeline.  The
        both-direction child states are cached for :meth:`advance`;
        ``last=True`` (the final level, which nothing advances past)
        skips materializing the cache."""
        if last:
            cnt = self._counts_last_fn(
                self.keys, self.frontier, self.alive_keys, jnp.int32(level)
            )
            self._children = None
        else:
            cnt, self._children = self._counts_fn(
                self.keys, self.frontier, self.alive_keys, jnp.int32(level)
            )
        return np.asarray(cnt)

    def level_count_shares(self, level: int, field=FE62, last: bool = False) -> np.ndarray:
        """Secure crawl: both parties' additive count shares [2, F, 2^d
        (, limbs)] — reconstruct as field.sub(shares[0], shares[1]).  The
        level field mirrors the socket path: FE62 inner levels, F255 last
        (ref: rpc.rs:60-62); the garbler alternates per level (gc_sender
        flip), each direction consuming its own OT-extension session;
        ``last=True`` skips the child-state cache."""
        assert self.secure, "runner built without secure_exchange"
        g = level % 2
        sess = self._sec[g]
        fn = self._secure_counts_fn(field, g, not last)
        self._crawl_ctr += 1
        gseed = secure.derive_seed(self._sec_seed, 1, level, self._crawl_ctr)
        bseed = secure.derive_seed(self._sec_seed, 2, level, self._crawl_ctr)
        z = np.zeros(4, np.uint32)
        # the derived seeds go in the GARBLER's mesh row (the body reads its
        # own row) — with alternation, pinning row 0 would hand odd levels'
        # garbler an all-zero seed and destroy per-level freshness
        put = lambda a: self._host_put(
            np.stack([a, z] if g == 0 else [z, a]), P(SERVERS, None)
        )
        # static per-call shapes -> deterministic stream consumption; the
        # GC/OT batch is sized to the CURRENT frontier bucket, not f_max
        n_local = self.keys.cw_seed.shape[1] // self.mesh.shape[DATA]
        f_cur = self.frontier.alive.shape[1]
        B = f_cur * (1 << self.n_dims) * n_local
        m = B * 2 * self.n_dims
        out = fn(
            self.keys, self.frontier, self.alive_keys,
            sess["s_bits"], sess["seeds_main"], sess["seeds_aux"],
            put(gseed), put(bseed),
            jnp.uint32(sess["blocks"]), jnp.uint32(sess["sent"]),
            jnp.int32(level),
        )
        if last:
            shares, self._children = out, None
        else:
            shares, self._children = out
        w1 = -(-m // 32)
        if secure._ot4_use(2 * self.n_dims):
            # 1-of-2^S fast path: one extension (m rows), per-test pads
            # in their own tweak domain — no second b2a extension
            sess["blocks"] += -(-w1 // 16)
            sess["sent"] += m
        else:
            w2 = -(-B // 32)
            sess["blocks"] += (-(-w1 // 16)) + (-(-w2 // 16))
            sess["sent"] += m + B
        return np.asarray(shares)

    def advance(self, level: int, parent_idx, pattern_bits, n_alive: int):
        assert self._children is not None, "advance before level_counts"
        self.frontier = self._advance_fn(
            self._children,
            jnp.asarray(parent_idx, jnp.int32),
            jnp.asarray(pattern_bits, bool),
            jnp.int32(n_alive),
        )
        self._children = None

    # -- checkpoint / restore (data-plane fault tolerance) ------------------

    def snapshot(self) -> dict:
        """Host-side snapshot of the device-resident crawl state — the
        mesh twin of the socket servers' ``tree_checkpoint`` blob.  ONE
        stacked ``device_get`` (each fetch is a blocking device->host
        round trip); keys are NOT included (the caller holds
        them, and they never change mid-crawl)."""
        assert self.frontier is not None, "snapshot before tree_init"
        st = self.frontier.states
        return jax.device_get(
            {
                "seed": st.seed,
                "bit": st.bit,
                "y_bit": st.y_bit,
                "alive": self.frontier.alive,
                "alive_keys": self.alive_keys,
            }
        )

    def restore(self, snap: dict) -> None:
        """Re-place a :meth:`snapshot` onto the mesh (works after the
        device state was lost — ``_host_put`` reshards from host copies,
        multi-process included).  The child-state cache is dropped: it
        belonged to a level whose advance never happened."""
        fs = self._frontier_spec
        self.frontier = Frontier(
            states=EvalState(
                seed=self._host_put(snap["seed"], fs.states.seed),
                bit=self._host_put(snap["bit"], fs.states.bit),
                y_bit=self._host_put(snap["y_bit"], fs.states.y_bit),
            ),
            alive=self._host_put(snap["alive"], fs.alive),
        )
        self.alive_keys = self._host_put(snap["alive_keys"], P(SERVERS, DATA))
        self._children = None


class MeshLeader:
    """Level-loop driver over a MeshRunner (host-side thresholds/paths,
    ref: leader.rs:185-297 — same bookkeeping as protocol.driver.Leader)."""

    def __init__(self, runner: MeshRunner, min_bucket: int | None = None):
        self.r = runner
        # default: the runner's own pin (so one knob covers init + prune)
        self.min_bucket = runner.min_bucket if min_bucket is None else min_bucket
        self.paths = None
        self.n_nodes = 0
        # telemetry: level spans (the heartbeat names the level a wedged
        # pod crawl died in) + survivor gauges + device-fetch counts
        self.obs = obsmetrics.Registry("mesh")

    def _level_counts(self, level: int) -> np.ndarray:
        """Per-level counts: plaintext compare in trusted mode, or leader
        reconstruction v0 - v1 of the parties' share outputs in secure mode
        (FE62 inner levels, F255 last — ref: rpc.rs:60-62)."""
        r = self.r
        last = level == r.data_len - 1
        self.obs.count("device_fetches", level=level)  # one host fetch per
        # level: the counts (trusted) or the reconstructed share diff
        if not r.secure:
            return r.level_counts(level, last=last)
        if last:
            sh = r.level_count_shares(level, F255, last=True)
            v = np.asarray(F255.sub(sh[0], sh[1]))
            counts = v[..., 0].astype(np.uint32)
            if np.any(v[..., 1:]):
                raise RuntimeError("non-count residue in F255 mesh shares")
            return counts
        sh = r.level_count_shares(level, FE62)
        v = np.asarray(FE62.canon(FE62.sub(sh[0], sh[1])))
        n = r.keys.cw_seed.shape[1]
        if np.any(v > n):  # e.g. a share-sign/role mismatch
            raise RuntimeError("count reconstruction out of range")
        return v.astype(np.uint32)

    def _run_one_level(self, level: int, nreqs: int, threshold: float):
        """One crawl->threshold->prune round; returns the kept counts for
        this level, or None when the crawl died out (no survivors)."""
        r = self.r
        d = r.n_dims
        counts = self._level_counts(level)
        thresh = max(1, int(threshold * nreqs))
        keep = counts >= thresh
        keep[self.n_nodes :, :] = False
        parent, pattern, n_alive = collect.compact_survivors(
            keep, r.f_max, self.min_bucket
        )
        pat_bits = collect.pattern_to_bits(pattern, d)
        self.obs.gauge("survivors", n_alive, level=level)
        if n_alive == 0:
            return None
        if level < r.data_len - 1:  # nothing advances past the leaves
            r.advance(level, parent, pat_bits, n_alive)
        new_paths = np.zeros((n_alive, d, self.paths.shape[-1] + 1), bool)
        for i in range(n_alive):
            new_paths[i, :, :-1] = self.paths[parent[i]]
            new_paths[i, :, -1] = pat_bits[i]
        self.paths = new_paths
        self.n_nodes = n_alive
        return counts[parent[:n_alive], pattern[:n_alive]]

    def run(self, nreqs: int, threshold: float):
        from ..protocol.driver import CrawlResult

        r = self.r
        d = r.n_dims
        r.tree_init()
        self.paths = np.zeros((1, d, 0), bool)
        self.n_nodes = 1
        counts_kept = np.zeros(0, np.uint32)
        for level in range(r.data_len):
            with self.obs.span("level", level=level):
                counts_kept = self._run_one_level(level, nreqs, threshold)
            if counts_kept is None:
                return CrawlResult(
                    paths=np.zeros((0, d, level + 1), bool),
                    counts=np.zeros(0, np.uint32),
                )
        return CrawlResult(paths=self.paths, counts=counts_kept)

    def run_supervised(
        self,
        nreqs: int,
        threshold: float,
        *,
        checkpoint_every: int = 2,
        max_recoveries: int = 4,
        chaos=None,
    ):
        """Fault-tolerant twin of :meth:`run` for the ICI path: host-side
        snapshots of the device-resident frontier every
        ``checkpoint_every`` levels, and recovery matched to what a mesh
        fault actually costs:

        - device state INTACT (a dropped data-parallel shard — the
          collective's result can't be trusted but the frontier can):
          re-run just that level;
        - device state LOST (a participant killed mid-collective): restore
          the last snapshot and re-run the lost levels — or restart from
          scratch if none was taken yet.

        ``chaos`` is a :class:`resilience.chaos.MeshChaos` injector (or
        None); its ``before_level`` hook fires the scheduled faults.
        Recovery is exact: counts are deterministic re-runs (secure-mode
        share randomness differs, their reconstruction does not), so a
        recovered crawl is bit-identical to a fault-free one."""
        from ..protocol.driver import CrawlResult
        from ..resilience.chaos import MeshFaultError
        from .. import obs as obsmod

        r = self.r
        d = r.n_dims
        r.tree_init()
        self.paths = np.zeros((1, d, 0), bool)
        self.n_nodes = 1
        counts_kept = np.zeros(0, np.uint32)
        # zero-touch the recovery counters: a supervised FAULT-FREE run
        # must still carry the run report's recovery section (as zeros)
        # so its absence can't be mistaken for a fault-free recovery
        for c in ("recoveries", "levels_rerun", "shards_rerun"):
            self.obs.count(c, 0)
        stash = None  # (level, snapshot, paths, n_nodes, counts_kept)
        recoveries = 0
        level = 0
        while level < r.data_len:
            try:
                if chaos is not None:
                    chaos.before_level(r, level)
                with self.obs.span("level", level=level):
                    counts_kept = self._run_one_level(level, nreqs, threshold)
                if counts_kept is None:
                    return CrawlResult(
                        paths=np.zeros((0, d, level + 1), bool),
                        counts=np.zeros(0, np.uint32),
                    )
                if level < r.data_len - 1 and (level + 1) % checkpoint_every == 0:
                    stash = (
                        level,
                        r.snapshot(),
                        self.paths.copy(),
                        self.n_nodes,
                        counts_kept.copy(),
                    )
                    self.obs.count("crawl_checkpoints", level=level)
                level += 1
            except MeshFaultError as err:
                recoveries += 1
                self.obs.count("recoveries")
                obsmod.emit(
                    "resilience.mesh_recover",
                    severity="warn",
                    level=level,
                    attempt=recoveries,
                    state_lost=err.state_lost,
                    error=str(err),
                )
                if recoveries > max_recoveries:
                    raise
                if not err.state_lost and r.frontier is not None:
                    # shard-granular cost: device state survived, only
                    # this level's collective result is suspect
                    self.obs.count("shards_rerun", level=level)
                    continue
                self.obs.count("levels_rerun")
                if stash is not None:
                    lvl, snap, paths, n_nodes, kept = stash
                    r.restore(snap)
                    self.paths = paths.copy()
                    self.n_nodes = n_nodes
                    counts_kept = kept.copy()
                    level = lvl + 1
                else:  # no snapshot yet: restart the crawl from scratch
                    r.tree_init()
                    self.paths = np.zeros((1, d, 0), bool)
                    self.n_nodes = 1
                    counts_kept = np.zeros(0, np.uint32)
                    level = 0
        return CrawlResult(paths=self.paths, counts=counts_kept)
