"""Per-collection session subsystem: the multi-tenant half of the server.

The reference protocol is embarrassingly parallel ACROSS collections —
independent trees, independent FSS keys, independent 2PC transcripts
(PAPER.md §0) — so one server pair can serve many collections at once
provided every piece of per-collection state is keyed instead of
global.  This module holds that keying:

- :class:`CollectionSession` — everything a single collection's crawl
  owns (frontier, keys, liveness, sketch ratchet/root, expand cache,
  ingest window pools + admission gate, checkpoint namespace, OT
  sessions, per-session verb lock).  The attributes that used to live
  directly on ``CollectorServer`` live here now; the fhh-race guard map
  binds them to the session's own ``_verb_lock``
  (``[tool.fhh-lint.guards]`` "CollectionSession.*" + the
  :data:`_SESSION_GUARDS` runtime twin).
- :class:`SessionTable` — the bounded keyed table of live sessions,
  selected on the wire by the ``collection`` field of the existing
  ``__hello__`` handshake (protocol/rpc.py).  A connection that never
  says hello (or says it without a collection) works on the DEFAULT
  session, so every single-tenant flow is unchanged.
- :class:`PlaneMux` — the server↔server data plane demultiplexed
  into per-collection FIFO channels: every plane frame is
  ``(channel, payload)``, the plane's reader thread hands frames to the
  loop, which routes them into per-channel queues, and each session's
  exchanges ride its own channel
  — two collections' 2PC transcripts interleave on the wire without
  ever desynchronizing, because each receiver demuxes by key instead
  of assuming global FIFO order.

Checkpoint namespacing: the default session keeps the legacy
``fhh_server{id}_l{level}.npz`` names; any other collection writes
``fhh_server{id}_c{key}_l{level}.npz``, and every blob is stamped with
its collection key (``sess`` field) so a blob renamed across
namespaces refuses to restore (validate-before-mutate, PR-4 contract).
"""

from __future__ import annotations

import asyncio
import contextlib
import hashlib
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs as obsmod
from ..obs import devmem
from ..obs import metrics as obsmetrics
from ..ops import dpf, prg
from ..ops.fields import F255, FE62
from ..ops.ibdcf import IbDcfKeyBatch
from ..parallel import server_mesh as smesh
from ..resilience import admission as resadmission
from ..utils import guards, taint_guard
from ..utils.config import Config
from . import collect, keyplanes, mpc, sketch as sketchmod

DEFAULT_COLLECTION = "default"
# collection keys become checkpoint filename components and wire channel
# tags: keep them filesystem- and log-safe
_KEY_RE = re.compile(r"^[A-Za-z0-9._-]{1,64}$")

SHARED_MASK_SEED = b"XXX This is bog\x00"  # 16 B, ref: server.rs:331-332

# structure template for (de)serializing sketch key batches over the wire
_z = np.zeros(0)
_SKETCH_TREEDEF = sketchmod.SketchKeyBatch(
    key=dpf.DpfKeyBatch(_z, _z, _z, _z, _z, _z),
    mac_key=_z,
    mac_key2=_z,
    mac_key_last=_z,
    mac_key2_last=_z,
    triples=mpc.TripleBatch(_z, _z, _z),
    triples_last=mpc.TripleBatch(_z, _z, _z),
)


def _mask_words(level: int, n: int, blocks_for: int) -> np.ndarray:
    """Shared pseudorandom mask words for one level (both servers derive the
    same stream, so shares cancel on reconstruction).  Host NumPy on
    purpose: the mask is tiny (F·2^d elements) and the device version
    would cost a device->host round trip per level per server."""
    seed = prg.seeds_from_bytes(SHARED_MASK_SEED)[0].copy()
    seed[3] ^= np.uint32(level)
    return prg.np_stream_words(seed, n * blocks_for).reshape(n, blocks_for)


def mask_fe62(level: int, n: int) -> np.ndarray:
    # host twin of FE62.sample (see protocol/rpc.py history): the device
    # version cost one device->host fetch per level for microseconds of NumPy
    return FE62.np_sample(_mask_words(level, n, 4))


def mask_f255(level: int, n: int) -> np.ndarray:
    return F255.np_sample(_mask_words(level, n, 8))


class _WindowPool:
    """One ingest window's append-only key pool (the streaming front
    door's unit of work: protocol verbs ``submit_keys`` → ``window_seal``
    → ``window_load``).

    ``entries`` holds admitted submissions (tuples of key arrays, the
    same chunk shape ``add_keys`` receives) in arrival order; once the
    reservoir shed policy engages, the list freezes into a SLOT TABLE
    and replacements overwrite in place.  ``verdicts`` records every
    FINAL outcome by ``sub_id`` so at-least-once delivery (reconnect
    replays, recovery journal replays) answers the recorded verdict
    instead of double-admitting or re-advancing the sampler's RNG.
    Overloaded rejections are deliberately NOT recorded — a backed-off
    retry is a fresh attempt against refilled tokens."""

    __slots__ = (
        "window", "wa", "entries", "verdicts", "keys",
        "admitted_keys", "shed_keys", "rejected", "sealed", "sealed_at",
        "sk_root",
    )

    def __init__(self, window: int, wa: resadmission.WindowAdmission):
        self.window = int(window)
        self.wa = wa
        self.entries: list = []
        self.verdicts: dict = {}
        self.keys = 0
        self.admitted_keys = 0
        self.shed_keys = 0
        self.rejected = 0
        self.sealed = False
        # wall-clock seal instant: the start of this window's
        # seal-to-hitters SLO clock (observed at final_shares of the
        # crawl that loads the window — protocol/rpc.py)
        self.sealed_at: float | None = None
        # malicious mode: the window's committed sketch-challenge root
        # (uint32[4], sketch.window_root) — stamped at seal, carried by
        # the seal stats and the ingest checkpoint so a recovered
        # window's crawl replays the IDENTICAL challenge sequence
        self.sk_root: np.ndarray | None = None

    def apply(self, sub_id: str, chunk: tuple,
              v: resadmission.Verdict) -> dict:
        """Commit one gate verdict to the pool; returns the wire
        response (the mirror server replays it via :meth:`apply_mirror`)."""
        n_keys = int(chunk[0].shape[0])
        if not v.admitted and v.scope is not None:
            self.rejected += 1
            return {
                "admitted": False, "overloaded": True, "scope": v.scope,
                "retry_after_s": round(float(v.retry_after_s), 4),
                "window": self.window,
            }
        if not v.admitted:  # reservoir shed this submission
            resp = {"admitted": False, "shed": True, "window": self.window}
            self.verdicts[sub_id] = resp
            self.shed_keys += n_keys
            return resp
        if v.slot is None:
            self.entries.append(chunk)
            self.keys += n_keys
        else:
            old = self.entries[v.slot]
            old_n = int(old[0].shape[0])
            self.entries[v.slot] = chunk
            self.keys += n_keys - old_n
            self.shed_keys += old_n
            # keep the admission ledger's occupancy honest under
            # variable-size chunks
            self.wa.keys += n_keys - old_n
        self.admitted_keys += n_keys
        resp = {"admitted": True, "slot": v.slot, "window": self.window}
        self.verdicts[sub_id] = resp
        return resp

    def apply_mirror(self, sub_id: str, chunk: tuple, mirror: dict,
                     client_id: str | None = None) -> dict:
        """Replay the GATE server's verdict on the peer pool so both
        servers' windows stay positionally identical.  Validates loudly —
        a mirror that cannot apply means the two pools diverged, which
        must never be papered over."""
        n_keys = int(chunk[0].shape[0])
        slot = mirror.get("slot")
        if self.wa.shed == resadmission.SHED_RESERVOIR:
            if self.wa.sub_keys is None:
                self.wa.sub_keys = n_keys  # uniform-chunk contract holds
            if mirror.get("shed") or slot is not None:
                # a restored GATE being rebuilt by the recovery journal:
                # the replayed verdict consumed one sampler draw in its
                # first life — advance the restored stream past it (the
                # verdict itself is applied verbatim below), so
                # post-recovery live admissions continue the SAME
                # seed-reproducible sequence.  When the reservoir
                # engaged only AFTER the last checkpoint, there is no
                # sampler to advance yet: bank the draw so the eventual
                # engagement fast-forwards past it.  A mirror server
                # never re-engages a reservoir, so this is harmless
                # bookkeeping outside recovery.
                if self.wa.reservoir is not None:
                    self.wa.reservoir.offer(1)
                else:
                    self.wa.pending_draws += 1
        if mirror.get("shed"):
            resp = {"admitted": False, "shed": True, "window": self.window}
            self.verdicts[sub_id] = resp
            self.shed_keys += n_keys
            return resp
        if slot is None:
            if self.keys + n_keys > self.wa.max_keys:
                raise RuntimeError(
                    f"ingest mirror overflows window {self.window}: "
                    f"{self.keys} + {n_keys} > {self.wa.max_keys} "
                    "(gate/mirror pools diverged)"
                )
            self.entries.append(chunk)
            self.keys += n_keys
            # keep the admission ledger in lockstep: a recovery journal
            # replay rebuilds a restarted GATE through this path, and its
            # later live decisions must see the true occupancy
            self.wa.subs += 1
            self.wa.keys += n_keys
            self.wa._charge(client_id, n_keys)
        else:
            slot = int(slot)
            if not 0 <= slot < len(self.entries):
                raise RuntimeError(
                    f"ingest mirror names slot {slot} of a "
                    f"{len(self.entries)}-slot window {self.window} pool "
                    "(gate/mirror pools diverged)"
                )
            old_n = int(self.entries[slot][0].shape[0])
            self.entries[slot] = chunk
            self.keys += n_keys - old_n
            self.shed_keys += old_n
            self.wa.keys += n_keys - old_n
            self.wa._charge(client_id, n_keys)
        self.admitted_keys += n_keys
        resp = {"admitted": True, "slot": slot, "window": self.window}
        self.verdicts[sub_id] = resp
        return resp

    def stats(self) -> dict:
        out = {
            "window": self.window,
            "sealed": self.sealed,
            "keys": self.keys,
            "subs": len(self.entries),
            "admitted_keys": self.admitted_keys,
            "shed_keys": self.shed_keys,
            "rejected": self.rejected,
        }
        if self.sk_root is not None:
            # plain ints: the driver banks these stats and replays them
            # into a recovery re-seal (dict equality in tests must stay
            # unambiguous, so no ndarray values here)
            out["sk_root"] = [int(x) for x in self.sk_root]
        return out


# Runtime twin of the fhh-race guard map — the "CollectionSession.*"
# entries of pyproject [tool.fhh-lint.guards], attr -> owning asyncio
# lock (drift-tested against the pyproject table in
# tests/test_concurrency.py).  Under FHH_DEBUG_GUARDS=1 (or
# Config.debug_guards) utils/guards.py arms a GuardedState descriptor
# per entry ON EVERY SESSION INSTANCE, so every access asserts the
# session's OWN verb lock is held by the current task — the per-tenant
# twin of the old server-global discipline, declared BEFORE the
# multi-tenant refactor multiplied the interleaving space (PR-9 ground
# rule).
_SESSION_GUARDS = {
    "frontier": "_verb_lock",
    "keys": "_verb_lock",
    "keys_parts": "_verb_lock",
    "key_planes": "_verb_lock",
    "alive_keys": "_verb_lock",
    "_children": "_verb_lock",
    "_last_shares": "_verb_lock",
    "_shard_children": "_verb_lock",
    "_shard_last": "_verb_lock",
    "_expand_ready": "_verb_lock",
    "_ingest_pools": "_verb_lock",
    "_admission": "_verb_lock",
    "_sketch_parts": "_verb_lock",
    "_sketch_root": "_verb_lock",
    "_ratchet_digest": "_verb_lock",
    "_window_sketch_root": "_verb_lock",
    "_export_epoch": "_verb_lock",
    "_import_seen": "_verb_lock",
    "_radix": "_verb_lock",
}


def planar_layout(radix: int) -> bool:
    """The layout rule behind :meth:`CollectionSession.planar`, as a
    function of what a config resolves to (``CollectorServer.engine_tags``
    reads it at start, before any session exists): plane-major with the
    Pallas expand wherever the process engine is the Pallas one, on one
    device or sharded — except under radix > 1 fusion."""
    return collect._expand_engine() and int(radix) == 1


class CollectionSession:
    """One collection's complete server-side state (see module doc).

    Everything here used to be a ``CollectorServer`` attribute; the
    crawl verbs (protocol/rpc.py) now receive the session resolved from
    the connection's ``__hello__`` and serialize on ``self._verb_lock``
    — per session, so two collections' verbs interleave on the event
    loop while each collection's own verbs stay strictly ordered."""

    def __init__(self, key: str, server_id: int, cfg: Config,
                 obs: obsmetrics.Registry, ckpt_dir: str | None):
        self.key = key
        self.server_id = server_id
        self.cfg = cfg
        self.obs = obs
        self.ckpt_dir = ckpt_dir
        self.last_used = time.monotonic()
        # heartbeat-gap instrument: when did this session last COMPLETE
        # a verb (last_used marks arrival — a wedged verb advances
        # last_used forever while last_progress stalls, which is exactly
        # the signal status.sessions.per_session.last_progress_s carries)
        self.last_progress = time.monotonic()
        # control connections currently bound to this session via
        # __hello__ (protocol/rpc.py increments at bind, decrements when
        # the connection closes): a session with live bindings is NEVER
        # idle-evicted, even when it holds no state yet — evicting it
        # would orphan the bound leader (its uploads would land in an
        # object the table no longer serves) and let a same-key
        # successor share its PlaneMux channel
        self.bound = 0
        # data-plane session state: which plane epoch this session's
        # channel handshake (coin flip + base-OT) ran against; 0 = never
        self.plane_epoch = 0
        # -- crawl state ---------------------------------------------------
        # radix-2^k level fusion (Config.crawl_radix_bits): bit levels
        # fused per crawl verb.  Fixed per session at construction —
        # leader and both servers derive it from the same config knob,
        # and checkpoint/export blobs stamp it so a restore under a
        # different radix refuses (validate-before-mutate)
        collect.check_radix(cfg.n_dims, cfg.crawl_radix_bits)
        self._radix: int = int(cfg.crawl_radix_bits)
        # the uploaded keys on their way to ``keys``: a bulk upload that
        # says where its batches go is placed on arrival (``key_planes``,
        # protocol/keyplanes.py); batches that arrive with no total (a
        # streaming window's pool, a leader that sends no ``n``) wait on
        # the host in ``keys_parts`` for ``concat_keys``
        self.keys_parts: list = []
        self.key_planes: keyplanes.KeyPlanes | None = None
        self.keys: IbDcfKeyBatch | None = None
        self.alive_keys: np.ndarray | None = None
        self.frontier: collect.Frontier | None = None
        self._children: object | None = None
        self._last_shares: np.ndarray | None = None
        self._shard_children: dict = {}
        self._shard_last: dict = {}
        self._shard_level: int | None = None
        self._mask_cache: tuple | None = None
        self._expand_ready: dict = {}
        # -- secure plane (per-session IKNP/base-OT endpoints) -------------
        self._ot: object | None = None
        self._ot_snd: object | None = None
        self._ot_rcv: object | None = None
        self._sec_seed: np.ndarray | None = None
        self._crawl_ctr: int = 0
        # -- sketch (malicious-secure) state -------------------------------
        self._sketch_parts: list = []
        self._sketch: object | None = None
        self._sketch_states: object | None = None
        self._sketch_pids: np.ndarray | None = None
        self._sketch_depth: int = 0
        # stored value-pair shares awaiting the next sketch_verify: a
        # list of (pairs, depth, field) — one entry per bit level of the
        # latest fused prune (a single entry at crawl_radix_bits=1)
        self._sketch_pairs: list | None = None
        self._sketch_seed: np.ndarray | None = None
        self._sketch_root: np.ndarray | None = None
        self._ratchet_digest: bytes | None = None
        # streaming malicious mode: the LOADED window's committed
        # challenge root (sketch.window_root, installed by window_load
        # from the sealed pool) — tree_init commits it as the ratchet
        # root instead of the raw session coin flip, so a recovered
        # window replays the identical challenge; None = batch flow
        self._window_sketch_root: np.ndarray | None = None
        # -- streaming ingest: PER-SESSION gate + pools --------------------
        # each collection gets its own admission controller (token
        # bucket, quotas, reservoir seed), so a flooding tenant exhausts
        # its own bucket and cannot starve another collection's window
        self._ingest_pools: dict = {}
        # seal instant of the window the CURRENT crawl loaded (None =
        # batch upload): final_shares observes seal-to-hitters from it
        self._window_seal_ts: float | None = None
        self._admission = resadmission.AdmissionController(
            max_window_keys=cfg.ingest_window_keys,
            rate_keys_per_s=cfg.ingest_rate_keys_per_s,
            burst_keys=cfg.ingest_burst_keys,
            client_quota=cfg.ingest_client_quota,
            shed=cfg.ingest_shed,
            seed=cfg.ingest_seed,
        )
        # admission gate: submit_keys runs the token-bucket arithmetic
        # in an executor behind this per-session lock, so a flooding
        # tenant's admission math never stalls the shared event loop
        # while the bucket still mutates strictly serialized per session
        self._adm_gate = asyncio.Lock()
        # -- fleet migration bookkeeping (protocol/fleet.py) ---------------
        # session_export stamps each blob with (boot id, export epoch);
        # session_import refuses a replayed stamp — double-importing one
        # export would double-land its in-flight sub_ids
        self._export_epoch = 0
        self._import_seen: set = set()
        # -- multi-chip mesh: per-session binding over the shared devices --
        # (ServerMesh.bind pins shard count to the client batch, which is
        # per-collection state; the underlying Mesh + jitted reduction
        # kernels are lru-cached at module level, so sessions share every
        # compiled program)
        k = smesh.resolve_data_devices(cfg.server_data_devices)
        self._mesh = smesh.ServerMesh(k, server_id) if k > 1 else None
        self._verb_lock = asyncio.Lock()
        # LAST: the sanitizer (a no-op unless FHH_DEBUG_GUARDS=1 or
        # cfg.debug_guards) wraps the already-constructed guarded state
        guards.install(self, _SESSION_GUARDS, force=cfg.debug_guards)

    # -- lifecycle --------------------------------------------------------

    def reset_state(self, reset_obs: bool = True) -> None:  # fhh-race: holds=_verb_lock (reached only from the reset verb, which _dispatch runs under this session's verb lock; sanitizer-validated)
        """The ``reset`` verb's body: a new collection run on this
        session opens clean (crawl state, sketch state, ingest pools,
        checkpoint namespace, telemetry).  ``reset_obs`` False skips the
        registry wipe — the DEFAULT session shares the SERVER's registry
        (single-tenant reports depend on that), so when other tenants
        are live its reset must not zero their shared-plane accounting
        (scheduler fills, dedup hits, control bytes)."""
        self.drop_keys()
        self.alive_keys = None
        self.frontier = None
        self._children = None
        self._last_shares = None
        self._shard_children.clear()
        self._shard_last.clear()
        self._shard_level = None
        self._expand_ready.clear()
        self._sketch_parts.clear()
        self._sketch = None
        self._sketch_states = None
        self._sketch_pids = None
        self._sketch_depth = 0
        self._sketch_pairs = None
        self._sketch_root = None
        self._ratchet_digest = None
        self._window_sketch_root = None
        self._ingest_pools.clear()  # a new collection's front door opens clean
        self._window_seal_ts = None
        self.ckpt_clear()  # a new collection must not resume an old one's
        if reset_obs:  # fresh per-collection phase/byte/fetch accounting
            self.obs.reset()
        if self._ot is not None:  # fresh GC/b2a randomness per collection
            import secrets as _secrets

            self._sec_seed = np.frombuffer(
                _secrets.token_bytes(16), dtype="<u4"
            ).copy()
            taint_guard.register(
                "CollectionSession._sec_seed", self._sec_seed
            )

    def clear_crawl_state(self) -> None:  # fhh-race: holds=_verb_lock (reached only from window_load/tree_restore, which run under this session's verb lock; sanitizer-validated)
        """Drop the crawl-plane state while leaving ingest pools and
        checkpoints alone (``window_load``'s reset-to-fresh-batch).
        The per-window SKETCH material clears with it: each window
        carries its own client sketch keys and its own committed
        challenge root (``window_load`` re-seeds both right after)."""
        self.keys = None
        self.alive_keys = None
        self.frontier = None
        self._children = None
        self._last_shares = None
        self._shard_children.clear()
        self._shard_last.clear()
        self._shard_level = None
        self._expand_ready.clear()
        self._sketch_parts.clear()
        self._sketch = None
        self._sketch_states = None
        self._sketch_pids = None
        self._sketch_depth = 0
        self._sketch_pairs = None
        self._sketch_root = None
        self._ratchet_digest = None
        self._window_sketch_root = None

    def idle(self) -> bool:  # fhh-race: atomic (read-only probe from the serve-loop session bind; one event-loop slice)
        """True when nothing durable lives here (eviction candidate)."""
        return (
            self.bound == 0
            and self.keys is None
            and not self.keys_parts
            and self.key_planes is None
            and self.frontier is None
            and not self._ingest_pools
            and not self._verb_lock.locked()
        )

    # -- engine/layout ----------------------------------------------------

    def planar(self) -> bool:  # fhh-race: atomic (pure read of init-time state: _radix is set at session construction)
        """This session's frontier LAYOUT, and with it its expand engine
        (one engine per layout): the process expand engine, except under
        radix > 1 fusion, whose multi-step expand is implemented on the
        interleaved/XLA engine only (collect.expand_share_bits_radix).
        A sharded session keeps the rule: its planar expand runs the
        Pallas engine per shard (``ServerMesh.expand_share_bits``)."""
        return planar_layout(self._radix)

    def expand(self, frontier, level, radix: int, want_children: bool):  # fhh-race: atomic (dispatch-only device work on init-time layout state; reached from the crawl verbs and the frame-arrival pre-expand)
        """One level's expansion of ``frontier`` on this session's
        engine: ``(packed, children)`` as ``collect.expand_share_bits``.
        The one place the engine is chosen from the layout rule."""
        if self._mesh is not None and self.planar():
            return self._mesh.expand_share_bits(
                self.keys, frontier, level, want_children
            )
        return collect.expand_share_bits_radix(
            self.keys, frontier, level, radix,
            want_children=want_children, use_pallas=self.planar(),
        )

    def init_frontier(self, f_bucket: int):  # fhh-race: atomic (dispatch-only device work on init-time layout state)
        """The root frontier at bucket ``f_bucket`` in this session's
        layout, client axis sharded on a sharded session."""
        with self.home():
            fr = collect.tree_init(self.keys, f_bucket, planar=self.planar())
            if self._mesh is not None:
                fr = self._mesh.shard_frontier(fr, self.planar())
        return fr

    def home(self):  # fhh-race: atomic (pure read of init-time state)
        """Context for this session's SYNCHRONOUS device sections
        (``tree_init``, ``tree_restore``, ``warmup``): what their jitted
        helpers create from nothing lands on the session's own mesh
        (:meth:`ServerMesh.home`), not on the process's default device.
        A one-device session runs on the default device: no-op."""
        return (
            self._mesh.home() if self._mesh is not None
            else contextlib.nullcontext()
        )

    def crawl_radix(self, level) -> int:  # fhh-race: atomic (pure read of init-time state)
        """Fused bit count of the crawl round based at bit ``level``:
        the session radix, clipped at the tail (data_len not divisible
        by k leaves a final partial round of ``L - level`` bits)."""
        L = self.keys.cw_seed.shape[-2]
        return min(self._radix, L - int(level))

    def drop_keys(self) -> None:  # fhh-race: holds=_verb_lock (reached only from the reset and session_export verbs under this session's verb lock; sanitizer-validated)
        """Let go of the collection's keys wherever they are: resident,
        on their way in (``key_planes``) or waiting on the host."""
        self.keys_parts.clear()
        self.key_planes = None
        self.keys = None
        self.obs.gauge("key_plane_bytes", 0)

    def add_key_batch(self, batch: IbDcfKeyBatch, n: int | None = None, lo: int | None = None) -> None:  # fhh-race: atomic (the body of the unlocked add_keys fast path: dispatch only, never suspends)
        """One batch of uploaded keys.  Of a bulk upload of ``n``
        clients, rows ``[lo, lo + B)``: written to its place on this
        session's chip(s) now (protocol/keyplanes.py).  The first batch
        of an upload allocates the planes, and with them lets go of a
        finished upload's: an ``add_keys`` after a crawl opens a new key
        set.  With no total the batch waits on the host for
        ``concat_keys``; the two do not mix in one collection."""
        if n is None:
            if self.key_planes is not None:
                raise RuntimeError(
                    "add_keys without a total into an upload that has one: "
                    "reset the collection first"
                )
            self.keys_parts.append(batch)
            return
        kp = self.key_planes
        if kp is None or kp.sealed:
            if self.keys_parts:
                raise RuntimeError(
                    "add_keys with a total after batches without one: "
                    "reset the collection first"
                )
            self.keys = None  # the planes of the upload before, if any
            devices = None
            if self._mesh is not None:
                devices = self._mesh.bind(int(n))._active_devices()
            # a lost chip is re-placed from the host (_mesh_recover ->
            # tree_restore), and only where there is a checkpoint to
            # stand on: such a session alone keeps its host batches
            kp = self.key_planes = keyplanes.KeyPlanes(
                n, batch, devices, keep_host=self.ckpt_dir is not None
            )
            # key-plane residency (obs.devmem): the flagship's "1.51
            # chips of key storage" risk as a live per-collection gauge,
            # set where the planes are allocated
            self.obs.gauge("key_plane_bytes", kp.nbytes)
        elif int(n) != kp.n:
            raise RuntimeError(
                f"add_keys for {int(n)} clients into an upload of {kp.n}"
            )
        self.obs.count("keys_placed_bytes", kp.add(lo, batch))

    def ready_keys(self, verb: str, again: bool = False) -> None:  # fhh-race: holds=_verb_lock (reached only from tree_init/tree_restore/warmup under this session's verb lock; sanitizer-validated)
        """``self.keys`` resident for ``verb``, from wherever the upload
        left them.  A bulk upload is on the chip already: the first call
        checks that its rows are whole and waits for the last write
        (span ``key_place``: until RESIDENT, not until queued; the sync
        costs nothing, level 0's expand cannot start before the keys
        are there), every later one finds the planes (``key_planes_
        reused``).  Batches that came with no total are concatenated
        and placed (``concat_keys``), where the planes are missing or,
        ``again``, by every ``tree_init``."""
        kp = self.key_planes
        if kp is not None:
            held = kp.host_nbytes()
            if self.keys is not None:
                self.obs.count("key_planes_reused")
            else:
                with self.obs.span("key_place") as sp:
                    self.keys = kp.finish(
                        None if self._mesh is None
                        else self._mesh.bind(kp.n).mesh
                    )
                obsmod.emit(
                    "ingest.keys_resident",
                    server=self.server_id,
                    collection=self.key,
                    clients=kp.n,
                    batches=len(kp.written),
                    plane_bytes=kp.nbytes,
                    host_bytes_held=held,
                    wait_s=round(sp.seconds, 4),
                )
        elif not self.keys_parts:
            raise RuntimeError(f"{verb} before add_keys")
        else:
            held = sum(devmem.tree_nbytes(tuple(p)) for p in self.keys_parts)
            if self.keys is None or again:
                with self.obs.span("concat_keys"):
                    self.concat_keys()
        self.obs.gauge("key_host_bytes_held", held)

    def concat_keys(self) -> None:  # fhh-race: holds=_verb_lock (reached only from ready_keys under this session's verb lock; sanitizer-validated)
        """Materialize ``self.keys`` from batches that arrived with no
        total (``keys_parts``: a streaming window's pool, a leader that
        sends no ``n``), in order of arrival.  Under the multi-chip mesh
        the batch binds the active shard count and the key planes land
        client-axis-sharded across the server's own devices.  The
        ``key_place`` span is the second half of it — the copies, after
        the host concatenate — and ends when the planes are RESIDENT."""
        self.keys = IbDcfKeyBatch(
            *[
                # fhh-lint: disable=chunked-device-readback,host-sync-in-hot-loop (wire input: the uploaded chunks are host numpy already — np.asarray is a no-copy view; runs once per collection/restore, never per level)
                np.concatenate([np.asarray(p[i]) for p in self.keys_parts])
                for i in range(len(self.keys_parts[0]))
            ]
        )
        with self.obs.span("key_place"):
            if self._mesh is not None:
                self._mesh.bind(self.keys.cw_seed.shape[0])
                self.keys = self._mesh.shard_keys(self.keys)
            else:
                # resident on the (effective default) device: left as host
                # numpy, every level's expand would re-upload the whole batch
                self.keys = jax.device_put(self.keys)
            # fhh-lint: disable=host-sync-in-hot-loop (once per collection/restore, never per level)
            jax.block_until_ready(self.keys)
        self.obs.gauge(
            "key_plane_bytes", devmem.tree_nbytes(tuple(self.keys))
        )

    def concat_sketch(self) -> None:  # fhh-race: holds=_verb_lock (reached only from tree_init/tree_restore under this session's verb lock; sanitizer-validated)
        """Materialize ``self._sketch`` from the uploaded chunks."""
        leaves = [jax.tree.leaves(p) for p in self._sketch_parts]
        # fhh-lint: disable=chunked-device-readback,host-sync-in-hot-loop (wire input: uploaded sketch chunks are host numpy; once per collection/restore)
        cat = [np.concatenate([np.asarray(p[i]) for p in leaves])
               for i in range(len(leaves[0]))]
        self._sketch = jax.tree.unflatten(
            jax.tree.structure(_SKETCH_TREEDEF), cat
        )

    def challenge_seed(self, level: int) -> np.ndarray:  # fhh-race: holds=_verb_lock (reached only from sketch_verify under this session's verb lock; sanitizer-validated)
        """This level's sketch challenge via the ratchet (sketch.py):
        hash(committed root ‖ level ‖ transcript digest).  Falls back to
        the raw session seed only when the ratchet was never committed
        (sketch keys without tree_init — a protocol error soon anyway)."""
        if self._sketch_root is None:
            return self._sketch_seed
        return sketchmod.ratchet_seed(
            self._sketch_root, level, self._ratchet_digest
        )

    # fhh-race: holds=_verb_lock (reached only from tree_prune/tree_prune_last under this session's verb lock; sanitizer-validated)
    def advance_sketch(self, level: int, parent: np.ndarray,
                       pat_bits: np.ndarray, n_alive: int) -> None:
        """Advance the frontier-following sketch DPF states with the same
        survivor table as the count frontier (one 1-D sketch tree per
        dimension; dim j's direction is pattern bit j), storing the new
        depth's value-pair shares gated by node liveness AND per-dim
        prefix DEDUPLICATION: in d > 1 the count frontier is a product —
        two frontier nodes routinely share the same dim-j prefix, and
        counting an honest one-hot entry twice makes ``<r,x>² != <r²,x>``
        (with r_i + r_j in place of a single r).  Each dim keeps only the
        FIRST slot of every distinct prefix; the dedup table derives from
        the public survivor table, so both servers gate identically.

        Radix fusion: ``pat_bits`` may carry a step axis ([F, r, d] —
        a fused prune at base bit level ``level``).  The sketch states
        advance r eval_bit steps and EVERY step's value-pair shares are
        stored (one ``_sketch_pairs`` entry per depth, each gated by its
        own depth's dedup table), so the next batched ``sketch_verify``
        opens every intermediate depth's Beaver slab exactly once and
        keeps k=1's detection guarantee — a payload forged at a depth
        the fused crawl never takes counts at is still caught, one
        fused level later than a sequential crawl would catch it."""
        L = self.keys.cw_seed.shape[-2]
        k = self._sketch.key  # batch [N, d]
        d = k.root_seed.shape[1]
        # fhh-lint: disable=host-sync-in-hot-loop (wire input: host numpy)
        parent = np.asarray(parent)
        pat_bits = np.asarray(pat_bits)
        if pat_bits.ndim == 2:  # radix-1 callers: [F, d] -> [F, 1, d]
            pat_bits = pat_bits[:, None, :]
        r = pat_bits.shape[1]
        st = jax.tree.map(lambda a: a[parent], self._sketch_states)
        F2 = parent.shape[0]
        parent_pid = self._sketch_pids[parent[:n_alive]]  # [n_alive, d]
        pids = self._sketch_pids
        entries = []
        for t in range(r):
            bl = level + t  # absolute bit level of this step
            last = bl == L - 1
            fld = F255 if last else FE62
            direction = jnp.asarray(pat_bits[:, t, :], bool)[:, None, :]
            cw = tuple(a[None] for a in dpf.level_cw(k, bl))  # [1, N, d, ...]
            cwv = (k.cw_val[..., bl, :] if not last else k.cw_val_last)[None]
            st, pair = dpf.eval_bit(
                cw, st, direction, cwv, k.key_idx[None], fld, sketchmod.LANES
            )  # pair [F, N, d, LANES(, limbs)]
            # this depth's per-dim prefix dedup: key = parent pid chain +
            # the step bits THROUGH step t (two fused survivors sharing a
            # dim-j prefix at this depth are one node of dim j's 1-D
            # sketch tree and must be counted once, even if they diverge
            # at a later step)
            pids = np.zeros((F2, d), np.int32)
            keep = np.zeros((F2, d), bool)
            for j in range(d):
                key_j = np.stack(
                    [parent_pid[:, j]]
                    + [pat_bits[:n_alive, u, j].astype(np.int32)
                       for u in range(t + 1)],
                    1,
                )
                _, inv = np.unique(key_j, axis=0, return_inverse=True)
                pids[:n_alive, j] = inv
                _, first = np.unique(inv, return_index=True)
                keep[first, j] = True
            gate = jnp.asarray(
                keep.reshape((F2, 1, d) + (1,) * (pair.ndim - 3))
            )
            entries.append((jnp.where(gate, pair, 0), bl + 1, fld))
        self._sketch_states = st
        self._sketch_pids = pids  # final depth's table seeds the next prune
        self._sketch_depth = level + r
        self._sketch_pairs = entries

    # -- crawl span bookkeeping -------------------------------------------

    def shard_frontier_view(self, shard):  # fhh-race: atomic (pure slice of the frontier, never suspends; reached from the frame-arrival pre-expand)
        """The frontier view one crawl verb works on: the whole frontier
        (``shard`` None) or the node span ``[lo, hi)`` of it."""
        if shard is None:
            return self.frontier
        return collect.frontier_slice(
            self.frontier, shard[0], shard[1], planar=self.planar()
        )

    def stash_children(self, level, shard, children) -> None:  # fhh-race: holds=_verb_lock (reached only from the crawl verbs under this session's verb lock; sanitizer-validated)
        """Bank one crawl's child-state cache for the coming prune: whole
        level under ``_children``, shards keyed by span ``lo`` (a shard
        RE-RUN overwrites its slot — exactly the retry semantics)."""
        if shard is None:
            self._children = children
            return
        if self._shard_level != int(level):
            # first shard of a new level: drop any stale spans
            self._shard_children.clear()
            self._shard_last.clear()
            self._shard_level = int(level)
        self._children = None  # sharded levels assemble at prune time
        if children is not None:
            self._shard_children[int(shard[0])] = children

    def assemble_shard_children(self):  # fhh-race: holds=_verb_lock (reached only from tree_prune under this session's verb lock; sanitizer-validated)
        """Stitch the per-shard child caches back into one full-level
        cache; refuses a torn level (a missing span would silently
        advance garbage for its nodes)."""
        children = collect.children_cat(sorted(self._shard_children.items()))
        got = (
            children.seed.shape[4]
            if isinstance(children, collect.PlanarChildren)
            else children.seed.shape[0]
        )
        if got != self.frontier.f_bucket:
            raise RuntimeError(
                f"sharded crawl incomplete: child caches cover {got} of "
                f"{self.frontier.f_bucket} frontier slots"
            )
        self._shard_children.clear()
        return children

    def mask_rows(self, level: int, shard, C: int, f255: bool) -> np.ndarray:  # fhh-race: holds=_verb_lock (reached only from tree_crawl/_last under this session's verb lock; sanitizer-validated)
        """Wire-format mask rows for one (level, shard): the FULL-level
        stream sliced to the shard's node rows — the leader's uniform
        v0 - v1 reconstruction must be shard-oblivious, so a node's mask
        cannot depend on how the level was sharded.  One-entry cache."""
        F = self.frontier.f_bucket
        key = (level, F, f255)
        if self._mask_cache is None or self._mask_cache[0] != key:
            full = (
                mask_f255(level, F * C).reshape(F, C, 8)
                if f255
                else mask_fe62(level, F * C).reshape(F, C)
            )
            self._mask_cache = (key, full)
        full = self._mask_cache[1]
        return full if shard is None else full[shard[0] : shard[1]]

    def keys_fp(self) -> np.ndarray:  # fhh-race: holds=_verb_lock (reached only from tree_checkpoint/tree_restore under this session's verb lock; sanitizer-validated)
        """Cheap key identity for checkpoint/restore pairing: key_idx +
        root seeds.  An OPERATIONAL check (did the leader re-upload the
        same batch it crawled with), not a cryptographic one."""
        h = hashlib.sha256()
        # fhh-lint: disable=host-sync-in-hot-loop (checkpoint/restore identity check: once per checkpoint, not per level)
        h.update(np.ascontiguousarray(np.asarray(self.keys.key_idx)))
        # fhh-lint: disable=host-sync-in-hot-loop (as above)
        h.update(np.ascontiguousarray(np.asarray(self.keys.root_seed)))
        return np.frombuffer(h.digest(), np.uint8)

    # -- checkpoint namespace ---------------------------------------------

    def ckpt_prefix(self) -> str:
        """Per-collection checkpoint filename prefix.  The default
        session keeps the legacy name so single-tenant deployments (and
        their existing on-disk checkpoints) are untouched; every other
        collection gets its own namespace."""
        if self.key == DEFAULT_COLLECTION:
            return f"fhh_server{self.server_id}_l"
        return f"fhh_server{self.server_id}_c{self.key}_l"

    def ckpt_levels(self) -> list:
        """Level stamps of this session's on-disk checkpoints, ascending
        NUMERICALLY (the same ordering :meth:`ckpt_prune` keeps by)."""
        if self.ckpt_dir is None or not os.path.isdir(self.ckpt_dir):
            return []
        prefix = self.ckpt_prefix()
        levels = []
        for name in os.listdir(self.ckpt_dir):
            if name.startswith(prefix) and name.endswith(".npz"):
                try:
                    levels.append(int(name[len(prefix):-4]))
                except ValueError:
                    continue
        return sorted(levels)

    def ckpt_path(self, level: int) -> str:
        # level-stamped: a torn checkpoint round (one server wrote level k,
        # the other died first) must leave BOTH servers able to restore the
        # same earlier level
        return os.path.join(
            self.ckpt_dir, f"{self.ckpt_prefix()}{level}.npz"
        )

    def ckpt_prune(self, keep: int = 2) -> None:
        """Drop all but the newest ``keep`` checkpoint levels of THIS
        collection's namespace (other sessions' files are untouched)."""
        prefix = self.ckpt_prefix()
        found = []
        for name in os.listdir(self.ckpt_dir):
            if name.startswith(prefix) and name.endswith(".npz"):
                try:
                    found.append((int(name[len(prefix):-4]), name))
                except ValueError:
                    continue
        found.sort()
        # NB: found[:-keep] would be the EMPTY slice at keep=0 ([-0] == [0])
        doomed = found[: len(found) - keep] if keep else found
        for _, name in doomed:
            os.remove(os.path.join(self.ckpt_dir, name))

    def ckpt_clear(self) -> None:
        if self.ckpt_dir is not None and os.path.isdir(self.ckpt_dir):
            self.ckpt_prune(keep=0)

    # -- ingest pools (per-session gate) ----------------------------------

    def ingest_pool(self, window: int) -> _WindowPool:  # fhh-race: atomic (create-or-get + bounded eviction in one event-loop slice; called from the unlocked ingest fast path and from locked verbs)
        """Create-or-get the pool for ``window``; live-window count is
        BOUNDED (``cfg.ingest_windows_retained``) so a runaway window id
        can never grow server memory — the refusal is loud, never a
        silent drop."""
        pool = self._ingest_pools.get(window)
        if pool is None:
            if len(self._ingest_pools) >= max(
                1, self.cfg.ingest_windows_retained
            ):
                # sealed EMPTY windows are fully consumed (window_load
                # skips them, so only loads drop pools): evict the
                # oldest such before refusing — a quiet stretch of idle
                # windows must not wedge the front door
                idle = [
                    w for w in sorted(self._ingest_pools)
                    if self._ingest_pools[w].sealed
                    and not self._ingest_pools[w].entries
                ]
                if idle:
                    del self._ingest_pools[idle[0]]
            if len(self._ingest_pools) >= max(
                1, self.cfg.ingest_windows_retained
            ):
                raise RuntimeError(
                    f"ingest window {window} would exceed the "
                    f"{self.cfg.ingest_windows_retained} live-window bound "
                    f"(live: {sorted(self._ingest_pools)})"
                )
            pool = self._ingest_pools[window] = _WindowPool(
                window, self._admission.window(window)
            )
        return pool

    def ingest_status(self) -> dict:  # fhh-race: holds=_verb_lock (reached only from the status verb under this session's verb lock; sanitizer-validated)
        """Front-door health for ``status``: per-window occupancy, the
        unsealed-queue depth, and the admit/shed/reject counters."""
        pools = [self._ingest_pools[w] for w in sorted(self._ingest_pools)]
        unsealed = [p for p in pools if not p.sealed]
        return {
            "current_window": (
                unsealed[-1].window if unsealed
                else (pools[-1].window if pools else None)
            ),
            "queue_depth": sum(p.keys for p in unsealed),
            "admitted": sum(p.admitted_keys for p in pools),
            "shed": sum(p.shed_keys for p in pools),
            "rejected": sum(p.rejected for p in pools),
            "windows": {
                str(p.window): {
                    "keys": p.keys,
                    "subs": len(p.entries),
                    "sealed": p.sealed,
                }
                for p in pools
            },
        }

    # verdict codes in the checkpoint blob: slot >= 0, -1 = appended in
    # arrival order (no slot), -2 = reservoir-shed
    _ING_APPEND, _ING_SHED = -1, -2

    def ingest_ckpt_fields(self, blob: dict) -> None:  # fhh-race: holds=_verb_lock (reached only from tree_checkpoint under this session's verb lock; sanitizer-validated)
        """Flatten every live ingest pool into ``ing_*`` npz fields."""
        ws = sorted(self._ingest_pools)
        if not ws:
            return
        blob["ing_windows"] = np.asarray(ws, np.int64)
        for i, w in enumerate(ws):
            p = self._ingest_pools[w]
            blob[f"ing{i}_meta"] = np.array(
                [w, int(p.sealed), p.keys, p.admitted_keys, p.shed_keys,
                 p.rejected, len(p.entries), p.wa.subs, p.wa.keys,
                 -1 if p.wa.sub_keys is None else p.wa.sub_keys,
                 p.wa.pending_draws],
                np.int64,
            )
            sub_ids, codes = [], []
            for sid, resp in p.verdicts.items():
                sub_ids.append(sid)
                if resp.get("shed"):
                    codes.append(self._ING_SHED)
                elif resp.get("slot") is None:
                    codes.append(self._ING_APPEND)
                else:
                    codes.append(int(resp["slot"]))
            blob[f"ing{i}_sub_ids"] = np.array(sub_ids, dtype=str)
            blob[f"ing{i}_sub_codes"] = np.array(codes, np.int64)
            blob[f"ing{i}_lens"] = np.array(
                [int(e[0].shape[0]) for e in p.entries], np.int64
            )
            n_leaf = len(p.entries[0]) if p.entries else 0
            blob[f"ing{i}_nleaf"] = np.int64(n_leaf)
            for j in range(n_leaf):
                # entries are host arrays already (submit_keys converts)
                blob[f"ing{i}_leaf{j}"] = np.concatenate(
                    [e[j] for e in p.entries]
                )
            blob[f"ing{i}_clients"] = np.array(
                list(p.wa.client_keys.keys()), dtype=str
            )
            blob[f"ing{i}_client_keys"] = np.array(
                list(p.wa.client_keys.values()), np.int64
            )
            if p.wa.reservoir is not None:
                blob[f"ing{i}_res"] = p.wa.reservoir.state()
            if p.sealed_at is not None:
                # the seal instant rides the checkpoint so a recovered
                # window's seal-to-hitters SLO observation survives the
                # restart (the replayed seal verb is a no-op on an
                # already-sealed pool and must not restamp the clock)
                blob[f"ing{i}_sealed_at"] = np.float64(p.sealed_at)
            if p.sk_root is not None:
                # the window's committed sketch-challenge root: a
                # recovered malicious window MUST replay the identical
                # challenge (a restarted server's fresh plane coin flip
                # would otherwise re-root the ratchet and turn the
                # re-run's slab openings into a <r - r', x> leak)
                blob[f"ing{i}_skroot"] = np.array(p.sk_root, np.uint32)

    @staticmethod
    def ingest_validate(z: dict, path: str) -> list | None:
        """Validate-before-mutate for the ``ing_*`` fields: parse every
        window's record fully (shapes cross-checked) BEFORE any pool is
        touched; a torn tail refuses loudly with live state intact.
        Returns the parsed per-window records, or None when the blob
        carries no ingest fields (a pre-streaming checkpoint)."""
        if "ing_windows" not in z:
            return None
        parsed = []
        # fhh-lint: disable=host-sync-in-hot-loop (checkpoint blob: host npz entries)
        ws = np.asarray(z["ing_windows"], np.int64)  # checkpoint blob: host
        for i, w in enumerate(ws):
            req_keys = {f"ing{i}_meta", f"ing{i}_sub_ids", f"ing{i}_sub_codes",
                        f"ing{i}_lens", f"ing{i}_nleaf"}
            missing = req_keys - set(z)
            if missing:
                raise RuntimeError(
                    f"tree_restore: checkpoint at {path} is missing ingest "
                    f"fields {sorted(missing)} (truncated write?)"
                )
            meta = np.array(z[f"ing{i}_meta"], np.int64)
            if meta.shape != (11,) or int(meta[0]) != int(w):
                raise RuntimeError(
                    f"tree_restore: checkpoint at {path} has a malformed "
                    f"ingest meta row for window {int(w)}"
                )
            lens = np.array(z[f"ing{i}_lens"], np.int64)
            n_leaf = int(z[f"ing{i}_nleaf"])
            if lens.shape[0] != int(meta[6]):
                raise RuntimeError(
                    f"tree_restore: ingest window {int(w)} entry table is "
                    f"torn ({lens.shape[0]} lengths vs {int(meta[6])} slots)"
                )
            leaves = []
            for j in range(n_leaf):
                key = f"ing{i}_leaf{j}"
                if key not in z:
                    raise RuntimeError(
                        f"tree_restore: ingest window {int(w)} is missing "
                        f"leaf {j} (truncated write?)"
                    )
                leaf = z[key]  # npz entries are host ndarrays
                if leaf.shape[0] != int(lens.sum()):
                    raise RuntimeError(
                        f"tree_restore: ingest window {int(w)} leaf {j} "
                        f"covers {leaf.shape[0]} keys, lengths sum to "
                        f"{int(lens.sum())}"
                    )
                leaves.append(leaf)
            sub_ids = z[f"ing{i}_sub_ids"]
            codes = np.array(z[f"ing{i}_sub_codes"], np.int64)
            if sub_ids.shape[0] != codes.shape[0]:
                raise RuntimeError(
                    f"tree_restore: ingest window {int(w)} verdict table "
                    "is torn"
                )
            parsed.append({
                "meta": meta,
                "lens": lens,
                "leaves": leaves,
                "sub_ids": sub_ids,
                "codes": codes,
                "clients": np.array(z.get(f"ing{i}_clients", [])),
                "client_keys": np.array(
                    z.get(f"ing{i}_client_keys", []), np.int64
                ),
                "res": (
                    np.array(z[f"ing{i}_res"], np.uint64)
                    if f"ing{i}_res" in z
                    else None
                ),
                # optional (blobs from before the SLO clock omit it)
                "sealed_at": (
                    float(z[f"ing{i}_sealed_at"])
                    if f"ing{i}_sealed_at" in z
                    else None
                ),
                # optional (semi-honest windows / older blobs omit it)
                "sk_root": (
                    np.array(z[f"ing{i}_skroot"], np.uint32)
                    if f"ing{i}_skroot" in z
                    else None
                ),
            })
        return parsed

    def ingest_restore_apply(self, parsed: list) -> None:  # fhh-race: holds=_verb_lock (reached only from tree_restore under this session's verb lock; sanitizer-validated)
        """Rebuild the ingest pools from validated records (the mutation
        half of the restore contract)."""
        from ..native import Reservoir

        self._ingest_pools.clear()
        for rec in parsed:
            meta = rec["meta"]
            w = int(meta[0])
            wa = self._admission.window(w)
            pool = _WindowPool(w, wa)
            pool.sealed = bool(meta[1])
            pool.sealed_at = rec.get("sealed_at")
            pool.sk_root = rec.get("sk_root")
            pool.keys = int(meta[2])
            pool.admitted_keys = int(meta[3])
            pool.shed_keys = int(meta[4])
            pool.rejected = int(meta[5])
            wa.subs = int(meta[7])
            wa.keys = int(meta[8])
            wa.sub_keys = None if int(meta[9]) < 0 else int(meta[9])
            wa.pending_draws = int(meta[10])
            bounds = np.concatenate([[0], np.cumsum(rec["lens"])])
            pool.entries = [
                tuple(
                    leaf[bounds[e]:bounds[e + 1]] for leaf in rec["leaves"]
                )
                for e in range(len(rec["lens"]))
            ]
            for sid, code in zip(rec["sub_ids"], rec["codes"]):
                code = int(code)
                if code == self._ING_SHED:
                    resp = {"admitted": False, "shed": True, "window": w}
                elif code == self._ING_APPEND:
                    resp = {"admitted": True, "slot": None, "window": w}
                else:
                    resp = {"admitted": True, "slot": code, "window": w}
                pool.verdicts[str(sid)] = resp
            wa.client_keys = {
                str(c): int(n)
                for c, n in zip(rec["clients"], rec["client_keys"])
            }
            if rec["res"] is not None:
                wa.reservoir = Reservoir.from_state(rec["res"])
            self._ingest_pools[w] = pool


class SessionTable:
    """Bounded keyed table of :class:`CollectionSession`.

    ``get`` creates on first use; the table is bounded by
    ``cfg.collection_sessions_max`` — at the cap an IDLE session (no
    keys, no frontier, no ingest pools, not mid-verb) is evicted
    oldest-first, otherwise the new collection is refused loudly (a
    server must never silently drop a live tenant's state)."""

    def __init__(self, server_id: int, cfg: Config,
                 server_obs: obsmetrics.Registry, ckpt_dir: str | None):
        self.server_id = server_id
        self.cfg = cfg
        self.server_obs = server_obs
        self.ckpt_dir = ckpt_dir
        self._by_key: dict[str, CollectionSession] = {}

    def __len__(self) -> int:
        return len(self._by_key)

    def keys(self):
        return list(self._by_key)

    def items(self):
        return list(self._by_key.items())

    def peek(self, key: str) -> CollectionSession | None:
        return self._by_key.get(key)

    def default(self) -> CollectionSession:
        return self.get(DEFAULT_COLLECTION)

    def get(self, key: str | None = None) -> CollectionSession:  # fhh-race: atomic (serve-loop session table: create-or-get + eviction never suspends; all connections share one event loop)
        key = key or DEFAULT_COLLECTION
        if not _KEY_RE.match(key):
            raise ValueError(
                f"collection key {key!r} is invalid (want "
                "[A-Za-z0-9._-]{1,64}: it names checkpoint files and "
                "wire channels)"
            )
        cs = self._by_key.get(key)
        if cs is None:
            cap = max(1, self.cfg.collection_sessions_max)
            if len(self._by_key) >= cap:
                idle = sorted(
                    (k for k, s in self._by_key.items() if s.idle()),
                    key=lambda k: self._by_key[k].last_used,
                )
                if idle:
                    del self._by_key[idle[0]]
            if len(self._by_key) >= cap:
                raise RuntimeError(
                    f"collection {key!r} would exceed the "
                    f"{cap}-session bound and no live session is idle "
                    f"(live: {sorted(self._by_key)})"
                )
            reg = (
                self.server_obs
                if key == DEFAULT_COLLECTION
                else obsmetrics.Registry(f"server{self.server_id}:{key}")
            )
            cs = self._by_key[key] = CollectionSession(
                key, self.server_id, self.cfg, reg, self.ckpt_dir
            )
            if key != DEFAULT_COLLECTION:
                obsmod.emit(
                    "session.created",
                    server=self.server_id,
                    collection=key,
                )
        cs.last_used = time.monotonic()
        return cs


class _PlaneFailure:
    """Queue sentinel delivering a plane death to a blocked recv."""

    __slots__ = ("err",)

    def __init__(self, err: BaseException):
        self.err = err


class PlaneMux:
    """Per-collection demux of the server↔server data plane.

    Every data-plane frame is ``(channel, payload)``; the plane's
    reader thread (:class:`~.wire.PlaneStreams`) hands each frame to
    :meth:`route` on the loop, which puts the payload into its
    channel's FIFO queue.  Sends interleave freely (each frame goes to
    the writer thread whole), and each receiver reads only its own
    channel — so two collections' 2PC exchanges share the plane without
    any cross-tenant ordering assumptions.  ``epoch`` counts planes: a
    session whose channel handshake ran against an older epoch must
    re-key before trusting the plane again (protocol/rpc.py
    ``_ensure_session_plane``), and a frame or a loss that a replaced
    plane still reports is dropped by its epoch."""

    # per-channel depth bound: the positional protocol keeps at most a
    # handful of frames in flight per collection (one exchange at a
    # time under the session's verb lock); hitting this bound means the
    # two servers' channel streams diverged — fail the plane loudly.
    MAX_DEPTH = 1024

    def __init__(self, route_count=None, tag: str = "plane"):
        self.epoch = 0
        self._queues: dict[str, asyncio.Queue] = {}
        self._err: BaseException | None = None
        # (chan, nbytes) byte-accounting hook, resolved by the server to
        # the owning session's registry
        self._route_count = route_count
        # trace component name for frame-arrival instants (fhh-trace):
        # the server passes "server{id}" so the merged timeline can draw
        # the peer's span -> this server's wire arrival
        self.tag = tag

    def attach(self) -> int:
        """Bind the mux to a fresh plane: fail every waiter of the old
        one (their frames can never arrive) and reset the channels.
        Returns the new epoch, which the plane's frames and its loss
        are to be reported under (:meth:`route`, :meth:`lost`)."""
        self.epoch += 1
        old, self._queues = self._queues, {}
        err = ConnectionError("data plane replaced by a new connection")
        for q in old.values():
            self._deliver_failure(q, err)
        self._err = None
        return self.epoch

    def close(self) -> None:
        self.fail(ConnectionError("data plane closed"))

    def lost(self, epoch: int, err: BaseException) -> None:
        """The plane of ``epoch`` was closed or lost."""
        if epoch == self.epoch and self._err is None:
            self.fail(err)

    def fail(self, err: BaseException) -> None:
        """Fail every current and future recv with ``err`` (until the
        next :meth:`attach`)."""
        self._err = err
        for q in self._queues.values():
            self._deliver_failure(q, err)

    @staticmethod
    def _deliver_failure(q: asyncio.Queue, err: BaseException) -> None:
        try:
            q.put_nowait(_PlaneFailure(err))
        except asyncio.QueueFull:
            # drop one data frame to make room: the plane is dead, the
            # waiter must learn it either way
            try:
                q.get_nowait()
            except asyncio.QueueEmpty:
                pass
            q.put_nowait(_PlaneFailure(err))

    def _queue(self, chan: str) -> asyncio.Queue:
        q = self._queues.get(chan)
        if q is None:
            # fhh-lint: disable=unbounded-queue (bounded: MAX_DEPTH is a positive maxsize; overflow fails the plane loudly in route)
            q = self._queues[chan] = asyncio.Queue(maxsize=self.MAX_DEPTH)
        return q

    async def recv(self, chan: str, reg=None):
        """Next payload on ``chan`` (FIFO per channel).  Raises the
        plane's death as ConnectionError — the same failure shape a
        direct socket read gave, so every existing recovery path
        (plane_reset, shard retry, supervisor rollback) works
        unchanged.

        The plane's reader thread reads the frame outside the
        receiving verb's context and stamps the wall clock on it;
        ``reg``, the receiver's registry, turns the stamps into its
        spans HERE, on the loop thread, where the registry's span stack
        lives: ``peer_wait`` (this call -> the frame's header read; 0
        where the frame was already there: time the peer's compute,
        pickle and write own), ``wire_read`` (header -> body held) and
        ``wire_unpickle``, as the thread took them: a frame read before
        this call lies before it in the trace too."""
        q = self._queue(chan)
        if self._err is not None and q.empty():
            # a channel born on a dead plane; one that lived through the
            # death holds the frames that arrived whole before it, and
            # then the failure (fail)
            raise ConnectionError(
                f"data plane down: {self._err!r}"
            ) from self._err
        t0 = time.time()
        # fhh-lint: disable=unbounded-await (deliberately unbounded like the serve-loop reads: response waits are bounded at the caller — per-verb deadlines on the control plane, TCP keepalive on the data plane)
        item = await q.get()
        if isinstance(item, _PlaneFailure):
            # leave the failure visible to any later recv on this chan
            self._deliver_failure(q, item.err)
            raise ConnectionError(
                f"data plane down: {item.err!r}"
            ) from item.err
        payload, hdr, stamps = item
        tracing = obsmod.trace.enabled()
        if reg is not None and stamps is not None:
            t_hdr, t_body, t_done = stamps
            sp = reg.current_span()
            level = None if sp is None else sp.level
            for name, a, b in (
                ("peer_wait", t0, max(t0, t_hdr)),
                ("wire_read", t_hdr, t_body),
                ("wire_unpickle", t_body, t_done),
            ):
                reg.timer_add(name, b - a, level)
                if tracing:
                    obsmod.trace.span_at(name, reg.name, a, b - a, level)
        if hdr is not None and tracing:
            # the peer stamped its (trace, span) onto the frame's
            # session header: an arrival instant parented under the
            # SENDER's span ties the two servers' timelines together
            obsmod.trace.instant(
                "plane_recv", comp=self.tag,
                trace_id=hdr[0], parent=hdr[1], chan=chan,
            )
        return payload

    def route(self, epoch: int, nbytes: int, frame, stamps=None) -> None:
        """One received frame into its channel's queue, on the loop
        thread.  Frames are (collection, payload) — or, under fhh-trace,
        (collection, payload, (trace_id, span_id)): the session header
        grows the sender's trace context.  ``stamps`` are the reader
        thread's (:meth:`recv`).  A frame that cannot be routed — a
        channel past ``MAX_DEPTH`` (the servers' streams diverged),
        something that is no frame — is a plane death for every blocked
        receiver."""
        if epoch != self.epoch or self._err is not None:
            return
        try:
            chan, payload = frame[0], frame[1]
            hdr = frame[2] if len(frame) > 2 else None
            if self._route_count is not None:
                self._route_count(chan, nbytes)
            self._queue(chan).put_nowait((payload, hdr, stamps))
        # fhh-lint: disable=broad-except (transport boundary: EVERY routing failure — a QueueFull divergence, a malformed frame — must surface to the blocked receivers as a plane death)
        except Exception as e:
            self.fail(e)
