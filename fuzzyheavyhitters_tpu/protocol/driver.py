"""In-process protocol driver: a leader and two colocated server states.

The correctness backbone of the framework — both servers' state machines run
in one process (the integration-test shape the reference intended with its
commented-out ``collect_test.rs``, SURVEY.md §4), with the trusted-exchange
data plane: the per-(node,client) packed share bits are compared directly
instead of passing through the GC+OT 2PC (functionally identical counts —
exactly what the leader reconstructs anyway via ``keep_values``,
ref: collect.rs:945-964 — with semi-honest security dropped).  The secure
data plane drops in behind the same ``counts_by_pattern`` seam.

Level-loop semantics mirror the reference leader (ref: leader.rs:185-297):

- threshold = ``max(1, threshold · nreqs)`` per level (leader.rs:193-194);
- ``data_len - 1`` inner levels then one last level (leader.rs:417-438);
- prune keeps only above-threshold children (leader.rs:229-234);
- paths decode MSB-first per dim; heavy hitters are the surviving leaves.
"""

from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass, field

import jax
import numpy as np
from jax import lax

from .. import obs as obsmod
from ..obs import metrics as obsmetrics
from ..ops.ibdcf import EvalState, IbDcfKeyBatch
from . import collect


def cw_window(keys: IbDcfKeyBatch, lo: int, hi: int):
    """Host-side correction-word WINDOW [lo, hi) -> device upload,
    LEVEL-MAJOR (``[W, N, d, 2, words]``).

    For the STREAMING crawl mode: ``keys`` leaves are host numpy arrays
    (the full ``cw_seed [N, d, 2, L, 4]`` never touches the device); the
    crawl uploads ~20 B per (client, dim, side, level) in windows of
    ``Leader.stream_window`` levels and slices each level ON DEVICE
    (:func:`cw_at`).  Windowing matters: eight big host->device
    transfers beat 512 small ones.  The
    level-major transpose happens on the HOST so the per-level device
    slice is one contiguous 13 MB view — slicing the natural
    ``[..., W, words]`` layout instead was a strided gather over the
    whole window and cost ~2 s/level on chip."""
    def take(a):
        # fhh-lint: disable=host-sync-in-hot-loop (keys are host-resident
        # by design in streaming mode; this IS the windowed upload)
        win = np.asarray(a)[..., lo:hi, :]
        return jax.device_put(np.ascontiguousarray(np.moveaxis(win, -2, 0)))
    return take(keys.cw_seed), take(keys.cw_bits), take(keys.cw_y_bits)


@jax.jit
def _cw_at(window, i):
    return tuple(
        lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False) for a in window
    )


def cw_at(window, idx: int):
    """One level's cw triple out of a level-major device window (one
    contiguous device slice — no host transfer)."""
    return _cw_at(window, np.int32(idx))


def slim_root_batch(keys: IbDcfKeyBatch) -> IbDcfKeyBatch:
    """Root-only key batch for ``tree_init`` in streaming mode: real
    root seeds + key_idx, zero-length correction-word axes (eval_init
    touches only the roots; uploading the full cw tensors is exactly what
    streaming exists to avoid)."""
    root = np.asarray(keys.root_seed)
    batch = root.shape[:-1]
    return IbDcfKeyBatch(
        key_idx=np.asarray(keys.key_idx),
        root_seed=root,
        cw_seed=np.zeros(batch + (0, 4), np.uint32),
        cw_bits=np.zeros(batch + (0, 2), bool),
        cw_y_bits=np.zeros(batch + (0, 2), bool),
    )


@dataclass
class ServerState:
    """One collector server's state (ref: server.rs:44-52 wraps the same)."""

    keys: IbDcfKeyBatch  # [N, d, 2]
    alive_keys: np.ndarray  # bool[N] liveness flags (ref: collect.rs:32)
    frontier: collect.Frontier | None = None
    children: object | None = None  # expand-time child-state cache


@dataclass
class CrawlResult:
    paths: np.ndarray  # bool[H, d, L] per-dim MSB-first paths
    counts: np.ndarray  # uint32[H]

    def decode_ints(self) -> np.ndarray:
        """paths -> int[H, d] leaf values (MSB-first per dim).

        Domains of 63+ bits (the COVID f64-bit encoding is 64) overflow
        an int64 weight vector, so wide paths decode through Python ints
        (object dtype) — this is leader-side decoration, not a hot path."""
        L = self.paths.shape[-1]
        if L < 63:
            weights = 1 << np.arange(L - 1, -1, -1)
            return (self.paths.astype(np.int64) * weights).sum(-1)
        vals = np.zeros(self.paths.shape[:-1], dtype=object)
        for i in range(L):
            vals = (vals << 1) | self.paths[..., i].astype(object)
        return vals


@dataclass
class Leader:
    """Drives two ServerStates level by level (ref: leader.rs:185-297)."""

    server0: ServerState
    server1: ServerState
    n_dims: int
    data_len: int
    f_max: int = 256
    min_bucket: int = 1  # pin >1 only on compile-bound test hosts
    # STREAMING mode: keys stay in host RAM; each level uploads only its
    # cw slice (double-buffered) and the crawl re-expands survivors
    # instead of caching children — the regime for key batches / wide
    # frontiers that exceed one chip's HBM (data_len=512 at >200k
    # clients with both servers colocated).
    stream: bool = False
    # streaming-advance transient bound: parent slots expanded per chunk
    # (None = whole bucket at once; set on HBM-bound runs, see
    # collect.advance_from_cw)
    stream_chunk: int | None = None
    # cw upload window in levels (see cw_window); the next window is
    # prefetched at the current window's entry so the transfer rides
    # behind ~stream_window levels of compute
    stream_window: int = 64
    # radix-2^k level fusion (Config.crawl_radix_bits): bits crawled per
    # round; each run_level call covers bit levels [level, level+r) with
    # r = min(radix, data_len - level).  Pruning is on the depth-(base+r)
    # counts, bit-identical to r sequential levels (monotone counts make
    # the intermediate prunes subsumed — collect.py radix section).
    # Streaming mode pins radix=1 (advance_from_cw re-expands one bit).
    radix: int = 1
    # leader-side bookkeeping
    paths: np.ndarray = field(default=None)  # bool[F, d, level]
    n_nodes: int = 0
    # telemetry: per-level phase timers + survivor gauges + checkpoint
    # events; the heartbeat thread names the level a wedged crawl died in
    obs: obsmetrics.Registry = None

    def __post_init__(self):
        if self.obs is None:
            self.obs = obsmetrics.Registry("driver")
        collect.check_radix(self.n_dims, self.radix)
        if self.stream and self.radix > 1:
            raise ValueError(
                "streaming crawl mode pins crawl_radix_bits=1 "
                "(advance_from_cw re-expands one bit per level)"
            )

    def tree_init(self):
        for s in (self.server0, self.server1):
            keys = slim_root_batch(s.keys) if self.stream else s.keys
            s.frontier = collect.tree_init(keys, self.min_bucket)
            s.children = None
        self.paths = np.zeros((1, self.n_dims, 0), bool)
        self.n_nodes = 1
        self._win = {}  # which -> (lo, window triple)
        self._win_next = {}  # (which, lo) -> prefetched window triple

    def _take_cw(self, which: int, level: int):
        W = self.stream_window
        lo = (level // W) * W
        ent = self._win.get(which)
        if ent is None or ent[0] != lo:
            tri = self._win_next.pop((which, lo), None)
            if tri is None:
                keys = (self.server0, self.server1)[which].keys
                tri = cw_window(keys, lo, min(lo + W, self.data_len))
            self._win[which] = ent = (lo, tri)
            # start the NEXT window's upload now — it arrives behind ~W
            # levels of compute
            nlo = lo + W
            if nlo < self.data_len and (which, nlo) not in self._win_next:
                keys = (self.server0, self.server1)[which].keys
                self._win_next[(which, nlo)] = cw_window(
                    keys, nlo, min(nlo + W, self.data_len)
                )
        return cw_at(ent[1], level - ent[0])

    def run_level(self, level: int, nreqs: int, threshold: float) -> int:
        """One crawl->threshold->prune round; returns surviving node count.

        Trusted-exchange mode: counts are exact (the reconstruction
        ``v0 - v1`` of ref collect.rs:945-964, computed directly).

        ``level`` is the BASE bit level of the round; with ``radix`` > 1
        the round fuses bit levels [level, level + r) for
        r = min(radix, data_len - level) — one expand, one count, one
        prune over the 2^(r·d) fused children.
        """
        d = self.n_dims
        r = min(self.radix, self.data_len - level)
        masks = collect.pattern_masks_radix(d, r)
        with self.obs.span("level", level=level):
            with self.obs.span("fss", level=level):
                if self.stream:
                    cw0 = self._take_cw(0, level)
                    cw1 = self._take_cw(1, level)
                    p0, _ = collect.expand_share_bits_from_cw(
                        cw0, self.server0.frontier, want_children=False
                    )
                    p1, _ = collect.expand_share_bits_from_cw(
                        cw1, self.server1.frontier, want_children=False
                    )
                else:
                    p0, ch0 = collect.expand_share_bits_radix(
                        self.server0.keys, self.server0.frontier, level, r
                    )
                    p1, ch1 = collect.expand_share_bits_radix(
                        self.server1.keys, self.server1.frontier, level, r
                    )
                    self.server0.children, self.server1.children = ch0, ch1
            with self.obs.span("field", level=level):
                counts = collect.counts_by_pattern(
                    p0,
                    p1,
                    masks,
                    self.server0.alive_keys,  # host bool[N] as-is
                    self.server0.frontier.alive,
                )
                self.obs.count("device_fetches")
                # the ONE deliberate per-level readback: the threshold
                # decision and prune bookkeeping are leader/host logic
                # fhh-lint: disable=host-sync-in-hot-loop (counted above)
                counts = np.asarray(counts)  # [F, 2^d]

                thresh = max(1, int(threshold * nreqs))  # ref: leader.rs:193-194
                # walk fused children in the k=1 visit order (earlier
                # steps most significant) so survivor order — and the
                # f_max truncation set — is bit-identical to r sequential
                # levels (collect.radix_pattern_order; identity at r=1)
                order = collect.radix_pattern_order(d, r)
                keep = counts[:, order] >= thresh  # [F, 2^(r·d)]
                keep[self.n_nodes :, :] = False
                parent, rank, n_alive = collect.compact_survivors(
                    keep, self.f_max, self.min_bucket
                )
                pattern = order[rank]
                pat_bits = collect.pattern_to_bits_radix(pattern, d, r)

            with self.obs.span("advance", level=level):
                if self.stream:
                    del p0, p1  # frontier buffers are donated by advance_from_cw
                    if level < self.data_len - 1 and n_alive:
                        f0, f1 = self.server0.frontier, self.server1.frontier
                        self.server0.frontier = None  # drop refs before donation
                        self.server1.frontier = None
                        self.server0.frontier = collect.advance_from_cw(
                            cw0, f0, parent, pat_bits[:, 0, :], n_alive,
                            self.stream_chunk
                        )
                        # free server 0's old frontier BEFORE server 1 advances:
                        # keeping both olds + both news alive is what overflows
                        # HBM at wide-frontier levels (four full frontiers)
                        del f0
                        self.server1.frontier = collect.advance_from_cw(
                            cw1, f1, parent, pat_bits[:, 0, :], n_alive,
                            self.stream_chunk
                        )
                        del f1
                else:
                    for s in (self.server0, self.server1):
                        s.frontier = collect.advance_from_children_radix(
                            s.children, parent, pat_bits, n_alive, r
                        )
                        s.children = None

            # leader-side path bookkeeping (step t's bit for dim j =
            # (pattern >> (t·d + j)) & 1 — the fused path appends r bits
            # per dim, step-major)
            new_paths = np.zeros((n_alive, d, self.paths.shape[-1] + r), bool)
            for i in range(n_alive):
                new_paths[i, :, : -r] = self.paths[parent[i]]
                for t in range(r):
                    new_paths[i, :, -r + t] = pat_bits[i, t]
            self.paths = new_paths
            self.n_nodes = n_alive
            self.obs.gauge("survivors", n_alive, level=level)
            self._last_counts = counts[parent[:n_alive], pattern[:n_alive]]
        return n_alive

    def run(
        self,
        nreqs: int,
        threshold: float,
        checkpoint_path: str | None = None,
        checkpoint_every: int = 64,
        resume: bool = False,
    ) -> CrawlResult:
        """Full crawl: init + data_len levels + final reconstruction
        (ref: leader.rs:417-438 then final_shares at :282-297).

        ``checkpoint_path`` + ``checkpoint_every`` persist the crawl state
        every N completed levels (see :meth:`checkpoint`); ``resume=True``
        restores from that file (if present) and continues from the next
        level instead of starting over — a 512-level flagship crawl is
        minutes of wall-clock, and the reference offers nothing but a full
        restart on interruption (its only recovery verb is ``reset``,
        server.rs:64-69).  Keys are NOT in the checkpoint (they are the
        bulk of the bytes and the caller already holds them); construct
        the Leader with the same key batches before resuming.  A completed
        crawl REMOVES its checkpoint file, so the natural crash-safe
        invocation (always pass the same path with ``resume=True``) starts
        the next crawl fresh instead of silently resuming a finished one."""
        if (resume and checkpoint_path is not None
                and os.path.exists(checkpoint_path)):
            start = self.restore(checkpoint_path, nreqs, threshold)
        else:
            start = 0
            self.tree_init()

        def done(result):
            if checkpoint_path is not None and os.path.exists(checkpoint_path):
                os.remove(checkpoint_path)
            return result

        # cadence clamped so SHORT crawls still checkpoint mid-crawl: with
        # the raw default (64) a data_len <= 64 run would only ever hit
        # the final level — which the guard below rightly skips (a
        # finished crawl has nothing to resume) — and silently write
        # nothing at all
        every = min(checkpoint_every, max(1, self.data_len // 2))
        for level in range(start, self.data_len, self.radix):
            r = min(self.radix, self.data_len - level)
            n = self.run_level(level, nreqs, threshold)
            if n == 0:
                return done(CrawlResult(
                    paths=np.zeros((0, self.n_dims, level + r), bool),
                    counts=np.zeros(0, np.uint32),
                ))
            if (
                checkpoint_path is not None
                and level + r < self.data_len
                and (level + r) % every == 0
            ):
                self.checkpoint(checkpoint_path, level, nreqs, threshold)
        return done(CrawlResult(paths=self.paths, counts=self._last_counts))

    # -- checkpoint / resume -------------------------------------------------

    def _key_fingerprint(self) -> np.ndarray:
        """SHA-256 over both servers' key identities: key_idx + root seeds
        PLUS an every-client checksum of the correction-word planes across
        ALL levels.  Root seeds alone are not an identity — two keygen runs
        sharing an RNG seed but differing in ball radius (or any other
        keygen parameter) produce identical roots with different
        correction words — and the level axis must be complete: the
        radius perturbs the LOW bits of the interval endpoints, so the
        first differing cw sits at the deepest levels, not level 0
        (measured: ball 1 vs 2 at L=5 diverges only from level 3 down).
        The client axis must be complete too — ANY client sample (prefix
        or spread) admits two batches that diverge only at unsampled
        clients — so each cw plane is collapsed by a position-weighted
        mod-2^32 checksum over the client axis BEFORE the fetch: every
        client contributes (odd weights are invertible mod 2^32, so a
        change in any single client's plane always moves the sum), while
        the device->host transfer stays the reduced plane (~16 KB at
        L=512 vs ~2 MB per-client).  Cached:
        keys are immutable for the crawl's lifetime."""
        fp = getattr(self, "_key_fp", None)
        if fp is None:
            import jax.numpy as jnp

            # Phase 1 — compute every piece WITHOUT fetching: per server,
            # the identity arrays (key_idx, root_seed) plus one reduced
            # plane per cw tensor.  All-level cw planes: seeds
            # [N, d, 2, L, 4] plus the t/y bit planes [N, d, 2, L, 2] (a
            # divergence at any level lands in at least one); reduce with
            # the array's own backend — streaming mode holds host keys,
            # uploading them just to reduce would defeat the point — and
            # in client CHUNKS: at the flagship 196k x L=512 shape a
            # full-batch weighted product would transiently double the
            # ~3 GB plane in host RAM (or HBM, which the crawl already
            # runs near the limit of) at checkpoint time.
            fetch: list = []  # device/host arrays, ONE stacked device_get
            layout: list = []  # hash order: ("arr", fetch_i) | ("red", red_i)
            device_reds: list = []  # raveled on-device reductions
            for s in (self.server0, self.server1):
                for ident in (s.keys.key_idx, s.keys.root_seed):
                    layout.append(("arr", len(fetch)))
                    fetch.append(ident)
                n = s.keys.key_idx.shape[0]
                for plane in (s.keys.cw_seed, s.keys.cw_bits, s.keys.cw_y_bits):
                    on_device = isinstance(plane, jax.Array)
                    xp = jnp if on_device else np
                    red = None
                    for i in range(0, n, 4096):
                        p = xp.asarray(plane[i : i + 4096], dtype=xp.uint32)
                        w = (
                            xp.arange(i, i + p.shape[0], dtype=xp.uint32) * 2
                            + 1
                        ).reshape((p.shape[0],) + (1,) * (p.ndim - 1))
                        part = (p * w).sum(axis=0, dtype=xp.uint32)
                        red = part if red is None else red + part
                    if on_device:
                        layout.append(("red", len(device_reds)))
                        device_reds.append(red.ravel())
                    else:
                        layout.append(("arr", len(fetch)))
                        fetch.append(red)
            # Phase 2 — ONE stacked transfer (was: one np.asarray per
            # piece, up to 8 device round trips per checkpoint): the six
            # plane reductions concatenate into a single device array and
            # ride one device_get together with the identity arrays
            # (host-resident ones pass through untouched)
            sizes = [r.size for r in device_reds]
            if device_reds:
                fetch.append(jnp.concatenate(device_reds))
            host = jax.device_get(fetch)
            offsets = np.cumsum([0] + sizes)
            red_cat = host[-1] if device_reds else None
            h = hashlib.sha256()
            for kind, idx in layout:
                arr = (
                    host[idx]
                    if kind == "arr"
                    else red_cat[offsets[idx] : offsets[idx + 1]]
                )
                h.update(np.ascontiguousarray(arr))
            fp = self._key_fp = np.frombuffer(h.digest(), np.uint8)
        return fp

    def checkpoint(
        self, path: str, level: int,
        nreqs: int | None = None, threshold: float | None = None,
    ) -> None:
        """Persist the crawl state AFTER ``level`` completed: both servers'
        frontier states + liveness flags, the leader's path bookkeeping,
        the state LAYOUT (the planar Pallas engine and the interleaved
        XLA engine shape the frontier differently — collect.Frontier; a
        restore under the other engine converts), a key fingerprint, and —
        when called from :meth:`run` — the crawl parameters, so a resume
        under different keys/nreqs/threshold refuses instead of mixing
        pruning regimes.  Written atomically (tmp + rename) so an
        interruption mid-write never corrupts the previous checkpoint."""
        planar = collect._expand_engine()
        blob = {
            "level": np.int64(level),
            "radix": np.int64(self.radix),
            "planar": np.bool_(planar),
            "paths": self.paths,
            "n_nodes": np.int64(self.n_nodes),
            "last_counts": np.asarray(self._last_counts),
            "meta": np.array(
                [self.n_dims, self.data_len, self.f_max, self.min_bucket],
                np.int64,
            ),
            "key_fp": self._key_fingerprint(),
        }
        if nreqs is not None and threshold is not None:
            blob["params"] = np.array([float(nreqs), float(threshold)])
        for i, s in enumerate((self.server0, self.server1)):
            st = s.frontier.states
            blob[f"s{i}_seed"] = st.seed
            blob[f"s{i}_bit"] = st.bit
            blob[f"s{i}_y_bit"] = st.y_bit
            blob[f"s{i}_alive"] = s.frontier.alive
            blob[f"s{i}_alive_keys"] = s.alive_keys
        # ONE stacked fetch for both servers' state planes (was: one
        # np.asarray per plane, 10 device round trips per checkpoint);
        # host-resident entries pass through device_get untouched
        blob = jax.device_get(blob)
        tmp = f"{path}.tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **blob)
        os.replace(tmp, path)
        self.obs.count("checkpoint_writes", level=level)
        obsmod.emit("checkpoint.write", path=path, level=level)

    def restore(
        self, path: str,
        nreqs: int | None = None, threshold: float | None = None,
    ) -> int:
        """Load a :meth:`checkpoint` and return the NEXT level to run.
        Refuses a checkpoint whose shape, key fingerprint, or (when both
        sides recorded them) crawl parameters differ from this Leader's —
        every mismatch would otherwise produce silently wrong hitters."""
        # materialize inside the context manager: NpzFile holds the file
        # descriptor open until closed, and run() later os.remove()s this
        # same path — a leaked handle pins the deleted file's blocks (and
        # on some filesystems fails the remove outright)
        with np.load(path) as npz:
            z = {k: npz[k] for k in npz.files}
        meta = z["meta"]
        want = [self.n_dims, self.data_len, self.f_max, self.min_bucket]
        if list(meta) != want:
            raise ValueError(
                f"checkpoint shape {list(meta)} != leader shape {want}"
            )
        # validate-before-mutate: a blob written under a different crawl
        # radix carries a frontier at a depth this leader's fused level
        # grid never visits — refuse with live state untouched (blobs
        # predating the radix stamp are radix-1 crawls)
        saved_radix = int(z["radix"]) if "radix" in z else 1
        if saved_radix != self.radix:
            raise ValueError(
                f"checkpoint crawl radix {saved_radix} != leader "
                f"crawl_radix_bits {self.radix}"
            )
        if "key_fp" not in z:
            raise ValueError(
                "checkpoint predates the key-fingerprint format — "
                "re-run the crawl from the start"
            )
        if not np.array_equal(z["key_fp"], self._key_fingerprint()):
            raise ValueError(
                "checkpoint was written under different key batches"
            )
        if "params" in z and nreqs is not None and threshold is not None:
            saved = z["params"]
            if saved[0] != float(nreqs) or saved[1] != float(threshold):
                raise ValueError(
                    f"checkpoint crawl params (nreqs, threshold) = "
                    f"({saved[0]:g}, {saved[1]:g}) != ({nreqs}, {threshold})"
                )
        saved_planar = bool(z["planar"])
        planar = collect._expand_engine()
        for i, s in enumerate((self.server0, self.server1)):
            states = EvalState(
                seed=jax.device_put(z[f"s{i}_seed"]),
                bit=jax.device_put(z[f"s{i}_bit"]),
                y_bit=jax.device_put(z[f"s{i}_y_bit"]),
            )
            if saved_planar != planar:
                states = _convert_layout(states, saved_planar)
            s.frontier = collect.Frontier(
                states=states, alive=jax.device_put(z[f"s{i}_alive"])
            )
            s.children = None
            s.alive_keys = z[f"s{i}_alive_keys"]
        self.paths = z["paths"]
        self.n_nodes = int(z["n_nodes"])
        self._last_counts = z["last_counts"]
        self._win = {}
        self._win_next = {}
        self.obs.count("checkpoint_restores", level=int(z["level"]))
        obsmod.emit("checkpoint.restore", path=path, level=int(z["level"]))
        lvl = int(z["level"])  # base bit level of the last completed round
        return lvl + min(self.radix, self.data_len - lvl)


def _convert_layout(states, from_planar: bool):
    """Frontier EvalState layout conversion for cross-engine checkpoint
    restore — delegates to :func:`collect.to_interleaved` /
    :func:`collect.to_planar`, the one source of truth for the engine-edge
    transposes.  Converting there and back is the identity."""
    return (
        collect.to_interleaved(states) if from_planar
        else collect.to_planar(states)
    )


def make_servers(
    keys0: IbDcfKeyBatch, keys1: IbDcfKeyBatch
) -> tuple[ServerState, ServerState]:
    n = keys0.cw_seed.shape[0]
    alive = np.ones(n, bool)
    return ServerState(keys0, alive.copy()), ServerState(keys1, alive.copy())
