"""RPC leader: drives two collector servers over the control plane.

The process-level twin of protocol/driver.Leader, speaking the 8-verb RPC
(ref: src/bin/leader.rs:185-297): batched key upload, level loop with
``tree_crawl`` → reconstruct ``v0 - v1`` → threshold → fused prune/advance,
the F255 last level, and final heavy-hitter reconstruction with the same
``max(1, threshold·nreqs)`` floor (leader.rs:193-194, 245-246).
"""

from __future__ import annotations

import asyncio
import collections as _collections
import contextlib
import time

import jax
import numpy as np

from .. import obs as obsmod
from ..obs import metrics as obsmetrics
from ..obs import trace as obstrace
from ..ops.fields import F255, FE62
from ..ops.ibdcf import IbDcfKeyBatch
from ..resilience import policy as respolicy
from ..utils import guards
from ..utils.config import Config
from . import collect
from .driver import CrawlResult
from .rpc import CollectorClient, ServerRestartedError


def _key_chunk(keys: IbDcfKeyBatch, sl: slice):
    return tuple(np.asarray(leaf)[sl] for leaf in keys)


class RpcLeader:
    def __init__(
        self,
        cfg: Config,
        client0: CollectorClient,
        client1: CollectorClient,
        min_bucket: int = 1,
    ):
        self.cfg = cfg
        self.c0, self.c1 = client0, client1
        self.min_bucket = min_bucket  # pin >1 only on compile-bound hosts
        self.paths: np.ndarray | None = None
        self.n_nodes = 0
        self.has_sketch = False
        self._f_bucket = min_bucket  # current frontier bucket (shard plan)
        self._boot_ids: dict = {}  # last known server boot ids
        self._mesh_faults: dict = {}  # last seen mesh.faults counts
        # leader-side telemetry: level spans (the heartbeat names the
        # level a wedged crawl died in) + survivor gauges.  A leader
        # driving a non-default collection names it in the registry so
        # the heartbeat/report show the (session, phase) pair.
        self.collection = getattr(client0, "collection", None) or "default"
        self.obs = obsmetrics.Registry(
            "leader" if self.collection == "default"
            else f"leader:{self.collection}"
        )
        # the clients predate this registry (connect() runs first); rebind
        # their control-plane byte accounting so control_bytes_* land on
        # the leader's registry, not the process default
        client0.obs = client1.obs = self.obs

    @staticmethod
    async def _all(*coros):
        """Gather with cancel-on-first-failure.  Plain ``asyncio.gather``
        leaves the other awaitables RUNNING when one raises — for this
        client that means an orphaned call still replaying its verb while
        the supervisor rolls both servers back, and a replayed
        never-executed ``tree_prune``/``add_keys`` landing AFTER a
        ``tree_restore``/``reset`` would corrupt the restored state.
        Cancelling the siblings kills their replay loops; anything that
        already executed server-side is answered from the dedup cache."""
        tasks = [asyncio.ensure_future(c) for c in coros]
        # fhh-lint: disable=unbounded-await (every child is a client call
        # bounded by its own per-verb wall-clock budget; a second timeout
        # here would race the real one)
        done, pending = await asyncio.wait(
            tasks, return_when=asyncio.FIRST_EXCEPTION
        )
        # retrieve EVERY done task's exception (not just the first): an
        # unretrieved sibling failure would be dumped on the event loop
        # as "Task exception was never retrieved" noise at GC time
        errs = [(t, t.exception()) for t in done]
        failed = next((t for t, e in errs if e is not None), None)
        if failed is not None:
            for t in pending:
                t.cancel()
            for t in pending:
                try:
                    await t
                except (asyncio.CancelledError, Exception):  # fhh-lint: disable=broad-except (cancellation sweep: sibling errors are subsumed by the first failure, re-raised below)
                    pass
            raise failed.exception()
        return [t.result() for t in tasks]

    async def _both(self, verb: str, req=None):
        return await self._all(self.c0.call(verb, req), self.c1.call(verb, req))

    async def upload_keys(
        self,
        keys0: IbDcfKeyBatch,
        keys1: IbDcfKeyBatch,
        sketch0=None,
        sketch1=None,
        which: int | None = None,
    ):
        """Batched async key upload with a ROLLING in-flight window (ref:
        leader.rs:340-364: 1000 addkey batches in flight, refilled as each
        completes — not drained in bursts: a stop-and-wait gather leaves
        the pipe empty while the slowest request of each burst finishes).
        Optional sketch key batches ride in the same requests
        (malicious-secure mode).  ``which`` (0 or 1) uploads to ONE
        server only — the recovery path re-seeding a restarted server."""
        n = np.asarray(keys0.cw_seed).shape[0]
        bs = max(1, self.cfg.addkey_batch_size)
        if which is None:
            self.has_sketch = sketch0 is not None

        def sk_chunk(sk, sl):
            if sk is None:
                return None
            return [np.asarray(x)[sl] for x in jax.tree.leaves(sk)]

        window = 256
        sem = asyncio.Semaphore(window)

        async def send_one(client, keys, sketch, sl):
            # the request dict is built INSIDE the window so at most
            # ``window`` chunks are materialized/pickled at a time
            async with sem:
                await client.call(
                    "add_keys",
                    {"keys": _key_chunk(keys, sl), "sketch": sk_chunk(sketch, sl),
                     # where the batch goes: the server writes it to rows
                     # [lo, lo + B) of n on its chip as it arrives
                     "n": n, "lo": sl.start},
                )

        with self.obs.span("upload_keys"):
            tasks = []
            for lo in range(0, n, bs):
                sl = slice(lo, min(lo + bs, n))
                if which in (None, 0):
                    tasks.append(send_one(self.c0, keys0, sketch0, sl))
                if which in (None, 1):
                    tasks.append(send_one(self.c1, keys1, sketch1, sl))
            # cancel-on-first-failure: an orphaned add_keys replay landing
            # after a recovery reset would append a duplicate key chunk
            await self._all(*tasks)
        self.obs.count("keys_uploaded", n)

    async def warmup(self, f_buckets=None) -> dict:
        """Ask both servers to pre-compile the per-``f_bucket`` crawl
        programs (rpc.CollectorServer.warmup) so bucket recompiles land
        BEFORE measured crawl time — with the persistent compile cache
        on (utils/compile_cache.py) the compiles also persist across
        processes.  Default bucket plan:
        powers of two from ``min_bucket`` up to ``cfg.f_max`` (the exact
        ladder ``collect.bucket_for`` walks as the frontier grows).
        Call after ``upload_keys`` (the servers need the key shapes);
        any time before or during the crawl is safe — warmup touches no
        protocol state."""
        if f_buckets is None:
            f_buckets, b = [], max(1, self.min_bucket)
            while b <= self.cfg.f_max:
                f_buckets.append(b)
                b *= 2
            if f_buckets and f_buckets[-1] != self.cfg.f_max:
                # non-power-of-two f_max: bucket_for caps at f_max itself,
                # so the largest (most expensive) shape is f_max, not the
                # last doubled rung — warm it too
                f_buckets.append(self.cfg.f_max)
        with self.obs.span("warmup"):
            r0, r1 = await self._both(
                "warmup",
                {
                    "f_buckets": [int(b) for b in f_buckets],
                    # warm THIS leader's path + span plan, which may
                    # override the servers' own config (bench legs)
                    "ot_path": self.cfg.ot_path,
                    "secure_spans": bool(
                        self.cfg.secure_exchange
                        and not self.cfg.secure_whole_level
                        and self.cfg.crawl_shard_nodes
                    ),
                    # the shard layout this leader BELIEVES the servers
                    # run (multi-chip client sharding): the servers warm
                    # their own live layout regardless, but a skew is
                    # warned about at warmup time instead of surfacing
                    # as fresh compiles on the measured clock
                    "data_shards": int(self.cfg.server_data_devices),
                },
            )
        return {"f_buckets": list(f_buckets), "s0": r0, "s1": r1}

    async def _crawl_level(self, level: int, last: bool):
        """This level's crawl verbs, sharded when ``cfg.crawl_shard_nodes``
        says so: one verb per deterministic node span
        (``collect.shard_spans``) — the data plane is positional, so both
        servers must work the same span at the same time.  With
        ``cfg.crawl_pipeline_depth`` > 1 up to that many span verbs ride
        in flight at once with in-order reassembly (the servers serialize
        execution on their verb lock in frame-arrival order, so the
        positional data plane stays matched while span k+1's device
        expand overlaps span k's GC/OT network phase); any in-flight
        transient fault quiesces the pipeline and falls back to the
        sequential per-span retry below, so PR 4's recovery and ratchet
        semantics are untouched.  Sequentially each span runs under its
        own retry (:meth:`_shard_call`).  A mid-level fault costs the
        lost span(s), not the level."""
        verb = "tree_crawl_last" if last else "tree_crawl"
        # alternate the garbling server per FUSED level (the reference's
        # gc_sender flip, leader.rs:204-210) to split garbling cost; under
        # radix-2^k fusion the bases are 0, k, 2k, … so the flip counts
        # round trips (level // k), not bit-levels — level % 2 would pin
        # one garbler forever at even k.  The equality-test path rides the
        # verb too, so both servers follow THIS leader's config even when
        # it differs from their own (the bench's GC-reference leg depends
        # on that)
        rdx = max(1, int(self.cfg.crawl_radix_bits))
        req = {"level": level, "garbler": (level // rdx) % 2,
               "ot_path": self.cfg.ot_path}
        spans = collect.shard_spans(self._f_bucket, self.cfg.crawl_shard_nodes)
        if self.cfg.secure_exchange and self.cfg.secure_whole_level:
            # whole-level secure batching: one verb a level, no node
            # spans cut here.  The servers cut the level themselves,
            # by ROWS of its test batch into chunks of whole planar
            # blocks inside this one verb (rpc ``_ev_chunks`` /
            # ``_gb_chunks``), which costs no verb round trip, expand
            # dispatch or reassembly a span.  What this comment said
            # until PR 31 — "pipelining those chunks loses more to
            # fragmented kernels than the overlap wins" — was read off
            # CPU runs of host-sized node spans; what the chip said of
            # chunked kernels is in PERF.md section 6 (PR 31).  A
            # mid-level fault then re-runs the level, not a span
            # (cfg.secure_whole_level=False restores span granularity).
            return await self._both(verb, req)
        if len(spans) == 1:
            return await self._both(verb, req)
        depth = max(1, int(getattr(self.cfg, "crawl_pipeline_depth", 1)))
        rerun = False
        if depth > 1 and len(spans) > 1:
            try:
                return await self._crawl_level_pipelined(
                    verb, req, spans, min(depth, len(spans)), level
                )
            except respolicy.TRANSIENT_ERRORS as err:
                if isinstance(err, ServerRestartedError):
                    raise  # lost state: the supervisor owns full recovery
                await self._quiesce_after_pipeline_fault(level, err)
                rerun = True
        parts0, parts1 = [], []
        for span in spans:
            s0, s1 = await self._shard_call(verb, dict(req, shard=list(span)))
            if rerun:  # fallback re-execution after a pipeline fault
                self.obs.count("shards_rerun", level=level)
            # fhh-lint: disable=host-sync-in-hot-loop (wire responses:
            # already host numpy off the control socket, no device sync)
            parts0.append(np.asarray(s0))
            # fhh-lint: disable=host-sync-in-hot-loop (wire response)
            parts1.append(np.asarray(s1))
        return np.concatenate(parts0, axis=0), np.concatenate(parts1, axis=0)

    async def _crawl_level_pipelined(
        self, verb: str, req: dict, spans: list, depth: int, level: int
    ):
        """Bounded-depth software pipeline over the level's spans: a
        sliding window of up to ``depth`` span verbs in flight, refilled
        as the OLDEST completes (in-order reassembly — the concatenated
        result is positional).  The per-span verbs hit both servers in
        frame order, so server-side execution order matches the
        sequential path exactly; only the leader-side await structure
        changes, which is what lets span k+1's expand (dispatched by the
        server on frame arrival, rpc.py ``_pre_expand``) run while span
        k's exchange is on the wire.  Telemetry: ``pipeline_depth`` /
        ``pipeline_overlap`` (sum of span busy-seconds beyond the level's
        wall-clock) / ``pipeline_stalls`` (head-of-line waits while a
        LATER span had already finished) per level."""

        def launch(span):
            async def one():
                t0 = time.monotonic()
                r = await self._all(
                    self.c0.call(verb, dict(req, shard=list(span))),
                    self.c1.call(verb, dict(req, shard=list(span))),
                )
                return r, time.monotonic() - t0

            return asyncio.ensure_future(one())

        t_level = time.monotonic()
        it = iter(spans)
        # fhh-lint: disable=unbounded-queue (bounded by construction: at most `depth` span tasks live at once — the refill below only appends after the head pops)
        window: _collections.deque = _collections.deque()
        for _ in range(depth):
            span = next(it, None)
            if span is not None:
                window.append(launch(span))
        parts0, parts1 = [], []
        busy = 0.0
        stalls = 0
        try:
            while window:
                head = window.popleft()
                if not head.done() and any(t.done() for t in window):
                    stalls += 1
                # fhh-lint: disable=unbounded-await (each span call is
                # bounded by its own per-verb wall-clock budget)
                (s0, s1), dt = await head
                busy += dt
                # fhh-lint: disable=host-sync-in-hot-loop (wire response)
                parts0.append(np.asarray(s0))
                # fhh-lint: disable=host-sync-in-hot-loop (wire response)
                parts1.append(np.asarray(s1))
                nxt = next(it, None)
                if nxt is not None:
                    window.append(launch(nxt))
        except BaseException:
            # quiesce step 1: no new spans, cancel the in-flight window
            # (their replay loops die; whatever already executed
            # server-side drains under the server's verb lock)
            for t in window:
                t.cancel()
            for t in window:
                with contextlib.suppress(Exception, asyncio.CancelledError):
                    await t
            raise
        wall = time.monotonic() - t_level
        self.obs.gauge("pipeline_depth", depth, level=level)
        self.obs.timer_add(
            "pipeline_overlap", max(0.0, busy - wall), level=level
        )
        if stalls:
            self.obs.count("pipeline_stalls", stalls, level=level)
        return np.concatenate(parts0, axis=0), np.concatenate(parts1, axis=0)

    async def _quiesce_after_pipeline_fault(self, level: int, err) -> None:
        """Quiesce step 2, after the in-flight window is cancelled: break
        any data-plane exchange wedged by the fault (a span that reached
        only ONE server leaves its peer blocked in a ``_swap`` recv
        holding the verb lock — ``plane_break`` closes the peer transport
        from BOTH ends without taking that lock, so the wedged verbs fail
        loudly and release), then re-key the plane through the normal
        locked ``plane_reset`` and verify neither server restarted.  The
        caller then re-runs the whole level sequentially; the servers'
        per-span caches overwrite, so the re-run is bit-identical."""
        self.obs.count("pipeline_faults", level=level)
        obsmod.emit(
            "pipeline.quiesce",
            severity="warn",
            level=level,
            error=f"{type(err).__name__}: {err}",
        )
        await self._all(
            self.c0.call("plane_break"), self.c1.call("plane_break")
        )
        if await self._reprobe_and_reset():
            raise err  # restarted server: full recovery owns it

    async def _shard_call(self, verb: str, req: dict):
        """One shard's verbs on both servers, retried under the shared
        policy: a transient mid-level fault re-keys the data plane
        (``plane_reset`` — a half-executed secure shard leaves the two
        servers' OT streams desynchronized, so the plane must re-handshake
        before the span re-runs) and re-issues JUST this span.  A changed
        boot id escalates unhandled — lost state is the supervisor's
        problem, not a shard retry's."""
        pol = respolicy.SHARD_POLICY
        attempt = 0
        while True:
            try:
                return await self._all(
                    self.c0.call(verb, req), self.c1.call(verb, req)
                )
            except respolicy.TRANSIENT_ERRORS as err:
                attempt += 1
                if isinstance(err, ServerRestartedError) or attempt >= pol.attempts:
                    raise
                if await self._reprobe_and_reset():
                    raise  # restarted server: full recovery owns it
                self.obs.count("shards_rerun", level=int(req["level"]))
                obsmod.emit(
                    "resilience.shard_rerun",
                    severity="warn",
                    level=int(req["level"]),
                    span=req.get("shard"),
                    attempt=attempt,
                    error=f"{type(err).__name__}: {err}",
                )
                await asyncio.sleep(pol.delay(attempt - 1))

    async def _run_one_level(self, level: int, nreqs: int, thresh: int):
        """One crawl->reconstruct->threshold->prune round under a level
        span (the heartbeat names this level while it runs).  Under
        radix-2^k fusion (``cfg.crawl_radix_bits``) ``level`` is the BASE
        bit-level of the fused step and the round covers bit-levels
        ``level .. level+r-1`` with ``r = min(k, L - level)`` — one round
        trip per fused level, 2^(d·r) count columns, and ``r`` path bits
        appended per dim.  Returns ``(counts_kept, alive_after_verify)``
        with ``counts_kept`` None when the crawl died out at this level."""
        cfg = self.cfg
        d, L = cfg.n_dims, cfg.data_len
        r = min(max(1, int(cfg.crawl_radix_bits)), L - level)
        last = level + r == L
        alive_after_verify = None
        if self.has_sketch and level != 1:
            # malicious-security gate first, so failing clients'
            # liveness flags flip before this level's counts are
            # taken.  Level 0 runs the FULL depth-1 check (both root
            # children per dim) — the first threshold never sees
            # unverified counts; levels >= 2 verify the
            # frontier-following shares stored by the previous prune.
            # The depth-1 frontier re-verify (level 1) is skipped: its
            # triples were consumed by the level-0 full check (see
            # rpc.sketch_verify / sketch.py scope note).
            a0, _ = await self._both("sketch_verify", {"level": level})
            alive_after_verify = np.asarray(a0)
        s0, s1 = await self._crawl_level(level, last)
        # the leader's own work of a level, outside the servers' verbs:
        # reconstruct, threshold, prune (the verb pair), paths
        with self.obs.span("reconstruct", level=level):
            if last:
                v = np.asarray(F255.sub(s0, s1))  # leader-side reconstruct
                counts = v[..., 0].astype(np.uint32)  # counts < 2^32 by def
                if np.any(v[..., 1:]):  # boundary check: must survive -O
                    raise RuntimeError("non-count residue in F255 share")
            else:
                v = np.asarray(FE62.canon(FE62.sub(s0, s1)))
                if np.any(v > nreqs):  # e.g. a share-sign/role mismatch
                    raise RuntimeError("count reconstruction out of range")
                counts = v.astype(np.uint32)
        with self.obs.span("threshold", level=level):
            # radix_pattern_order permutes the fused (step-major) child
            # columns into the order a k=1 crawl would visit them, so
            # compact_survivors' walk — and therefore any f_max truncation
            # — is bit-identical to the sequential crawl (identity at r=1)
            order = collect.radix_pattern_order(d, r)
            keep = counts[:, order] >= thresh
            keep[self.n_nodes :, :] = False
            parent, rank, n_alive = collect.compact_survivors(
                keep, cfg.f_max, self.min_bucket
            )
            pattern = order[rank]
            pat_bits = collect.pattern_to_bits_radix(pattern, d, r)
        self.obs.gauge("survivors", n_alive, level=level)
        if n_alive == 0:
            return None, alive_after_verify
        self._f_bucket = int(parent.shape[0])  # next level's shard plan
        # r == 1 sends the historical 2-D [F', d] pattern wire (the
        # transcript ratchet absorbs the wire bytes — k=1 must stay
        # digest-identical); r > 1 sends the fused [F', r, d] form
        wire_bits = pat_bits[:, 0, :] if r == 1 else pat_bits
        with self.obs.span("prune", level=level):
            if last:
                await self._both(
                    "tree_prune_last",
                    {
                        "parent_idx": parent,
                        "pattern_bits": wire_bits,
                        "n_alive": n_alive,
                    },
                )
            else:
                await self._both(
                    "tree_prune",
                    {
                        "level": level,
                        "parent_idx": parent,
                        "pattern_bits": wire_bits,
                        "n_alive": n_alive,
                    },
                )
        with self.obs.span("paths", level=level):
            new_paths = np.zeros(
                (n_alive, d, self.paths.shape[-1] + r), bool
            )
            for i in range(n_alive):
                new_paths[i, :, : -r] = self.paths[parent[i]]
                for t in range(r):
                    new_paths[i, :, -r + t] = pat_bits[i, t]
            self.paths = new_paths
        self.n_nodes = n_alive
        return counts[parent[:n_alive], pattern[:n_alive]], alive_after_verify

    async def run(self, nreqs: int) -> CrawlResult:
        # one distributed trace per crawl (obs.trace): every verb this
        # leader issues below carries the trace id, so both servers'
        # spans land in ONE merged timeline.  FHH_PROFILE additionally
        # wraps the crawl in a jax.profiler capture (per-level captures
        # are the servers' FHH_PROFILE_LEVELS hook).
        with obstrace.root("crawl"), obstrace.profile_capture("crawl"):
            return await self._run(nreqs)

    async def _run(self, nreqs: int) -> CrawlResult:
        cfg = self.cfg
        d, L = cfg.n_dims, cfg.data_len
        await self._both("tree_init", {"root_bucket": self.min_bucket})
        self.paths = np.zeros((1, d, 0), bool)
        self.n_nodes = 1
        self._f_bucket = self.min_bucket
        thresh = max(1, int(cfg.threshold * nreqs))
        counts_kept = np.zeros(0, np.uint32)
        alive_before_leaf = None  # liveness after the latest verify
        # radix-2^k fusion: one round trip per FUSED level — bases
        # 0, k, 2k, …, ⌈L/k⌉ rounds total (the tail round covers the
        # remaining L mod k bit-levels when k ∤ L)
        rdx = max(1, int(cfg.crawl_radix_bits))
        for level in range(0, L, rdx):
            r = min(rdx, L - level)
            with self.obs.span("level", level=level) as sp_level:
                counts_kept, alive = await self._run_one_level(
                    level, nreqs, thresh
                )
            # leader-side per-level latency histogram (SLO surface:
            # p50/p95 in the run report's slo section + the bench line)
            self.obs.observe("level_latency", sp_level.seconds)
            # the block-buffered span log (obs/trace.py), a level at most
            # behind the crawl
            obstrace.flush()
            if alive is not None:
                alive_before_leaf = alive
            if counts_kept is None:
                return CrawlResult(
                    paths=np.zeros((0, d, level + r), bool),
                    counts=np.zeros(0, np.uint32),
                )
        if self.has_sketch and L > 1:
            # final F255 leaf-payload check (surviving leaves; counts for
            # this collection are already taken — the verdict gates the
            # liveness flags for any further use and flags forged leaves).
            # Warn only on NEW exclusions relative to the latest verify:
            # a client caught mid-tree stays excluded and must not read
            # as a leaf forgery.  data_len == 1 skips this call entirely:
            # there the level-0 full check IS the leaf check, and a second
            # opening of triples_last under a fresh challenge would leak
            # <r - r', x> (see rpc.sketch_verify).
            a0, _ = await self._both("sketch_verify", {"level": L})
            prev = (
                alive_before_leaf
                if alive_before_leaf is not None
                else np.ones_like(np.asarray(a0))
            )
            if np.any(prev & ~np.asarray(a0)):
                obsmod.emit(
                    "sketch.leaf_forgery",
                    severity="warn",
                    new_exclusions=int(np.sum(prev & ~np.asarray(a0))),
                )
        # final reconstruction from re-served leaf shares: v0 - v1 per
        # surviving leaf (ref: collect.rs:993-1029 final_shares/final_values;
        # the crawl-time counts are only the pruning signal)
        f0, f1 = await self._both("final_shares")
        v = np.asarray(F255.sub(f0["shares"], f1["shares"]))
        final_counts = v[..., 0].astype(np.uint32)
        if np.any(v[..., 1:]) or not np.array_equal(final_counts, counts_kept):
            raise RuntimeError("final share reconstruction mismatch")
        return CrawlResult(paths=self.paths, counts=final_counts)

    # -- fault-tolerant crawl (resilience layer) -------------------------

    @staticmethod
    async def _probe(client) -> dict:
        """``status`` with restart absorption: the reconnect handshake may
        discover a new boot id and poison the FIRST call with
        ServerRestartedError; the second call runs against the fresh boot
        and is replay-free by construction."""
        try:
            return await client.call("status")
        except ServerRestartedError:
            return await client.call("status")

    async def _reprobe_and_reset(self) -> list:
        """The shared fault-recovery preamble: probe s0, re-establish the
        data plane via the dialer, probe s1, and diff both boot ids
        against the known table (updating it).  Returns the indices of
        servers that RESTARTED — a previously-unknown boot id is learned,
        not treated as a restart."""
        st0 = await self._probe(self.c0)
        await self.c0.call("plane_reset")
        st1 = await self._probe(self.c1)
        restarted = []
        for i, st in enumerate((st0, st1)):
            known = self._boot_ids.get(i)
            if known is not None and st["boot_id"] != known:
                restarted.append(i)
            self._boot_ids[i] = st["boot_id"]
        return restarted

    async def _recover(self, keys0, keys1, sketch0, sketch1, stash) -> int:
        """Bring both servers back to one consistent state after any
        control-plane, data-plane, or server loss; returns the next level
        to run.  With a checkpoint stash: redial, re-establish the data
        plane, re-seed restarted servers' keys (sketch material
        included), ``tree_restore`` both to the stash level.  Without
        one: full restart from level 0."""
        if stash is None and sketch0 is not None:
            # refuse BEFORE touching any server: restart-from-scratch
            # would re-upload the SAME Beaver triple shares and commit a
            # NEW ratchet root (fresh coin flip) — run 2's openings
            # d' = <r', x> - a against run 1's d = <r, x> - a hand the
            # servers <r - r', x> of every honest payload, the exact leak
            # the ratchet prevents.  The supervisor banks an init (level
            # -1) checkpoint precisely so this branch is unreachable with
            # a working FHH_CKPT_DIR; without one, the only sound rerun
            # uses fresh client sketch keys.
            raise ValueError(
                "sketch crawl cannot restart from scratch: re-opening "
                "the same Beaver slabs under a fresh challenge root "
                "would leak <r - r', x> — configure FHH_CKPT_DIR so "
                "recovery can roll back, or rerun with fresh sketch keys"
            )
        # probe s0 first: the supervisor's client redials under policy
        st0 = await self._probe(self.c0)
        # re-establish the data plane via the DIALER side, always: a
        # surviving s0's plane may be half-dead, and a restarted s1 can't
        # even serve its control plane until s0 redials (its start()
        # blocks on the plane accept before binding the RPC listener)
        await self.c0.call("plane_reset")
        st1 = await self._probe(self.c1)
        restarted = []
        for i, st in enumerate((st0, st1)):
            if st["boot_id"] != self._boot_ids.get(i):
                restarted.append(i)
            self._boot_ids[i] = st["boot_id"]
        # device-loss vs server-loss: a server with an INTACT boot id
        # whose mesh FAULT counter advanced since the leader last looked
        # lost a device, not itself — either it already re-sharded in
        # place (rpc._mesh_recover counts reshards too) or it escalated
        # for want of a usable checkpoint; the faults counter covers
        # both, where reshards alone would miss the escalation.  The
        # delta matters: the counters are cumulative per boot, so an old
        # fault must not re-attribute a later, unrelated recovery wave
        # (attribution is "since the last probe" — a silently recovered
        # reshard between waves lands on the next one).  Name both so
        # the postmortem (and the recovery tests) can tell the cases
        # apart without scraping server logs.
        device_loss = []
        for i, st in enumerate((st0, st1)):
            f = int((st.get("mesh") or {}).get("faults") or 0)
            if i not in restarted and f > self._mesh_faults.get(i, 0):
                device_loss.append(i)
            self._mesh_faults[i] = f
        if restarted or device_loss:
            obsmod.emit(
                "resilience.loss_classified",
                server_loss=restarted,
                device_loss=device_loss,
            )
        if stash is None:
            # no checkpoint to stand on: restart the crawl from scratch
            # (sketch mode was refused above — it can never restart)
            await self._both("reset")
            await self.upload_keys(keys0, keys1, sketch0, sketch1)
            await self._both("tree_init", {"root_bucket": self.min_bucket})
            self.paths = np.zeros((1, self.cfg.n_dims, 0), bool)
            self.n_nodes = 1
            self._f_bucket = self.min_bucket
            obsmod.emit(
                "resilience.restarted_from_scratch",
                severity="warn",
                restarted_servers=restarted,
            )
            return 0
        level = stash["level"]
        for i in restarted:
            # a restarted server lost its key batch; re-seed it before
            # tree_restore re-concatenates (NO reset here: reset would
            # delete the very checkpoint files we are about to restore)
            await self.upload_keys(keys0, keys1, sketch0, sketch1, which=i)
        r0, r1 = await self._both("tree_restore", {"level": level})
        if int(r0["level"]) != level or int(r1["level"]) != level:
            raise RuntimeError(
                f"restored levels diverge: s0={r0['level']} s1={r1['level']} "
                f"leader stash={level}"
            )
        self.paths = stash["paths"].copy()
        self.n_nodes = stash["n_nodes"]
        self._f_bucket = stash["f_bucket"]
        obsmod.emit(
            "resilience.restored",
            level=level,
            restarted_servers=restarted,
        )
        # next base on the fused level grid: a checkpoint banks the state
        # AFTER the fused level at base ``level``, so the crawl resumes at
        # level + r (level -1 is the sketch init checkpoint: resume at 0)
        rdx = max(1, int(self.cfg.crawl_radix_bits))
        if level < 0:
            return 0
        return level + min(rdx, self.cfg.data_len - level)

    async def run_supervised(
        self,
        nreqs: int,
        keys0: IbDcfKeyBatch,
        keys1: IbDcfKeyBatch,
        sketch0=None,
        sketch1=None,
        *,
        checkpoint_every: int = 8,
        max_recoveries: int = 4,
        warmup: bool = False,
    ) -> CrawlResult:
        """The fault-tolerant twin of :meth:`run`, owning the WHOLE crawl
        (reset + upload + levels + final reconstruction) because recovery
        needs the key batches to re-seed a restarted server.

        Per completed ``checkpoint_every`` levels it instructs both
        servers to ``tree_checkpoint`` and stashes the leader-side path
        bookkeeping; on any transport loss, server restart, or verb
        failure it rolls BOTH servers back to the last stash (fresh
        data-plane handshake included) and re-runs only the lost levels.
        Counts are exact re-runs: a recovered crawl's results are
        bit-identical to a fault-free one.

        Malicious (sketch) mode is supervised too: pass the clients'
        sketch key batches, and recovery re-seeds them alongside the
        ibDCF keys.  The per-level challenge RATCHET (sketch.py) makes
        the rollback sound — a re-run level derives the identical
        challenge from the committed root + restored transcript digest,
        so re-opening its Beaver slab is a bit-identical replay, never a
        second opening.  Checkpointing degrades gracefully: servers
        without a checkpoint dir disable it (recovery then means
        restart-from-scratch), keeping supervision usable everywhere."""
        # one distributed trace per supervised crawl — recovery waves,
        # restores, and the re-run levels all land in the SAME timeline
        with obstrace.root("crawl"), obstrace.profile_capture("crawl"):
            return await self._run_supervised(
                nreqs, keys0, keys1, sketch0, sketch1,
                checkpoint_every=checkpoint_every,
                max_recoveries=max_recoveries, warmup=warmup,
            )

    async def _run_supervised(
        self,
        nreqs: int,
        keys0: IbDcfKeyBatch,
        keys1: IbDcfKeyBatch,
        sketch0=None,
        sketch1=None,
        *,
        checkpoint_every: int = 8,
        max_recoveries: int = 4,
        warmup: bool = False,
    ) -> CrawlResult:
        cfg = self.cfg
        d, L = cfg.n_dims, cfg.data_len
        if cfg.malicious and sketch0 is None:
            # refuse BEFORE touching the servers: proceeding would upload
            # keys without their sketch material and silently run a
            # malicious-mode collection semi-honest
            raise ValueError(
                "run_supervised in malicious mode needs the sketch key "
                "batches (pass sketch0/sketch1)"
            )
        thresh = max(1, int(cfg.threshold * nreqs))
        await self._both("reset")
        await self.upload_keys(keys0, keys1, sketch0, sketch1)
        if warmup:
            # per-f_bucket compile warmup before any crawl time is spent;
            # rides inside the supervised flow because reset() above
            # cleared any earlier upload (warmup needs the key shapes)
            await self.warmup()
        await self._both("tree_init", {"root_bucket": self.min_bucket})
        self.paths = np.zeros((1, d, 0), bool)
        self.n_nodes = 1
        self._f_bucket = self.min_bucket
        self._boot_ids = {
            0: self.c0.boot_id,
            1: self.c1.boot_id,
        }
        stash = None  # leader-side bookkeeping at the last checkpoint
        counts_kept = np.zeros(0, np.uint32)
        alive_before_leaf = None  # liveness after the latest sketch verify
        # zero-touch the recovery counters so a fault-free supervised run
        # still reports a (zeroed) recovery section in the run report
        for c in ("recoveries", "levels_rerun", "shards_rerun"):
            self.obs.count(c, 0)
        ckpt_enabled = True
        if sketch0 is not None:
            # INIT checkpoint (level -1): sketch mode cannot restart from
            # scratch (see _recover's refusal — same triples under a new
            # root leak <r - r', x>), so bank a rollback point BEFORE any
            # Beaver slab opens: a fault ahead of the first level
            # checkpoint then restores the committed root + empty
            # transcript and replays from level 0 under the identical
            # challenge sequence.  Not counted as a crawl checkpoint —
            # no crawl progress is banked by it.
            try:
                await self._both("tree_checkpoint", {"level": -1})
                stash = {
                    "level": -1,
                    "paths": self.paths.copy(),
                    "n_nodes": 1,
                    "counts": counts_kept.copy(),
                    "f_bucket": self._f_bucket,
                    "alive": None,
                }
            except RuntimeError as e:
                ckpt_enabled = False
                obsmod.emit(
                    "resilience.checkpoint_disabled",
                    severity="warn",
                    error=str(e),
                )
        recoveries = 0
        level = 0
        rdx = max(1, int(cfg.crawl_radix_bits))
        while level < L:
            # radix-2^k fusion: this round covers bit-levels
            # level .. level+r-1; the next base is level + r
            r = min(rdx, L - level)
            try:
                with self.obs.span("level", level=level) as sp_level:
                    counts_kept, alive = await self._run_one_level(
                        level, nreqs, thresh
                    )
                self.obs.observe("level_latency", sp_level.seconds)
                obstrace.flush()  # as in run()
                if alive is not None:
                    alive_before_leaf = alive
                if counts_kept is None:
                    return CrawlResult(
                        paths=np.zeros((0, d, level + r), bool),
                        counts=np.zeros(0, np.uint32),
                    )
                if (
                    ckpt_enabled
                    and level + r < L
                    and (level + r) % checkpoint_every == 0
                ):
                    try:
                        await self._both("tree_checkpoint", {"level": level})
                        stash = {
                            "level": level,
                            "paths": self.paths.copy(),
                            "n_nodes": self.n_nodes,
                            "counts": counts_kept.copy(),
                            "f_bucket": self._f_bucket,
                            "alive": (
                                None
                                if alive_before_leaf is None
                                else alive_before_leaf.copy()
                            ),
                        }
                        self.obs.count("crawl_checkpoints", level=level)
                    except RuntimeError as e:
                        # servers can't checkpoint (no FHH_CKPT_DIR):
                        # supervise without — recovery restarts from 0
                        ckpt_enabled = False
                        obsmod.emit(
                            "resilience.checkpoint_disabled",
                            severity="warn",
                            error=str(e),
                        )
                level += r
            except (ConnectionError, TimeoutError, RuntimeError) as err:
                while True:
                    recoveries += 1
                    self.obs.count("recoveries")
                    obsmod.emit(
                        "resilience.recover",
                        severity="warn",
                        level=level,
                        attempt=recoveries,
                        error=f"{type(err).__name__}: {err}",
                    )
                    if recoveries > max_recoveries:
                        raise err
                    try:
                        level = await self._recover(
                            keys0, keys1, sketch0, sketch1, stash
                        )
                        break
                    except (ConnectionError, TimeoutError, RuntimeError) as e2:
                        err = e2  # recovery itself failed: another round
                if stash is not None:
                    counts_kept = stash["counts"].copy()
                    alive_before_leaf = (
                        None if stash["alive"] is None
                        else stash["alive"].copy()
                    )
                else:
                    counts_kept = np.zeros(0, np.uint32)
                    alive_before_leaf = None
                self.obs.count("levels_rerun")
        if self.has_sketch and L > 1:
            # final F255 leaf-payload check, as in run() (read-only from
            # the crawl's perspective: the verdict gates liveness flags)
            a0, _ = await self._both("sketch_verify", {"level": L})
            prev = (
                alive_before_leaf
                if alive_before_leaf is not None
                else np.ones_like(np.asarray(a0))
            )
            if np.any(prev & ~np.asarray(a0)):
                obsmod.emit(
                    "sketch.leaf_forgery",
                    severity="warn",
                    new_exclusions=int(np.sum(prev & ~np.asarray(a0))),
                )
        # final reconstruction, as in run() (final_shares is read-only:
        # the client's transparent replay covers transient losses here)
        f0, f1 = await self._both("final_shares")
        v = np.asarray(F255.sub(f0["shares"], f1["shares"]))
        final_counts = v[..., 0].astype(np.uint32)
        if np.any(v[..., 1:]) or not np.array_equal(final_counts, counts_kept):
            raise RuntimeError("final share reconstruction mismatch")
        return CrawlResult(paths=self.paths, counts=final_counts)


# ---------------------------------------------------------------------------
# Streaming ingest: the windowed front-door driver
# ---------------------------------------------------------------------------

# a client's submit backoff: quick first retries (token refill horizons
# are sub-second at sane rates), generous attempt count — a flooding
# client spends its time HERE, sleeping, which is exactly the
# backpressure story (it stalls itself, not the crawl)
INGEST_POLICY = respolicy.RetryPolicy(
    base_s=0.02, cap_s=0.5, factor=2.0, attempts=16
)


class IngestOverloadedError(RuntimeError):
    """Every backed-off retry of one submission was answered Overloaded —
    the caller is flooding faster than the front door will ever admit.
    Deliberately NOT transport-shaped: retrying at the RPC layer cannot
    help, the caller must slow down or drop."""


# Runtime twin of the fhh-race guard map — the "WindowedIngest.*"
# entries of pyproject [tool.fhh-lint.guards] (drift-tested in
# tests/test_concurrency.py); see rpc._SERVER_GUARDS for the contract.
_INGEST_GUARDS = {
    "window": "_submit_lock",
    "_journal": "_submit_lock",
    "_journaled": "_submit_lock",
    "_sealed": "_submit_lock",
}


class WindowedIngest:
    """Leader-side driver of the streaming front door: continuous
    ``submit_keys`` into tumbling windows, ``seal_window`` at each
    boundary, and :meth:`crawl_window` running the NORMAL level loop on
    the frozen snapshot while later submissions keep landing — the
    online successor of the one-bulk-upload ``upload_keys`` flow.

    Admission protocol: server 0 is the GATE — its verdict (admit slot /
    shed / Overloaded) is obtained first, then MIRRORED to server 1, so
    the two pools stay positionally identical without requiring the two
    servers to agree on arrival order.  Overloaded verdicts back off
    under ``policy`` (honoring the gate's ``retry_after_s`` hint) and
    re-attempt: a flooding client stalls itself.

    Window-consistent recovery: every admitted/shed submission is
    journaled until its window's crawl completes.  On a server restart
    the driver restores the last ingest checkpoint (``tree_restore`` of
    the ``ingest_only`` blob banked at each seal — pools, per-``sub_id``
    verdicts, reservoir RNG state), then REPLAYS the journal in mirror
    form — the recorded-verdict dedup makes the combination exactly-once
    (nothing lost, nothing double-counted) — re-seals, reloads, and
    re-runs the window's crawl.  Results are bit-exact vs a batch crawl
    over the same admitted key set (asserted in tests and bench)."""

    def __init__(self, lead: RpcLeader, *, policy=None, checkpoint=True):
        self.lead = lead
        self.cfg = lead.cfg
        self.window = 0
        self.policy = policy or INGEST_POLICY
        # a dedicated registry so the heartbeat names the ingest phase
        # (span "ingest" per window) independently of the crawl's spans;
        # a non-default collection is named in the registry so the
        # report's sessions rollup attributes its ingest counters
        _coll = getattr(lead, "collection", None) or "default"
        self.obs = obsmetrics.Registry(
            "ingest" if _coll == "default" else f"ingest:{_coll}"
        )
        self._span_ctx = None
        self._journal: dict[int, list] = {}  # window -> submission records
        self._journaled: set = set()  # sub_ids already journaled
        self._sealed: dict[int, dict] = {}  # window -> seal stats
        self._n_subs = 0
        self._ckpt = bool(checkpoint)
        self._have_ckpt = False
        # gate->mirror ordering: the mirror MUST apply verdicts in the
        # gate's decision order (pools are positional), so the
        # gate-call + mirror-call pair serializes on this lock; backoff
        # sleeps happen OUTSIDE it, so a rejected (flooding) client
        # stalls only itself while others keep submitting
        self._submit_lock = asyncio.Lock()
        # one recovery at a time: a concurrent submit and crawl may both
        # observe the same fault — the second waiter re-probes (cheap,
        # idempotent) instead of interleaving journal replays
        self._recover_lock = asyncio.Lock()
        # seed restart detection: recovery compares boot ids against the
        # ones the clients learned at connect time
        for i, c in ((0, lead.c0), (1, lead.c1)):
            if lead._boot_ids.get(i) is None:
                lead._boot_ids[i] = getattr(c, "boot_id", None)
        # LAST: the sanitizer (a no-op unless FHH_DEBUG_GUARDS=1 or
        # cfg.debug_guards) wraps the already-constructed guarded state
        guards.install(
            self, _INGEST_GUARDS,
            force=bool(getattr(self.cfg, "debug_guards", False)),
        )

    # -- window lifecycle -------------------------------------------------

    # fhh-race: atomic (telemetry-only read of the window id for the span label: one event-loop slice, and a label one boundary stale costs nothing)
    def _ensure_span(self) -> None:
        if self._span_ctx is None:
            with guards.unguarded(
                "telemetry-only window-id read for the span label "
                "(fhh-race atomic contract on _ensure_span)"
            ):
                w = self.window
            # fhh-lint: disable=span-discipline (explicitly managed context: the ingest span opens at the first submit and _exit_span closes it at the seal boundary — a with-block cannot straddle the two call sites)
            self._span_ctx = self.obs.span("ingest", level=w)
            self._span_ctx.__enter__()

    def _exit_span(self) -> None:
        if self._span_ctx is not None:
            self._span_ctx.__exit__(None, None, None)
            self._span_ctx = None

    async def submit(self, client_id, k0_chunk, k1_chunk, *,
                     sk0_chunk=None, sk1_chunk=None,
                     sub_id: str | None = None) -> dict:
        """Submit one client's key-share chunks (k0 to server 0, k1 to
        server 1) into the CURRENT window.  Blocks through Overloaded
        verdicts under the backoff policy; returns the gate verdict
        (``admitted`` or ``shed``).  Raises
        :class:`IngestOverloadedError` when every attempt was rejected.

        Malicious mode: ``sk0_chunk``/``sk1_chunk`` carry the client's
        sketch key leaves (the ``upload_keys`` sk_chunk form — flat
        ``jax.tree.leaves`` of its :class:`~.sketch.SketchKeyBatch`
        share); they ride the same admission verdict, the same journal
        record, and the same recovery replay as the ibDCF chunks."""
        self._ensure_span()
        t_admit = time.perf_counter()  # ingest-admit SLO clock (e2e:
        # gate + mirror + every Overloaded backoff this submission ate)
        if sub_id is None:
            # unique per LOGICAL submission; reused across transport
            # replays and recovery re-submissions so the servers'
            # recorded verdicts dedup them.  Window-independent: a
            # backed-off retry that straddles a seal lands in the NEW
            # current window under the same id.
            self._n_subs += 1
            sub_id = f"{self.lead.c0.session_id}:{self._n_subs}"
        k0_chunk = tuple(np.asarray(a) for a in k0_chunk)
        k1_chunk = tuple(np.asarray(a) for a in k1_chunk)
        if (sk0_chunk is None) != (sk1_chunk is None):
            raise ValueError(
                "sketch chunks come in pairs: pass sk0_chunk AND "
                "sk1_chunk (one per server's share) or neither"
            )
        if sk0_chunk is not None:
            sk0_chunk = [np.asarray(a) for a in sk0_chunk]
            sk1_chunk = [np.asarray(a) for a in sk1_chunk]
        n_keys = int(k0_chunk[0].shape[0])
        attempt = 0
        faults = 0
        while True:
            # the lock pins gate-order == mirror-order (positional
            # pools); a rejected attempt releases it before backing off.
            # The window id is read UNDER the lock: a concurrent
            # seal_window may have advanced it while this task waited,
            # and a stale id would target a sealed window.
            async with self._submit_lock:
                w = self.window
                base = {
                    "window": w, "sub_id": sub_id,
                    "client_id": str(client_id),
                }
                # the gate+mirror pair (and its fault recovery) runs
                # entirely under this lock hold: once the gate admits,
                # nothing — not even a seal — may interleave before the
                # mirror lands, or the two pools' seal stats diverge
                while True:
                    try:
                        r0 = await self.lead.c0.call(
                            "submit_keys",
                            dict(base, keys=k0_chunk, sketch=sk0_chunk),
                        )
                        if r0.get("overloaded"):
                            break
                        rec = {
                            "sub_id": sub_id,
                            "client_id": str(client_id),
                            "window": w,
                            "slot": r0.get("slot"),
                            "shed": bool(r0.get("shed")),
                            "k0": k0_chunk,
                            "k1": k1_chunk,
                            "sk0": sk0_chunk,
                            "sk1": sk1_chunk,
                        }
                        # journal BEFORE the mirror call: if s1 restarts
                        # mid-mirror, the recovery replay carries this
                        # record and the retried mirror dedups against it
                        # (a faulted retry of the same sub_id — the gate
                        # answers it as a dup — must not journal twice)
                        if sub_id not in self._journaled:
                            self._journal.setdefault(w, []).append(rec)
                            self._journaled.add(sub_id)
                        await self.lead.c1.call(
                            "submit_keys",
                            dict(
                                base, keys=k1_chunk, sketch=sk1_chunk,
                                mirror={"slot": rec["slot"],
                                        "shed": rec["shed"]},
                            ),
                        )
                        break
                    except respolicy.TRANSIENT_ERRORS:
                        # restart or transport loss mid-submission:
                        # recover the ingest state and re-attempt the
                        # SAME sub_id — recorded verdicts make the
                        # retry exactly-once
                        faults += 1
                        if faults > 8:
                            raise
                        await self._recover_ingest()
            if not r0.get("overloaded"):
                break
            if r0.get("scope") == "burst":
                # permanent by configuration: no refill horizon ever
                # covers a chunk larger than the burst — backing off
                # would be 16 futile round trips
                self.obs.count("ingest_rejected", level=w)
                raise IngestOverloadedError(
                    f"submission {sub_id} ({n_keys} keys) exceeds the "
                    "gate's burst allowance — split the chunk or raise "
                    "ingest_burst_keys"
                )
            attempt += 1
            self.obs.count("ingest_rejected", level=w)
            if attempt >= self.policy.attempts:
                raise IngestOverloadedError(
                    f"submission {sub_id} rejected {attempt} times "
                    f"(last scope: {r0.get('scope')}) — client is "
                    "over the front door's sustained capacity"
                )
            await asyncio.sleep(
                max(
                    float(r0.get("retry_after_s", 0.0)),
                    self.policy.delay(attempt - 1),
                )
            )
        if not rec["shed"]:
            # fhh-lint: disable=stale-read-across-await (deliberate snapshot: the admitted count labels the window this submission LANDED in — the id banked under the lock at gate time, not whatever window is current after the backoff awaits)
            self.obs.count("ingest_admitted", n_keys, level=w)
        self.obs.observe("ingest_admit", time.perf_counter() - t_admit)
        return r0

    async def seal_window(self) -> dict:
        """Freeze the current window on both servers (tumbling-window
        boundary), bank the ingest checkpoint, and open the next window.
        Returns the gate's seal stats (keys/subs/shed/rejected)."""
        faults = 0
        while True:
            # the WHOLE boundary — window-id read, seal pair, stats
            # bank, window advance — under one submit-lock hold: it must
            # not race a half-mirrored submission (gate applied, mirror
            # in flight), and the id must be read UNDER the lock — a
            # pre-lock read could re-seal a window a concurrent boundary
            # already advanced past and ROLL THE WINDOW COUNTER BACK
            # (fhh-race caught this: the PR-7 stale-window-id shape,
            # this time on the seal path)
            async with self._submit_lock:
                w = self.window
                try:
                    r0, r1 = await self.lead._both(
                        "window_seal", {"window": w}
                    )
                except respolicy.TRANSIENT_ERRORS:
                    faults += 1
                    if faults > 8:
                        raise
                    await self._recover_ingest()
                    continue
                # sk_root rides the comparison: the two servers derive
                # the window's challenge root from their own session
                # coin flip — a plane re-key landing between the two
                # seal-time handshakes would commit DIFFERENT roots,
                # and a root mismatch silently excludes every honest
                # client in the window (divergent challenge streams)
                if (
                    r0["keys"], r0["subs"], r0.get("sk_root")
                ) != (r1["keys"], r1["subs"], r1.get("sk_root")):
                    raise RuntimeError(
                        f"window {w} pools diverged at seal: "
                        f"gate {r0} vs mirror {r1}"
                    )
                # bank the DRIVER's seal instant with the stats: the
                # start of this window's seal-to-hitters SLO clock
                # (observed when crawl_window serves its hitters)
                self._sealed[w] = dict(r0, sealed_at=time.time())
                self.window = w + 1
                break
        self._exit_span()
        # shed keys include reservoir-replaced occupants the driver
        # cannot see per-submit — the seal stats are authoritative
        self.obs.count("ingest_shed", int(r0["shed_keys"]), level=w)
        self.obs.count("ingest_windows")
        obsmod.emit(
            "ingest.window_boundary",
            window=w,
            keys=int(r0["keys"]),
            subs=int(r0["subs"]),
            shed=int(r0["shed_keys"]),
            rejected=int(r0["rejected"]),
        )
        if self._ckpt:
            # bank the pools at the boundary: the rollback point a
            # kill-mid-window recovers to (ingest-only blob, level -1)
            try:
                await self.lead._both(
                    "tree_checkpoint", {"level": -1, "ingest_only": True}
                )
                self._have_ckpt = True
            except RuntimeError as e:
                self._ckpt = False  # no FHH_CKPT_DIR: journal replay covers
                obsmod.emit(
                    "ingest.checkpoint_disabled", severity="warn", error=str(e)
                )
        return r0

    # -- windowed crawl + recovery ---------------------------------------

    async def crawl_window(self, w: int, *, max_recoveries: int = 4):
        """Run the normal level loop over SEALED window ``w``'s frozen
        pool; ingest into later windows continues concurrently
        (``submit_keys`` bypasses the servers' verb lock).  On any
        transport loss / server restart the driver recovers ingest state
        (checkpoint restore + journal replay), reloads the window, and
        re-runs its crawl — results stay bit-exact because the frozen
        pool is reconstructed exactly and the crawl is deterministic.

        One distributed trace per WINDOW: the nested ``run()`` reuses
        it, so window_load, every level, and the final reconstruction
        share the window's trace id; the seal-to-hitters latency
        (seal instant banked by :meth:`seal_window` -> hitters served
        here) lands in the driver's ``seal_to_hitters`` histogram — the
        first-class SLO the always-on dashboard reads."""
        with obstrace.root("window"):
            return await self._crawl_window(w, max_recoveries=max_recoveries)

    async def _crawl_window(self, w: int, *, max_recoveries: int = 4):
        async with self._submit_lock:
            stats = self._sealed.get(w)
        if stats is None:
            raise RuntimeError(f"crawl_window: window {w} is not sealed")
        nreqs = int(stats["keys"])
        recoveries = 0
        while True:
            try:
                l0, _ = await self.lead._both("window_load", {"window": w})
                # a malicious window's crawl must run the sketch_verify
                # gates (the batch flow learns this at upload_keys; the
                # windowed flow learns it from the loaded pool)
                self.lead.has_sketch = bool(l0.get("sketch"))
                with self.obs.span("window_crawl", level=w):
                    res = await self.lead.run(nreqs)
                break
            except (ConnectionError, TimeoutError, RuntimeError) as err:
                recoveries += 1
                self.obs.count("ingest_recoveries")
                obsmod.emit(
                    "ingest.window_recover",
                    severity="warn",
                    window=w,
                    attempt=recoveries,
                    error=f"{type(err).__name__}: {err}",
                )
                if recoveries > max_recoveries:
                    raise
                try:
                    # under the submit lock (same _submit_lock ->
                    # _recover_lock order as submit/seal): the journal
                    # replay rebuilds pools POSITIONALLY, so a live
                    # gate+mirror pair interleaving with it could land
                    # between replayed records and diverge the two
                    # servers' slot order (fhh-race caught the unlocked
                    # form)
                    async with self._submit_lock:
                        await self._recover_ingest()
                except respolicy.TRANSIENT_ERRORS:
                    # a server still coming back up: the next loop turn
                    # re-probes (bounded by max_recoveries)
                    continue
        # seal -> hitters served: the driver-side seal-to-hitters SLO
        # observation (the servers observe their own copy at
        # final_shares; both merge into the report's slo section)
        # fhh-lint: disable=stale-read-across-await (deliberate snapshot: the SLO clock starts at the SEAL-time instant banked in the stats row — a sealed window's stats never mutate, and re-reading under the lock would return the identical row or None after the prune below)
        sealed_at = stats.get("sealed_at")
        if sealed_at is not None:
            self.obs.observe(
                "seal_to_hitters", max(0.0, time.time() - sealed_at)
            )
        # the window is crawled: its journal, journaled-id set, and seal
        # stats (and any earlier) are done — bounded driver memory
        # mirrors the servers' bounded pools.  Under the submit lock:
        # the prune itself never suspends, but the discipline is that
        # EVERY journal/seal-table access holds the lock, and a submit
        # mid-await must not watch its window's records vanish
        async with self._submit_lock:
            for old in [k for k in self._journal if k <= w]:
                for rec in self._journal[old]:
                    self._journaled.discard(rec["sub_id"])
                del self._journal[old]
            for old in [k for k in self._sealed if k <= w]:
                del self._sealed[old]
        return res

    async def _recover_ingest(self) -> None:
        """Bring both servers' INGEST state back to this driver's view:
        probe boot ids, re-key the data plane, and for every restarted
        server restore the last ingest checkpoint then replay the journal
        (mirror form, recorded verdicts deduping) and re-seal the sealed
        windows.  The crawl state is NOT restored here — the caller
        reloads the window and re-runs, which rebuilds it exactly."""
        async with self._recover_lock:
            await self._recover_ingest_locked()

    async def _recover_ingest_locked(self) -> None:
        lead = self.lead
        restarted = await lead._reprobe_and_reset()
        for i in restarted:
            client = lead.c0 if i == 0 else lead.c1
            if self._have_ckpt:
                try:
                    await client.call("tree_restore", {"level": -1})
                except RuntimeError as e:
                    obsmod.emit(
                        "ingest.restore_failed",
                        severity="warn",
                        server=i,
                        error=str(e),
                    )
            await self._replay_journal(client, i)
            for w in sorted(self._sealed):
                req = {"window": w}
                root = self._sealed[w].get("sk_root")
                if root is not None:
                    # the ORIGINAL window challenge root banked at first
                    # seal: a journal-rebuilt pool on a restarted server
                    # must commit THIS root, never derive a fresh one
                    # (same Beaver slabs under a new root leak
                    # <r - r', x> on the window's re-run)
                    req["sk_root"] = root
                await client.call("window_seal", req)
            obsmod.emit("ingest.server_reseeded", server=i)

    async def _replay_journal(self, client, which: int) -> None:
        """Re-submit every journaled record to ONE server in mirror form
        (the recorded gate verdicts, not fresh admission): entries the
        restored checkpoint already carries answer as dups, the rest
        rebuild the pool positionally identical to the pre-fault one."""
        replayed = 0
        for w in sorted(self._journal):
            for rec in self._journal[w]:
                await client.call(
                    "submit_keys",
                    {
                        "window": rec["window"],
                        "sub_id": rec["sub_id"],
                        "client_id": rec["client_id"],
                        "keys": rec["k0"] if which == 0 else rec["k1"],
                        "sketch": rec.get("sk0" if which == 0 else "sk1"),
                        "mirror": {"slot": rec["slot"], "shed": rec["shed"]},
                    },
                )
                replayed += 1
        if replayed:
            self.obs.count("ingest_journal_replays", replayed)

    # -- fleet: live migration + whole-host failover ----------------------

    async def migrate(self, new_lead: RpcLeader) -> dict:
        """Live-migrate this session onto ``new_lead``'s host pair
        mid-stream (protocol/fleet.py decides *where*; this is *how*).

        Quiesce: the whole transfer runs under ``_submit_lock`` →
        ``_recover_lock`` (the same order submit/seal/crawl-recovery
        take), so no gate+mirror pair is half-landed and no boundary is
        mid-advance; the servers additionally refuse to export mid-level.
        Steps, ordered so a failure at ANY point leaves the source
        authoritative (the source copy is only retired LAST):

        1. ``session_export`` on both source servers (stamped blobs);
        2. ``session_import`` on both destination servers — stamp-
           verified, validate-before-mutate, and the destination
           re-keys its own per-session base-OT/coin-flip plane;
        3. rebind the driver to ``new_lead`` and replay the journal in
           mirror form: recorded verdicts dedup everything the export
           already carried, so every in-flight ``sub_id`` lands exactly
           once;
        4. re-seal the sealed windows with the ORIGINAL banked challenge
           roots (a migrated malicious window re-opens the IDENTICAL
           challenge — never a second opening);
        5. bank a fresh ingest checkpoint on the destination, then
           retire the source copy (drops its retained pools — satellite
           of the bounded-retention contract)."""
        async with self._submit_lock:
            async with self._recover_lock:
                old = self.lead
                x0 = await old.c0.call("session_export", {})
                x1 = await old.c1.call("session_export", {})
                await new_lead.c0.call("session_import", {
                    "path": x0["path"], "boot": x0["boot"],
                    "epoch": x0["epoch"],
                })
                await new_lead.c1.call("session_import", {
                    "path": x1["path"], "boot": x1["boot"],
                    "epoch": x1["epoch"],
                })
                # the destination pair is now authoritative-in-waiting:
                # rebind, then make its pools exactly-once complete
                new_lead.has_sketch = old.has_sketch
                for i, c in ((0, new_lead.c0), (1, new_lead.c1)):
                    new_lead._boot_ids[i] = getattr(c, "boot_id", None)
                self.lead = new_lead
                replayed = sum(len(v) for v in self._journal.values())
                await self._replay_journal(new_lead.c0, 0)
                await self._replay_journal(new_lead.c1, 1)
                for w in sorted(self._sealed):
                    req = {"window": w}
                    root = self._sealed[w].get("sk_root")
                    if root is not None:
                        req["sk_root"] = root
                    await new_lead._both("window_seal", req)
                if self._ckpt:
                    try:
                        await new_lead._both(
                            "tree_checkpoint",
                            {"level": -1, "ingest_only": True},
                        )
                        self._have_ckpt = True
                    except RuntimeError as e:
                        self._ckpt = False
                        obsmod.emit(
                            "ingest.checkpoint_disabled", severity="warn",
                            error=str(e),
                        )
                # LAST: drop the source copy (both halves) — only after
                # the destination holds the complete, re-sealed state
                await old.c0.call(
                    "session_export",
                    {"retire": True, "epoch": x0["epoch"]},
                )
                await old.c1.call(
                    "session_export",
                    {"retire": True, "epoch": x1["epoch"]},
                )
                windows = [int(w) for w in x0.get("windows", [])]
        self.obs.count("ingest_migrations")
        obsmod.emit(
            "ingest.session_migrated", windows=windows, replayed=replayed,
        )
        return {"windows": windows, "replayed": replayed}

    async def failover_to(self, new_lead: RpcLeader, *,
                          level: int = -1) -> dict:
        """Whole-host failover: the source pair is DEAD (dead boot id on
        probe — fleet.FleetDirectory.probe) — adopt ``new_lead``'s
        surviving pair from this session's newest banked checkpoint in
        the shared store.  Same machinery as :meth:`migrate` minus the
        export/retire half: ``session_import`` of the ``level``-stamped
        ingest blob (the one seal_window banks at every boundary), then
        journal replay + re-seal with the original challenge roots.  A
        session that never checkpointed still recovers: the journal
        replay alone rebuilds every pool positionally."""
        async with self._submit_lock:
            async with self._recover_lock:
                new_lead.has_sketch = self.lead.has_sketch
                imported = False
                for c in (new_lead.c0, new_lead.c1):
                    if self._have_ckpt:
                        try:
                            await c.call("session_import", {"level": level})
                            imported = True
                        except RuntimeError as e:
                            obsmod.emit(
                                "ingest.restore_failed", severity="warn",
                                error=str(e),
                            )
                for i, c in ((0, new_lead.c0), (1, new_lead.c1)):
                    new_lead._boot_ids[i] = getattr(c, "boot_id", None)
                self.lead = new_lead
                replayed = sum(len(v) for v in self._journal.values())
                await self._replay_journal(new_lead.c0, 0)
                await self._replay_journal(new_lead.c1, 1)
                for w in sorted(self._sealed):
                    req = {"window": w}
                    root = self._sealed[w].get("sk_root")
                    if root is not None:
                        req["sk_root"] = root
                    await new_lead._both("window_seal", req)
        self.obs.count("ingest_failovers")
        obsmod.emit(
            "ingest.session_failed_over", imported=imported,
            replayed=replayed,
        )
        return {"imported": imported, "replayed": replayed}


# ---------------------------------------------------------------------------
# Multi-tenant: N concurrent collections against ONE server pair
# ---------------------------------------------------------------------------


class MultiCollectionDriver:
    """Run N collections CONCURRENTLY against one collector server pair
    (the multi-tenant driver of ROADMAP items b/c).

    Each job gets its own client pair — the ``collection`` field of the
    ``__hello__`` handshake binds every connection to its
    per-collection server session (protocol/sessions.py) — and its own
    :class:`RpcLeader`, then all jobs run concurrently on one event
    loop: while tenant A's level waits on the GC/OT wire, tenant B's
    expand dispatches (the servers' TenantScheduler counts those stall
    fills).  Results are bit-identical to solo runs of the same keys by
    construction: independent trees, independent FSS keys, independent
    OT streams per session — asserted end-to-end in
    tests/test_sessions.py and gated in ``bench_multitenant``."""

    def __init__(self, cfg: Config, host0: str, port0: int,
                 host1: str, port1: int, *, min_bucket: int = 1,
                 budgets: respolicy.VerbBudgets | None = None):
        self.cfg = cfg
        self._addr0 = (host0, port0)
        self._addr1 = (host1, port1)
        self.min_bucket = min_bucket
        self.budgets = budgets
        self.leaders: dict[str, RpcLeader] = {}

    async def open(self, collection: str) -> RpcLeader:
        """Connect one collection's client pair and build its leader
        (``reset`` included, so the session starts clean)."""
        kw = {"collection": collection}
        if self.budgets is not None:
            kw["budgets"] = self.budgets
        c0 = await CollectorClient.connect(*self._addr0, **kw)
        c1 = await CollectorClient.connect(*self._addr1, **kw)
        lead = RpcLeader(self.cfg, c0, c1, min_bucket=self.min_bucket)
        await lead._both("reset")
        self.leaders[collection] = lead
        return lead

    async def close(self) -> None:
        for lead in self.leaders.values():
            for c in (lead.c0, lead.c1):
                await c.aclose()
        self.leaders.clear()

    async def run_collections(self, jobs: list, *, supervised: bool = True,
                              warmup: bool = False,
                              checkpoint_every: int = 8) -> dict:
        """Run every job concurrently; returns {collection: CrawlResult}.

        ``jobs``: list of dicts ``{collection, nreqs, keys0, keys1}``
        with optional ``sketch0``/``sketch1`` (malicious mode).
        ``supervised`` routes through :meth:`RpcLeader.run_supervised`
        (per-tenant checkpoints in the session's own namespace,
        per-tenant recovery); False runs the bare upload+run path.  A
        single tenant's failure does not tear the others down — it is
        reported under its collection key as the raised exception."""

        async def one(job: dict):
            key = str(job["collection"])
            lead = self.leaders.get(key) or await self.open(key)
            if supervised:
                return await lead.run_supervised(
                    int(job["nreqs"]), job["keys0"], job["keys1"],
                    job.get("sketch0"), job.get("sketch1"),
                    checkpoint_every=checkpoint_every, warmup=warmup,
                )
            await lead.upload_keys(
                job["keys0"], job["keys1"],
                job.get("sketch0"), job.get("sketch1"),
            )
            if warmup:
                await lead.warmup()
            return await lead.run(int(job["nreqs"]))

        keys = [str(j["collection"]) for j in jobs]
        done = await asyncio.gather(
            *(one(j) for j in jobs), return_exceptions=True
        )
        results = dict(zip(keys, done))
        failed = {k: r for k, r in results.items() if isinstance(r, BaseException)}
        if failed:
            obsmod.emit(
                "tenancy.collections_failed",
                severity="warn",
                collections=sorted(failed),
                errors={k: f"{type(e).__name__}: {e}" for k, e in failed.items()},
            )
        return results
