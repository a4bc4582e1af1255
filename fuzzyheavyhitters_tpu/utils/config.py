"""Config system — JSON schema compatible with the reference's config files.

The schema mirrors ``Config`` in the reference (ref: src/config.rs:5-16):
``data_len, n_dims, ball_size, addkey_batch_size, num_sites, threshold,
zipf_exponent, server0, server1, distribution``.  The reference's shipped
JSON files also carry ``sketch_batch_size`` / ``sketch_batch_size_last``
keys that its parser ignores (config.rs vs src/bin/config.json:9-10); here
they are live in the spec helper (protocol/sketch.py ``verify_level``
chunks the client axis by them; the *_last knob covers the 8-limb F255
level).  The production verify (protocol/rpc.py ``sketch_verify``) runs
the whole level as ONE fused device program — sharded by
``sketch_shards`` below — so the host chunking knobs no longer gate it.

Extra TPU-native knobs (all defaulted so reference configs load unchanged):

- ``backend``: "tpu" | "cpu" — aggregation device; "cpu" pins the server's
  array ops onto the host backend (bin/server.py).
- ``secure_exchange``: if True, the GC+OT 2PC data plane (protocol/secure.py);
  if False, the trusted-exchange mode that reveals per-(node,client)
  equality bits between the two servers (counts still travel as field
  shares toward the leader).
- ``malicious``: if True, clients attach MAC'd sketch keys + Beaver triples
  (protocol/sketch.py — the resurrected sketch.rs/mpc.rs path named in
  BASELINE.json) and the servers verify every level, excluding cheating
  clients via the liveness gate.  Covers the flagship fuzzy multi-dim
  workloads: one payload DPF per dimension sharing the client's MAC key,
  verified per dim with per-dim prefix dedup (the product frontier repeats
  per-dim prefixes across slots).  Caveat: verification follows the
  *frontier* the servers actually crawl — depth 1 is checked in full
  before the first threshold, and the server refuses depth-1 re-verifies
  afterward (Beaver-triple reuse under a fresh challenge would leak).
- ``f_max``: padded-frontier capacity (static device shapes).
- ``crawl_shard_nodes``: split each level's crawl into node-axis shards of
  this many frontier slots, one RPC verb per shard — a mid-level fault
  then re-runs only the lost shards (protocol/leader_rpc.py shard retry).
  0 (default) keeps one verb per level.
- ``crawl_pipeline_depth``: how many shard verbs the leader keeps in
  flight at once on a sharded level (protocol/leader_rpc.py pipelined
  crawl): span k's GC/OT network phase overlaps span k+1's device
  expand.  1 (default) is the sequential PR-4 path; values > 1 require
  ``crawl_shard_nodes`` > 0 to have any effect.  Results are
  bit-identical either way; on any in-flight fault the pipeline
  quiesces and falls back to the sequential per-span retry.
"""

from __future__ import annotations

import argparse
import dataclasses
import json


@dataclasses.dataclass
class Config:
    data_len: int
    n_dims: int
    ball_size: int
    addkey_batch_size: int
    num_sites: int
    threshold: float
    zipf_exponent: float
    server0: str
    server1: str
    distribution: str
    sketch_batch_size: int = 100_000
    sketch_batch_size_last: int = 25_000
    backend: str = "tpu"
    secure_exchange: bool = False
    malicious: bool = False
    f_max: int = 1024  # padded-frontier capacity (static shapes on device)
    # mid-level retry granularity: frontier-node span per crawl shard
    # (each shard is its own RPC verb — a mid-level fault re-runs only
    # the lost shards, protocol/leader_rpc.py).  0 disables sharding.
    crawl_shard_nodes: int = 0
    # sharded-crawl pipelining: shard verbs the leader keeps in flight
    # (1 = sequential; >1 overlaps span k's plane I/O with span k+1's
    # device expand — protocol/leader_rpc.py pipelined crawl)
    crawl_pipeline_depth: int = 1
    # radix-2^k level fusion (protocol/collect.py): crawl k prefix bits
    # per wire round trip — each fused level expands every frontier node
    # by all 2^(k·n_dims) child patterns, runs ONE equality stage at the
    # fused string width S' = k·2·n_dims, and prunes on the depth-(base+k)
    # counts (bit-identical to k sequential levels: a fused child
    # survives iff its count clears threshold, and monotone counts make
    # the intermediate-depth prunes subsumed).  1 = today's crawl,
    # bit-identical compiled programs; 2 and 3 cut round trips, per-level
    # telemetry, and checkpoint cadence by k.  Dim caps from the 32-bit
    # packed plane (collect.check_radix): k=2 ⇒ n_dims ≤ 2, k=3 ⇒ 1.
    # Both servers + leader must agree; checkpoints/exports stamp it.
    crawl_radix_bits: int = 1
    # equality-test engine (protocol/secure.ot_path): "auto" runs the
    # 1-of-2^S chosen-payload OT (no garbled circuit) whenever the
    # string width S = 2·n_dims fits secure.OT2S_MAX_S, the garbled
    # circuit beyond; "ot2s"/"gc" force one path (both servers derive
    # the path from this knob + S, so the wire format always agrees)
    ot_path: str = "auto"
    # secure crawls garble/evaluate each level as ONE whole-level device
    # program (ignoring crawl_shard_nodes for the GC/OT batch) — the
    # device-resident batching that closes the trusted/secure gap.  Set
    # False to restore node-sharded secure levels (mid-level retry at
    # span granularity, span pipelining) at the cost of fragmenting the
    # equality batch into host-sized chunks.
    secure_whole_level: bool = True
    # multi-chip collector servers (parallel/server_mesh.py): how many
    # LOCAL devices each CollectorServer shards the client axis over.
    # 0 = auto: every visible local device on an accelerator host, ONE on
    # a CPU host (the virtual host-platform devices exist for tests —
    # production CPU servers gain nothing from sharding a host backend;
    # tests pass explicit counts under
    # XLA_FLAGS=--xla_force_host_platform_device_count=8).  1 pins the
    # single-device path; N > 1 requests exactly N (capped at the visible
    # device count, then at the largest divisor of the client batch).
    # Results are bit-identical at every setting: sharding is a physical
    # layout, the 2PC transcript never changes (asserted in tier-1).
    server_data_devices: int = 0
    # multi-chip SECURE KERNEL stage (parallel/kernel_shard.py): how many
    # of the server's data-mesh devices the whole-level 2PC kernels —
    # row-sharded IKNP extension, 1-of-2^S / GC equality, b2a — shard
    # over.  0 = auto: follow the mesh's data shards.  1 pins the
    # single-device kernel path (the packed share bits gather over ICI
    # before string extraction — the pre-PR-10 layout).  N > 1 caps the
    # kernel shards at N; the ACTIVE count per level is the largest
    # divisor of the level's planar block count (padded_tests(B)/8192)
    # that fits, so a small batch degrades to fewer shards — ultimately
    # to the gather path — instead of failing.  The wire is byte-
    # identical at every setting (asserted in tier-1).
    secure_kernel_shards: int = 0
    # malicious-secure SKETCH verify sharding (parallel/sketch_shard.py):
    # how many of the server's data-mesh devices the per-level check
    # batch (the three MAC/square checks per (client, dim)) shards over.
    # 0 = auto: follow the mesh's data shards.  1 pins the single fused
    # program; N > 1 caps at N.  The ACTIVE count is the largest divisor
    # of the client batch that fits, so a non-dividing batch degrades to
    # fewer shards instead of failing.  The challenge stream, both wire
    # messages, and the verdict vector are bit-identical at every
    # setting (asserted in tier-1).
    sketch_shards: int = 0
    # per-level secure-kernel phase split (phase_otext/garble/eval/b2a
    # spans in the run report): True syncs the device at each phase
    # boundary so the spans carry real device time — the acceptance
    # instrument for kernel work.  False skips the syncs (spans then
    # measure dispatch only); the phases are sequential data-dependent
    # steps, so the syncs cost only the dispatch-ahead slack.
    secure_phase_sync: bool = True
    # -- streaming ingest front door (protocol/rpc.py submit_keys,
    #    resilience/admission.py) -------------------------------------
    # hard per-window pool bound in KEYS — the "no unbounded queue"
    # invariant made a number; over it the shed policy below decides
    ingest_window_keys: int = 1 << 20
    # keys/sec token-bucket rate limit at the gate server (0 = off) and
    # its burst allowance; an over-rate submission gets a retryable
    # Overloaded verdict carrying the refill horizon
    ingest_rate_keys_per_s: float = 0.0
    ingest_burst_keys: int = 4096
    # per-client keys-per-window quota (0 = off): a flooding client hits
    # its quota and backs off; other clients' admissions are unaffected
    ingest_client_quota: int = 0
    # over-capacity behavior: "reject" answers Overloaded (the client
    # backs off and retries); "reservoir" keeps the pool a seeded
    # uniform sample of everything offered (native.Reservoir — seed-
    # reproducible, checkpoint-carried)
    ingest_shed: str = "reject"
    # seed for the reservoir shed sampler (per-window streams derive
    # from it deterministically)
    ingest_seed: int = 0
    # how many ingest windows a server keeps live at once (the sealed
    # window being crawled + the window(s) still accruing); bounds
    # server memory against a runaway window id
    ingest_windows_retained: int = 4
    # multi-tenant collection sessions (protocol/sessions.py): how many
    # per-collection sessions one server keeps live at once.  Each
    # session owns a full crawl state (frontier, keys, ingest pools,
    # OT endpoints), so the bound is a memory bound; at the cap an IDLE
    # session (nothing uploaded, no pools, not mid-verb) is evicted
    # oldest-first and a new collection is otherwise refused loudly.
    collection_sessions_max: int = 8
    # arm the fhh-race runtime sanitizer (utils/guards.py) on this
    # process's servers/drivers regardless of FHH_DEBUG_GUARDS — every
    # guarded-attribute access then asserts its owning lock is held by
    # the current task.  Debug/chaos-suite instrumentation, never a
    # production knob: attribute access gains a descriptor hop
    debug_guards: bool = False


def load_config(path: str) -> Config:
    with open(path) as f:
        raw = json.load(f)
    fields = {f.name for f in dataclasses.fields(Config)}
    unknown = set(raw) - fields
    if unknown:
        raise ValueError(f"Unknown config keys: {sorted(unknown)}")
    return Config(**raw)


def get_args(name: str, get_server_id: bool = False, get_n_reqs: bool = False):
    """CLI mirroring the reference's flags (ref: src/config.rs:55-111)."""
    p = argparse.ArgumentParser(prog=name, description="TPU-native private fuzzy heavy hitters.")
    p.add_argument("-c", "--config", required=True, help="Location of JSON config file")
    if get_server_id:
        p.add_argument("-i", "--server_id", type=int, required=True, help="Zero-indexed ID of server")
    if get_n_reqs:
        p.add_argument("-n", "--num_requests", type=int, required=True, help="Number of client requests")
    args = p.parse_args()
    cfg = load_config(args.config)
    server_id = getattr(args, "server_id", -1)
    n_reqs = getattr(args, "num_requests", 0)
    return cfg, server_id, n_reqs
