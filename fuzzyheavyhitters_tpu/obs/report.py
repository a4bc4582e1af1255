"""End-of-run machine-readable report.

One JSON document aggregating every registry's snapshot — the artifact
postmortems consume instead of scraping stdout.  Schema
(``fhh-run-report/1``)::

    {
      "schema": "fhh-run-report/1",
      "written_at": <epoch seconds>,
      "registries": {
        "server0": {
          "counters": {"data_bytes_sent": {"total": N, "by_level": {"0": n0, ...}}, ...},
          "gauges":   {"survivors":       {"last": v, "by_level": {...}}, ...},
          "phases":   {"fss": {"seconds": s, "count": c, "by_level": {...}}, ...}
        },
        ...
      }
    }

Well-known metric names (what populates them):

- phases ``fss`` / ``gc_ot`` / ``field`` — the reference's per-level
  3-phase server breakdown (protocol/rpc.py crawl verbs; trusted mode's
  ``gc_ot`` slot is the plaintext exchange), plus ``level`` on the
  leader/driver side and ``upload_keys`` / ``setup`` one-offs.
- phases ``otext`` / ``garble`` / ``eval`` / ``b2a`` — the secure-kernel
  split of ``gc_ot`` (extension, circuit garble/eval — zero on the
  1-of-2^S path — and payload-table/open + field conversion); they are
  a BREAKDOWN of gc_ot, not additive with it, and the wire wait is the
  gc_ot remainder.  Counters ``ot_path_ot2s`` / ``ot_path_gc`` count
  levels by the equality-test engine taken, ``secure_chunks`` the
  chunks a level's two messages crossed in (1 = whole),
  ``secure_chunk_programs`` the device programs of its ``otext`` +
  ``b2a`` spans, ``secure_fetch_syncs`` / ``secure_phase_waits`` where
  they were waited for (a fetch's thread / the stage); gauges
  ``device_waits_high`` / ``device_wait_threads`` the most such thread
  calls a server had parked at once in a level and the threads it has
  for them, counter ``secure_account_errors`` the fetches whose stamps
  could not be recorded.  Rolled up across
  registries into a top-level ``secure_kernels`` section whenever a
  secure crawl ran.
- phases ``stage_wall:<stage>`` / ``stage_starved:<stage>`` /
  ``stage_blocked:<stage>`` — the wait account of the secure level's
  chunk pipeline (protocol/rpc.py ``_Stage``: the evaluator's stages
  ``extend``, ``u_fetch``, ``u_send``, ``open``, the garbler's
  ``build``, ``msg_fetch``, ``msg_send``), and ``program_dispatch`` /
  ``program_device`` / ``program_hop``, ``d2h_ready`` / ``d2h_copy`` /
  ``d2h_hop``, ``send_resume`` — how a span that waits on a thread
  splits; ``stream_gap`` — what the data plane's writer thread waited
  between two frames of a level's send stage, by its own stamps (a send
  stage keeps two frames with it, so its next frame is queued when a
  write ends); timers alone (no span-log record), per server in
  ``secure_kernels.stages``.
- counters ``data_bytes_sent`` / ``data_bytes_recv`` /
  ``data_msgs_sent`` — server↔server data plane, per level;
  ``control_bytes_*`` — leader↔server control plane;
  ``wire_oob_bytes`` — the part of a registry's sent bytes (either
  plane) that crossed as raw array buffers, not through pickle
  (protocol/wire.py); ``plane_stream_frames`` — data-plane frames sent
  through their stream's writer thread (``wire.PlaneStreams``: all of
  ``data_msgs_sent``), ``plane_sends_overlapped`` those of them handed
  over while the thread still held another (a chunk level's send stage:
  near (K-1)/K of its frames; 0 where a level is one frame), with the
  gauge ``plane_send_queue_high`` (the
  most frames that thread held at a hand-over of the level, that frame
  included: 1 = the stream was free) — rolled up per server into a
  top-level ``plane`` section whenever a data plane carried a frame;
  ``device_fetches`` — device->host transfers (each a synchronous
  round trip: the COUNT is a latency term beside the byte count, so
  both are measured); ``gc_tests`` — secure-mode equality tests;
  ``checkpoint_writes`` / ``checkpoint_restores``.
- gauges ``ot_batch_size`` (per level), ``secure_string_bits``,
  ``child_patterns`` and ``secure_payload_words`` (per level: the bits a
  secure level's equality test compares, S = 2 a dimension and radix
  step, the child patterns a node, 2^d a step, and the u32 words of its
  message's payload, the width of the field the level counts over: 2
  for FE62, 8 for F255; ``secure_kernels.string_bits`` /
  ``.child_patterns`` of the last level, ``.payload_words`` every
  width a level had), ``survivors`` /
  ``frontier_nodes`` (per level).
- counters ``keys_placed_bytes`` (bytes of a bulk upload's batches
  written to their rows of the resident key planes as they arrived,
  protocol/keyplanes.py: the key-plane bytes once an upload) and
  ``key_planes_reused`` (``tree_init`` / ``warmup`` / ``tree_restore``
  calls that found the planes resident and placed nothing); gauges
  ``key_plane_bytes`` (set where the planes are allocated) and
  ``key_host_bytes_held`` (host batches the session still references
  when a crawl opens: 0 unless it keeps them to re-place after a lost
  chip, i.e. has a checkpoint directory); phase ``key_place`` (the
  wait for the last write, once an upload).
- counters ``recoveries`` / ``levels_rerun`` / ``shards_rerun``
  (supervising leaders, socket and mesh) and ``dedup_hits`` /
  ``verb_requests`` (servers' idempotent-replay accounting) — rolled up
  across registries into a top-level ``recovery`` section
  (``{count, levels_rerun, shards_rerun, dedup_hits, dedup_hit_rate}``)
  whenever any supervised component ran, so a recovered run is
  distinguishable from a fault-free one in the report alone.
- gauge ``data_shards`` + phase ``ici_reduce`` + counters
  ``mesh_reshards`` / ``mesh_faults`` (multi-chip servers,
  parallel/server_mesh.py: client-axis shard count, the pre-wire ICI
  psum's fetch-synced seconds, and device-loss recovery events), plus
  gauge ``kernel_shards`` + phase ``kernel_gather`` / counter
  ``kernel_gathers`` (the row-sharded secure kernel stage,
  parallel/kernel_shard.py: the level's active kernel shard count — 1 =
  the degraded gather-to-one-device path — and that gather's dispatch
  seconds, ~0 whenever the sharded stage carries the crawl) — rolled
  up into a top-level ``mesh`` section whenever a multi-chip crawl ran.
- counters ``ingest_admitted`` / ``ingest_shed`` / ``ingest_rejected`` /
  ``ingest_windows`` + phases ``ingest`` / ``window_crawl`` (the
  windowed front-door driver's dedicated registry,
  leader_rpc.WindowedIngest) — rolled up into a top-level ``ingest``
  section (``{admitted, shed, rejected, windows, keys_per_sec,
  window_crawl_seconds}``) whenever a streaming run happened; servers
  additionally keep ``pool_*`` counters surfaced by the ``status`` verb.

``FHH_RUN_REPORT=<path>`` makes the binaries (and bench) write the
report there at exit / on SIGTERM; :func:`maybe_write_run_report` is
that one-liner.
"""

from __future__ import annotations

import contextlib
import json
import os
import signal
import time

from . import alerts as _alerts
from . import metrics
from ..utils import taint_guard
from . import trace as tracemod
from .hist import Histogram

SCHEMA = "fhh-run-report/1"


def run_report(registries=None) -> dict:
    """Aggregate snapshot of ``registries`` (default: every live one,
    plus the retained final snapshots of dropped ones — see
    ``metrics._retain_final``; snapshots beyond the retention bound are
    counted under ``dropped_registries`` so the cap is never silent).

    Same-named registries (a second ``driver.Leader`` after a checkpoint
    restore registers another ``driver``) get deterministic ``name#2``,
    ``name#3``, ... keys in registration order instead of silently
    overwriting each other."""
    dropped = 0
    if registries is None:
        # dedupe by (name, seq), live snapshot winning: at interpreter
        # exit the weakref finalizers (whose exitfunc registers at first
        # Registry creation, AFTER e.g. bench's atexit dump) may have
        # already retained final snapshots of registries that are still
        # alive — without the dedupe every one would appear twice
        by_id = {
            (name, seq): (name, seq, snap)
            for name, seq, snap in metrics.final_snapshots()
        }
        for r in metrics.all_registries():
            by_id[(r.name, r.seq)] = (r.name, r.seq, r.report())
        items = sorted(by_id.values(), key=lambda t: (t[0], t[1]))
        dropped = metrics.final_dropped()
    else:
        items = [(r.name, r.seq, r.report()) for r in registries]
    out: dict = {}
    seen: dict = {}
    for name, _seq, snap in items:
        n = seen[name] = seen.get(name, 0) + 1
        out[name if n == 1 else f"{name}#{n}"] = snap
    doc = {
        "schema": SCHEMA,
        "written_at": round(time.time(), 3),
        "registries": out,
    }
    rec = _recovery_summary(out)
    if rec is not None:
        doc["recovery"] = rec
    pipe = _pipeline_summary(out)
    if pipe is not None:
        doc["pipeline"] = pipe
    sk = _secure_kernel_summary(out)
    if sk is not None:
        doc["secure_kernels"] = sk
    plane = _plane_summary(out)
    if plane is not None:
        doc["plane"] = plane
    sketch = _sketch_summary(out)
    if sketch is not None:
        doc["sketch"] = sketch
    ing = _ingest_summary(out)
    if ing is not None:
        doc["ingest"] = ing
    fleet = _fleet_summary(out)
    if fleet is not None:
        doc["fleet"] = fleet
    mesh = _mesh_summary(out)
    if mesh is not None:
        doc["mesh"] = mesh
    sess = _sessions_summary(out)
    if sess is not None:
        doc["sessions"] = sess
    slo = _slo_summary(out)
    if slo is not None:
        doc["slo"] = slo
    # alert transitions (obs.alerts): everything that fired in this
    # process, so a postmortem reader sees the stall/burn/backlog
    # events inline with the accounting they explain — None (absent)
    # when nothing fired, keeping the pre-alert report shape exact
    al = _alerts.report_section()
    if al is not None:
        doc["alerts"] = al
    if dropped:
        doc["dropped_registries"] = dropped
    # the report is handed to files/stdout whole: assert no registered
    # secret buffer rode a summary row in (fhh-taint runtime twin)
    taint_guard.check(doc, sink="run-report")
    return doc


def _slo_summary(registries: dict) -> dict | None:
    """Cross-registry SLO rollup: every latency histogram
    (obs.hist.Histogram — fixed buckets, so same-named histograms merge
    across the leader, both servers, and every per-session registry by
    summing bucket counts) reduced to p50/p95/p99 + max.  Per-verb RPC
    latencies (``rpc:<verb>`` histograms on the servers) fold into a
    ``verbs`` sub-table; everything else (``level_latency``,
    ``seal_to_hitters``, ``ingest_admit``) is a top-level metric with a
    ``by_registry`` breakdown so the merged count's multiplicity (each
    server observes every level once) stays visible.  Chip-profiler
    captures (FHH_PROFILE) ride along under ``profile`` with the trace
    ids they were taken in.  Present only when some histogram (or
    capture) exists — pre-SLO runs omit the section entirely."""
    merged: dict = {}
    by_reg: dict = {}
    for name, snap in registries.items():
        for hname, hsnap in (snap.get("hists") or {}).items():
            h = Histogram.from_snapshot(hsnap)
            if hname in merged:
                merged[hname].merge(h)
            else:
                merged[hname] = h
            by_reg.setdefault(hname, {})[name] = {
                k: v for k, v in hsnap.items() if k != "buckets"
            }
    captures = tracemod.profile_captures()
    if not merged and not captures:
        return None
    out: dict = {}
    verbs: dict = {}
    for hname in sorted(merged):
        row = merged[hname].summary()
        if hname.startswith("rpc:"):
            verbs[hname.split(":", 1)[1]] = row
            continue
        row["by_registry"] = by_reg.get(hname, {})
        out[hname] = row
    if verbs:
        out["verbs"] = verbs
    if captures:
        out["profile"] = captures
    return out


def _recovery_summary(registries: dict) -> dict | None:
    """Cross-registry recovery rollup: a RECOVERED run must be
    distinguishable from a fault-free one in the report alone.  Sums the
    supervisor counters (``recoveries`` / ``levels_rerun`` /
    ``shards_rerun``) and the servers' replay-dedup accounting
    (``dedup_hits`` over ``verb_requests`` -> hit rate) across every
    registry.  Present whenever any of those counters exists — a
    supervised fault-free run reports zeros, an unsupervised legacy run
    omits the section entirely."""
    names = (
        "recoveries", "levels_rerun", "shards_rerun",
        "dedup_hits", "verb_requests",
    )
    sums = dict.fromkeys(names, 0)
    seen = False
    for snap in registries.values():
        counters = snap.get("counters", {})
        for n in names:
            if n in counters:
                seen = True
                sums[n] += counters[n].get("total", 0)
    if not seen:
        return None
    return {
        "count": sums["recoveries"],
        "levels_rerun": sums["levels_rerun"],
        "shards_rerun": sums["shards_rerun"],
        "dedup_hits": sums["dedup_hits"],
        "dedup_hit_rate": round(
            sums["dedup_hits"] / max(1, sums["verb_requests"]), 6
        ),
    }


def _pipeline_summary(registries: dict) -> dict | None:
    """Cross-registry pipelined-crawl rollup (protocol/leader_rpc.py's
    bounded-depth span pipeline): per level and overall, the configured
    in-flight ``depth``, ``overlap_seconds`` (span busy-time the pipeline
    hid behind the level's wall-clock), and ``stalls`` (head-of-line
    reassembly waits while a later span had already finished), plus
    ``faults`` whenever a mid-flight failure quiesced the pipeline into
    the sequential fallback.  Present only when a pipelined crawl ran —
    sequential (depth 1) runs never emit these metrics."""
    depth_by, overlap_by, stall_by = {}, {}, {}
    overlap_total = stalls_total = faults_total = 0
    depth_last = None
    seen = False
    for snap in registries.values():
        g = snap.get("gauges", {}).get("pipeline_depth")
        if g is not None:
            seen = True
            depth_last = g.get("last")
            depth_by.update(g.get("by_level", {}))
        t = snap.get("phases", {}).get("pipeline_overlap")
        if t is not None:
            seen = True
            overlap_total += t.get("seconds", 0.0)
            for lvl, s in t.get("by_level", {}).items():
                overlap_by[lvl] = overlap_by.get(lvl, 0.0) + s
        for name, total, by in (
            ("pipeline_stalls", "stalls", stall_by),
            ("pipeline_faults", "faults", None),
        ):
            c = snap.get("counters", {}).get(name)
            if c is None:
                continue
            seen = True
            if total == "stalls":
                stalls_total += c.get("total", 0)
                for lvl, n in c.get("by_level", {}).items():
                    by[lvl] = by.get(lvl, 0) + n
            else:
                faults_total += c.get("total", 0)
    if not seen:
        return None
    levels = sorted(
        set(depth_by) | set(overlap_by) | set(stall_by), key=lambda k: int(k)
    )
    return {
        "depth": depth_last,
        "overlap_seconds": round(overlap_total, 6),
        "stalls": stalls_total,
        "faults": faults_total,
        "by_level": {
            lvl: {
                "depth": depth_by.get(lvl),
                "overlap_seconds": round(overlap_by.get(lvl, 0.0), 6),
                "stalls": stall_by.get(lvl, 0),
            }
            for lvl in levels
        },
    }


def _by_level(d: dict) -> dict:
    """``{level (a string): value}`` in the levels' order."""
    return dict(sorted(d.items(), key=lambda kv: int(kv[0])))


def _secure_kernel_summary(registries: dict) -> dict | None:
    """Cross-registry secure-kernel rollup (the acceptance instrument of
    the device-resident GC/OT work): per phase, total seconds summed
    across every registry (garbler and evaluator roles alternate per
    level, so one server's registry holds half of each phase), plus the
    per-level union breakdown and the equality-test path actually taken
    (``ot2s`` / ``gc`` / ``mixed`` from the ot_path_* counters).
    Present only when a secure crawl ran — trusted runs never emit these
    metrics."""
    names = ("otext", "garble", "eval", "b2a")
    totals = dict.fromkeys(names, 0.0)
    by_level: dict = {}
    paths = {"ot2s": 0, "gc": 0}
    chunks: dict = {}
    # per-level counters both servers keep, the larger of the two
    larger = {
        "secure_chunk_programs": {}, "secure_fetch_syncs": {},
        "secure_phase_waits": {},
    }
    held: dict = {}
    index_high = 0
    # a server's waits for the device (protocol/rpc.py ``_DeviceWaits``):
    # the most thread calls one had parked at once in a level, the
    # threads it has for them, and faults in a fetch's recording
    waits = {"device_waits_high": 0, "device_wait_threads": 0}
    account_errors = 0
    shape = {"secure_string_bits": None, "child_patterns": None}
    widths: set = set()
    kshards = None
    kgather = 0.0
    seen = False
    for snap in registries.values():
        phases = snap.get("phases", {})
        for n in names:
            t = phases.get(n)
            if t is None:
                continue
            seen = True
            totals[n] += t.get("seconds", 0.0)
            for lvl, s in t.get("by_level", {}).items():
                by_level.setdefault(lvl, dict.fromkeys(names, 0.0))
                by_level[lvl][n] += s
        for p in paths:
            c = snap.get("counters", {}).get(f"ot_path_{p}")
            if c is not None:
                seen = True
                paths[p] += c.get("total", 0)
        # both servers cut a level alike: one registry's count a level
        # (a level crawled again counts again)
        c = snap.get("counters", {}).get("secure_chunks")
        for lvl, k in (c or {}).get("by_level", {}).items():
            chunks[lvl] = max(chunks.get(lvl, 0), k)
        for name, by in larger.items():
            c = snap.get("counters", {}).get(name)
            for lvl, k in (c or {}).get("by_level", {}).items():
                by[lvl] = max(by.get(lvl, 0), k)
        g = snap.get("gauges", {}).get("kernel_shards")
        if g is not None:
            kshards = g.get("last") if kshards is None else max(
                kshards, g.get("last")
            )
        g = snap.get("gauges", {}).get("ot_index_high")
        if g is not None:
            index_high = max(index_high, g.get("last"))
        for name in waits:
            g = snap.get("gauges", {}).get(name) or {}
            waits[name] = max(
                waits[name], g.get("last", 0), *g.get("by_level", {}).values()
            )
        account_errors += snap.get("counters", {}).get(
            "secure_account_errors", {}
        ).get("total", 0)
        for name in shape:
            g = snap.get("gauges", {}).get(name)
            if g is not None:
                shape[name] = g.get("last")
        g = snap.get("gauges", {}).get("secure_payload_words")
        widths.update((g or {}).get("by_level", {}).values())
        g = snap.get("gauges", {}).get("secure_t_rows_held_bytes")
        for lvl, b in (g or {}).get("by_level", {}).items():
            held[lvl] = max(held.get(lvl, 0), b)
        t = phases.get("kernel_gather")
        if t is not None:
            kgather += t.get("seconds", 0.0)
    if not seen:
        return None
    stages = {
        name: acct for name, snap in registries.items()
        if (acct := _stage_account(snap.get("phases", {}))) is not None
    }
    if paths["ot2s"] and paths["gc"]:
        ot_path = "mixed"
    elif paths["gc"]:
        ot_path = "gc"
    else:
        ot_path = "ot2s"
    return {
        "ot_path": ot_path,
        "levels_ot2s": paths["ot2s"],
        "levels_gc": paths["gc"],
        # chunks each level's two messages crossed in (protocol/rpc.py
        # ``_ev_chunks``; 1 = the level went whole)
        "chunks_by_level": _by_level(chunks),
        # device programs a server handed over inside each level's
        # ``otext`` + ``b2a`` spans (counter ``secure_chunk_programs``):
        # one a span, 2 x chunks
        "chunk_programs_by_level": _by_level(larger["secure_chunk_programs"]),
        # where those programs (and the circuit's) were waited for: on
        # the thread of the fetch that takes their output (counter
        # ``secure_fetch_syncs``: the garbling server's 2 a chunk on the
        # table path, against the other's 1) or by the stage that
        # dispatched them (``secure_phase_waits``: the evaluating
        # server's 1 a chunk for the opening, against the other's 0);
        # both 0 with ``secure_phase_sync`` off
        "fetch_syncs_by_level": _by_level(larger["secure_fetch_syncs"]),
        "phase_waits_by_level": _by_level(larger["secure_phase_waits"]),
        # the most thread calls a server had parked in waits for the
        # device at once in a level (gauge ``device_waits_high``) beside
        # the threads of its own it has for them (``device_wait_threads``:
        # a reading above it is calls queued for a thread), and fetches
        # whose stamps could not be recorded (counter
        # ``secure_account_errors``; the level went on)
        **waits,
        "account_errors": account_errors,
        # device bytes of the chunks the evaluator had sent u for and not
        # yet opened, at the fullest of each level (gauge
        # ``secure_t_rows_held_bytes``)
        "t_rows_held_bytes_by_level": _by_level(held),
        # the high word of the OT sessions' 64-bit pad index (gauge
        # ``ot_index_high``): above 0, a session has extended 2^32 OTs
        "ot_index_high": index_high,
        # the last level's shape: bits an equality test compares (2 a
        # dimension and radix step) and child patterns a node; and every
        # width a level's payload crossed in, u32 words (2 = FE62, 8 =
        # F255: [2, 8] for a crawl that reached its leaf)
        "string_bits": shape["secure_string_bits"],
        "child_patterns": shape["child_patterns"],
        "payload_words": sorted(widths),
        # kernel-stage layout (multi-chip servers only; None/0.0 on a
        # single-device crawl — see the mesh section for the per-level
        # breakdown): the phase seconds above are the SHARDED kernels'
        # whenever kernel_shards > 1
        "kernel_shards": kshards,
        "kernel_gather_seconds": round(kgather, 6),
        **{f"{n}_seconds": round(totals[n], 6) for n in names},
        "by_level": {
            lvl: {n: round(v[n], 6) for n in names}
            for lvl, v in sorted(by_level.items(), key=lambda kv: int(kv[0]))
        },
        # the chunk pipeline's wait account, per registry that ran a
        # stage (protocol/rpc.py ``_Stage``): see _stage_account
        "stages": stages,
    }


_SEND_STAGES = ("u_send", "msg_send")
_SPLIT_TIMERS = (
    "program_dispatch", "program_device", "program_hop",
    "d2h_ready", "d2h_copy", "d2h_hop", "send_resume",
)


def _stage_account(phases: dict) -> dict | None:
    """One registry's wait account of the secure level's chunk pipeline
    (timers ``stage_wall:<stage>`` / ``stage_starved:`` /
    ``stage_blocked:``, protocol/rpc.py): per stage its wall, what it
    waited for input and for room downstream, the rest (busy) and busy
    as a share of ``gc_ot`` over the levels the stage ran in (a server
    garbles every other level); ``pace_setter``, the stage busy for the
    largest share of it (where every stage lives about one ``gc_ot``, as
    in a level of many chunks, the one that waited least: never starved,
    never blocked); and how the spans that wait on a thread
    split (``program_*`` of ``otext`` + ``b2a`` + ``eval`` + ``garble``,
    ``d2h_*`` of ``d2h``, ``send_resume``).  A send stage (``u_send``,
    ``msg_send``) keeps two frames with the plane's writer thread, so
    its row also has that thread's account of the stage's frames over
    the same levels: ``wire_write_seconds`` (the socket's own) and
    ``stream_gap_seconds`` (the thread's waits between them).  Their
    sum is the thread's life with the stage's frames and lies inside
    the stage's wall: busy is at least that sum less the stage's waits
    (a stage that starves while a frame is written is not busy) and at
    most ``wire_pickle`` + that sum + the first frame's ``wire_queue``
    + the last frame's ``send_resume``; where the stage sets the pace,
    busy is the sum and little else.  None where no stage ran."""
    seconds = lambda name: phases.get(name, {}).get("seconds", 0.0)
    gc_by_level = phases.get("gc_ot", {}).get("by_level", {})
    over = lambda name, levels: sum(
        phases.get(name, {}).get("by_level", {}).get(lvl, 0.0)
        for lvl in levels
    )
    by_stage = {}
    for name, t in phases.items():
        kind, _, stage = name.partition(":")
        if kind != "stage_wall":
            continue
        wall = t.get("seconds", 0.0)
        starved = seconds(f"stage_starved:{stage}")
        blocked = seconds(f"stage_blocked:{stage}")
        levels = t.get("by_level", {})
        gc_ot = sum(gc_by_level.get(lvl, 0.0) for lvl in levels)
        busy = wall - starved - blocked
        by_stage[stage] = {
            "wall_seconds": round(wall, 6),
            "starved_seconds": round(starved, 6),
            "blocked_seconds": round(blocked, 6),
            "busy_seconds": round(busy, 6),
            "busy_share_of_gc_ot": round(busy / gc_ot, 4) if gc_ot else 0.0,
        }
        if stage in _SEND_STAGES:
            by_stage[stage].update(
                wire_write_seconds=round(over("wire_write", levels), 6),
                stream_gap_seconds=round(over("stream_gap", levels), 6),
            )
    if not by_stage:
        return None
    return {
        "by_stage": by_stage,
        "pace_setter": max(
            by_stage, key=lambda st: by_stage[st]["busy_share_of_gc_ot"]
        ),
        "split": {n: round(seconds(n), 6) for n in _SPLIT_TIMERS},
    }


def _plane_summary(registries: dict) -> dict | None:
    """The data plane's streams, per registry that sent on one: frames
    sent, how many of them went through the stream's writer thread (a
    frame that took any other way shows as the difference), how many of
    those were handed over while the thread still held another (a chunk
    level's send stage keeps two with it), and the most frames that
    thread held at once; None when no plane carried a frame."""
    out = {}
    for name, snap in registries.items():
        counters = snap.get("counters", {})
        sent = counters.get("data_msgs_sent")
        if sent is None:
            continue
        g = snap.get("gauges", {}).get("plane_send_queue_high") or {}
        out[name] = {
            "msgs_sent": sent.get("total", 0),
            "stream_frames": counters.get(
                "plane_stream_frames", {}
            ).get("total", 0),
            "sends_overlapped": counters.get(
                "plane_sends_overlapped", {}
            ).get("total", 0),
            "send_queue_high": max(
                [g.get("last", 0), *g.get("by_level", {}).values()]
            ),
        }
    return out or None


def _sketch_summary(registries: dict) -> dict | None:
    """Cross-registry malicious-sketch rollup (the device-resident
    sharded verify, parallel/sketch_shard.py): total verify seconds
    (the per-level ``sketch`` phase summed across both servers), the
    levels verified, and the verify's shard layout (``sketch_shards``
    gauge — max across levels; 1 = the single fused program).  Present
    only when a sketch verification ran — semi-honest runs never emit
    these metrics."""
    seconds = 0.0
    levels: set = set()
    shards = None
    seen = False
    for snap in registries.values():
        t = snap.get("phases", {}).get("sketch")
        if t is not None:
            seen = True
            seconds += t.get("seconds", 0.0)
            levels |= set(t.get("by_level", {}))
        g = snap.get("gauges", {}).get("sketch_shards")
        if g is not None:
            seen = True
            vals = [v for v in g.get("by_level", {}).values()]
            if g.get("last") is not None:
                vals.append(g["last"])
            if vals:
                m = max(vals)
                shards = m if shards is None else max(shards, m)
    if not seen:
        return None
    return {
        "verify_seconds": round(seconds, 6),
        "levels_verified": len(levels),
        "sketch_shards": shards,
    }


def _ingest_summary(registries: dict) -> dict | None:
    """Cross-registry streaming-ingest rollup (the windowed front door,
    protocol/leader_rpc.WindowedIngest): admitted/shed keys and rejected
    (Overloaded) attempts, sealed-window count, sustained admission rate
    over the ingest phase's wall-clock, and the windowed crawls' total
    seconds.  The driver's dedicated ``ingest`` registry is the source
    of truth (servers keep their own ``pool_*`` counters for ``status``);
    present only when a streaming run happened — batch-upload runs omit
    the section entirely."""
    names = ("ingest_admitted", "ingest_shed", "ingest_rejected",
             "ingest_windows")
    sums = dict.fromkeys(names, 0)
    ingest_s = crawl_s = 0.0
    seen = False
    for snap in registries.values():
        counters = snap.get("counters", {})
        for n in names:
            if n in counters:
                seen = True
                sums[n] += counters[n].get("total", 0)
        phases = snap.get("phases", {})
        t = phases.get("ingest")
        if t is not None:
            seen = True
            ingest_s += t.get("seconds", 0.0)
        t = phases.get("window_crawl")
        if t is not None:
            seen = True
            crawl_s += t.get("seconds", 0.0)
    if not seen:
        return None
    return {
        "admitted": sums["ingest_admitted"],
        "shed": sums["ingest_shed"],
        "rejected": sums["ingest_rejected"],
        "windows": sums["ingest_windows"],
        "keys_per_sec": round(
            sums["ingest_admitted"] / ingest_s, 2
        ) if ingest_s > 0 else None,
        "window_crawl_seconds": round(crawl_s, 6),
    }


def _fleet_summary(registries: dict) -> dict | None:
    """Cross-registry fleet rollup (protocol/fleet.py): placement
    decisions, live migrations and whole-host failovers (the placer's
    ``fleet`` registry), plus the per-server ``session_exports`` /
    ``session_imports`` verb counters and the driver-side journal
    replays that made each transfer exactly-once.  Present only when a
    fleet operation happened — single-pair runs omit the section."""
    names = ("placement_decisions", "session_migrations",
             "session_failovers", "session_exports", "session_imports",
             "ingest_migrations", "ingest_failovers", "sessions_retired")
    sums = dict.fromkeys(names, 0)
    seen = False
    for snap in registries.values():
        counters = snap.get("counters", {})
        for n in names:
            if n in counters:
                seen = True
                sums[n] += counters[n].get("total", 0)
    if not seen:
        return None
    return {
        "placement_decisions": sums["placement_decisions"],
        "session_migrations": sums["session_migrations"],
        "session_failovers": sums["session_failovers"],
        "session_exports": sums["session_exports"],
        "session_imports": sums["session_imports"],
        "sessions_retired": sums["sessions_retired"],
    }


def _mesh_summary(registries: dict) -> dict | None:
    """Cross-registry multi-chip rollup (per-server client sharding,
    parallel/server_mesh.py): the shard count the crawl ran at
    (``data_shards`` gauge, per level), total + per-level
    ``ici_reduce_seconds`` (the pre-wire psum's cost instrument — fetch-
    synced, so these are real seconds), and the device-loss recovery
    counters (``mesh_reshards`` — frontier re-placed from a host-side
    checkpoint; ``mesh_faults`` — every injected/detected mesh fault).
    Present only when a multi-chip crawl ran — single-device servers
    never emit these metrics."""
    shards_last = None
    shards_by: dict = {}
    kshards_last = None
    kshards_by: dict = {}
    ici_total = kgather_total = 0.0
    ici_by: dict = {}
    reshards = faults = kgathers = 0
    seen = False
    for snap in registries.values():
        g = snap.get("gauges", {}).get("data_shards")
        if g is not None:
            seen = True
            shards_last = g.get("last")
            shards_by.update(g.get("by_level", {}))
        g = snap.get("gauges", {}).get("kernel_shards")
        if g is not None:
            seen = True
            kshards_last = g.get("last")
            for lvl, v in g.get("by_level", {}).items():
                kshards_by[lvl] = max(kshards_by.get(lvl, 0), v)
        t = snap.get("phases", {}).get("ici_reduce")
        if t is not None:
            seen = True
            ici_total += t.get("seconds", 0.0)
            for lvl, s in t.get("by_level", {}).items():
                ici_by[lvl] = ici_by.get(lvl, 0.0) + s
        t = snap.get("phases", {}).get("kernel_gather")
        if t is not None:
            seen = True
            kgather_total += t.get("seconds", 0.0)
        c = snap.get("counters", {}).get("kernel_gathers")
        if c is not None:
            seen = True
            kgathers += c.get("total", 0)
        for name in ("mesh_reshards", "mesh_faults"):
            c = snap.get("counters", {}).get(name)
            if c is None:
                continue
            seen = True
            if name == "mesh_reshards":
                reshards += c.get("total", 0)
            else:
                faults += c.get("total", 0)
    if not seen:
        return None
    levels = sorted(set(shards_by) | set(ici_by), key=lambda k: int(k))
    return {
        "data_shards": shards_last,
        "ici_reduce_seconds": round(ici_total, 6),
        # row-sharded secure kernel stage (parallel/kernel_shard.py):
        # the active kernel-shard count (1 = the degraded gather path).
        # kernel_gathers counts exactly the crawl levels that gathered
        # the packed share bits onto one device — the LAYOUT detector
        # (0 on a fully sharded crawl); kernel_gather_seconds is those
        # gathers' dispatch time (the transfer completes lazily under
        # the level's later fetch), a supplement to the counter
        "kernel_shards": kshards_last,
        "kernel_gathers": kgathers,
        "kernel_gather_seconds": round(kgather_total, 6),
        "reshards": reshards,
        "faults": faults,
        "by_level": {
            lvl: {
                "data_shards": shards_by.get(lvl),
                "ici_reduce_seconds": round(ici_by.get(lvl, 0.0), 6),
                **(
                    {"kernel_shards": kshards_by[lvl]}
                    if lvl in kshards_by
                    else {}
                ),
            }
            for lvl in levels
        },
    }


def _sessions_summary(registries: dict) -> dict | None:
    """Cross-registry multi-tenant rollup (per-collection sessions,
    protocol/sessions.py + tenancy.py): per collection, the crawl phase
    seconds and ingest counters summed across its per-session
    registries (named ``server{N}:{key}`` / ``leader:{key}``; the
    default collection rides the bare ``server{N}``/``leader``
    registries and is NOT broken out), plus the tenant scheduler's
    device-turn/stall-fill accounting (``tenant_device_turns`` /
    ``tenant_stall_fills`` on the server registries — a stall fill is a
    device dispatch that ran while ANOTHER collection waited on the
    GC/OT wire, i.e. the idle gap multi-tenancy exists to fill).
    Present only when a multi-tenant run happened — single-collection
    runs omit the section entirely."""
    per: dict = {}
    turns = fills = 0
    seen = False
    for name, snap in registries.items():
        counters = snap.get("counters", {})
        for cname, total_key in (
            ("tenant_device_turns", "turns"),
            ("tenant_stall_fills", "fills"),
        ):
            c = counters.get(cname)
            if c is None:
                continue
            # turns/fills alone do NOT make the section present: every
            # crawl takes device turns — only a per-session registry
            # (a non-default collection) marks a multi-tenant run
            if total_key == "turns":
                turns += c.get("total", 0)
            else:
                fills += c.get("total", 0)
        base = name.split("#", 1)[0]  # strip the dedup suffix
        if ":" not in base:
            continue
        seen = True
        key = base.split(":", 1)[1]
        row = per.setdefault(
            key,
            {"crawl_seconds": 0.0, "levels": 0, "ingest_admitted": 0,
             "data_bytes": 0},
        )
        # heartbeat-gap instrument: the server stamps a per-session
        # last_progress_ts gauge at every verb completion, so a wedged
        # tenant is visible from the report (and live from ``status``)
        # without reading logs — the age here is "as of report time"
        g = snap.get("gauges", {}).get("last_progress_ts")
        if g is not None and g.get("last") is not None:
            row["last_progress_s"] = round(
                max(0.0, time.time() - float(g["last"])), 3
            )
        phases = snap.get("phases", {})
        for ph in ("fss", "gc_ot", "field"):
            t = phases.get(ph)
            if t is not None:
                row["crawl_seconds"] += t.get("seconds", 0.0)
                lv = [int(k) for k in t.get("by_level", {})]
                if lv:
                    row["levels"] = max(row["levels"], max(lv) + 1)
        for cname in ("pool_admitted_keys", "ingest_admitted"):
            c = counters.get(cname)
            if c is not None:
                row["ingest_admitted"] += c.get("total", 0)
        for cname in ("data_bytes_sent", "data_bytes_recv"):
            c = counters.get(cname)
            if c is not None:
                row["data_bytes"] += c.get("total", 0)
    if not seen:
        return None
    for row in per.values():
        row["crawl_seconds"] = round(row["crawl_seconds"], 6)
    return {
        "count": len(per),
        "device_turns": turns,
        "stall_fills": fills,
        "fill_ratio": round(fills / max(1, turns), 6),
        "per_session": dict(sorted(per.items())),
    }


def write_run_report(path: str, registries=None) -> dict:
    rep = run_report(registries)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(rep, f, indent=1)
    os.replace(tmp, path)  # atomic: a SIGKILL mid-write leaves no torn file
    return rep


def maybe_write_run_report(registries=None) -> str | None:
    """Write to ``$FHH_RUN_REPORT`` if set; returns the path written."""
    path = os.environ.get("FHH_RUN_REPORT")
    if not path:
        return None
    write_run_report(path, registries)
    return path


def per_process_report_path(path: str, tag: str) -> str:
    """``/tmp/r.json`` + ``s0`` -> ``/tmp/r.s0.json``.  Multi-process
    deployments (the two socket servers and their leader) inherit ONE
    ``FHH_RUN_REPORT`` path from the shared environment, and each process
    writes the whole document atomically at exit — without a per-process
    suffix the last exiter silently clobbers the other parties' reports."""
    root, ext = os.path.splitext(path)
    return f"{root}.{tag}{ext}"


def claim_report_path(tag: str) -> None:
    """Rewrite this process's ``$FHH_RUN_REPORT`` to its per-process
    path (no-op when the env var is unset)."""
    path = os.environ.get("FHH_RUN_REPORT")
    if path:
        os.environ["FHH_RUN_REPORT"] = per_process_report_path(path, tag)


def _sigterm(_sig, _frame):
    raise SystemExit(143)


@contextlib.contextmanager
def exit_report(heartbeat_default_s: float = 30.0):
    """The binaries' shared exit contract: SIGTERM -> ``SystemExit(143)``
    (so the ``finally`` runs instead of the default immediate kill),
    heartbeat on, and the run report written on the way out — a
    timed-out/killed run still leaves the per-level accounting it
    accumulated plus a heartbeat trail naming the phase it died in."""
    from .heartbeat import start_heartbeat

    signal.signal(signal.SIGTERM, _sigterm)
    start_heartbeat(heartbeat_default_s)
    try:
        yield
    finally:
        maybe_write_run_report()
        tracemod.flush()  # the trace ring survives the exit too
