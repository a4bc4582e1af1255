"""What a traced run's span log says, and how well it agrees with the
profiler's clock.

    python scripts/trace_spans.py <FHH_TRACE_DIR> [--capture <x.xplane.pb>
        --wall-ns-at-sync <ns> [--sync-event bench_sync]]

Reads the program's JSONL span log (obs/trace.py) and prints one JSON
object:

- ``span_ms``: per ``<comp>:<name>`` the count, median, mean and largest
  duration, in milliseconds;
- ``gc_ot_cover``: per server, over its ``gc_ot`` spans, the share of the
  span that the UNION of its leaf spans' intervals covers (median and
  smallest: a secure level that crosses in chunks runs its stages as tasks,
  so a server's leaves overlap and their sum passes the span), the leaves' sum over
  the span (``busy_share_median``: how many leaves ran at once), the median
  milliseconds of each leaf inside one ``gc_ot`` (summed over its chunks),
  and for the levels that crossed in chunks (spans labelled ``chunk``) the
  median count of chunks and the median milliseconds of one chunk's leaf;
- ``wire_oob``: per component, the frames that carried raw array buffers
  (``wire_oob`` instants, one a frame: protocol/rpc.py ``_send``), their
  framed bytes, the bytes out of band (the counter ``wire_oob_bytes``) and
  the share;
- ``secure_levels``: per component, over its ``secure_level`` instants
  (one a secure level), the most chunks a level crossed in, the most
  device programs one handed over inside its ``otext`` + ``b2a`` spans
  (the counter ``secure_chunk_programs``: 2 a chunk), the most of them
  waited for on a fetch's thread and the most awaited by their stage
  (``secure_fetch_syncs``, ``secure_phase_waits``), the most
  device bytes its evaluator held in unopened chunks (the gauge
  ``secure_t_rows_held_bytes``), the high word of the OT pad index
  (the gauge ``ot_index_high``), the widest shape a level had (bits an
  equality test compares and child patterns a node: the gauges
  ``secure_string_bits``, ``child_patterns``) and every width a level's
  payload crossed in, u32 words (the gauge ``secure_payload_words``:
  ``[2, 8]`` where inner levels went at FE62's two and a leaf at F255's
  eight);
- ``plane_streams``: per component, over its ``plane_send`` instants
  (one a data-plane frame sent through its stream's writer thread:
  protocol/rpc.py ``_dp_send_finish``), the frames (the counter
  ``plane_stream_frames``), those of them handed over while the writer
  still held another (the counter ``plane_sends_overlapped``: a chunk
  level's send stage keeps two frames with the thread), the most frames
  the writer held at a hand-over (the gauge ``plane_send_queue_high``;
  1: the stream was free), and, over its ``wire_write`` spans, what the
  thread waited between one chunk's frame and the next of a level (the
  timer ``stream_gap``);
- ``clock`` (with ``--capture``): the program's spans are also profiler
  annotations (``<comp>:<name>``) on the profiler's own clock.  The
  benchmark lays the JSONL lines over a capture by one sync mark
  (``--sync-event``, entered when the wall clock read ``--wall-ns-at-sync``);
  this is the check of that shift: for every span in the capture, the
  annotation's start and end against the JSONL interval shifted by the
  mark's offset, as median and largest absolute difference in microseconds,
  over all spans and per name;
- ``device_busy`` (with ``--capture``): per device plane of the capture,
  the seconds it ran anything inside the window of ``bench_level``
  annotations, by ``benchmark/trace_reduce.py``'s rule (the benchmark
  reports their mean as ``busy_s``; a sharded server's chips show one by
  one here).
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fuzzyheavyhitters_tpu.obs import trace as obstrace  # noqa: E402

LEAVES = (
    "d2h", "wire_pickle", "wire_write", "peer_wait", "wire_read",
    "wire_unpickle", "h2d", "otext", "b2a", "garble", "eval",
)


def span_ms(spans: list) -> dict:
    by_key: dict = {}
    for e in spans:
        by_key.setdefault(f"{e['comp']}:{e['name']}", []).append(1e3 * e["dur"])
    return {
        k: {"n": len(v), "median": statistics.median(v),
            "mean": statistics.fmean(v), "max": max(v)}
        for k, v in sorted(by_key.items())
    }


def _union(intervals: list) -> float:
    """Seconds covered by at least one of ``(start, end)``."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def gc_ot_cover(spans: list) -> dict:
    out = {}
    for comp in sorted({e["comp"] for e in spans if e["name"] == "gc_ot"}):
        mine = [e for e in spans if e["comp"] == comp]
        shares, busy, by_leaf, n_chunks, by_chunk_leaf = [], [], {}, [], {}
        for g in (e for e in mine if e["name"] == "gc_ot" and e["dur"] > 0):
            lo, hi = g["ts"], g["ts"] + g["dur"]
            inside = [e for e in mine if e["name"] in LEAVES
                      and lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-5]
            shares.append(
                _union([(e["ts"], e["ts"] + e["dur"]) for e in inside]) / g["dur"])
            busy.append(sum(e["dur"] for e in inside) / g["dur"])
            per = {}
            for e in inside:
                per[e["name"]] = per.get(e["name"], 0.0) + 1e3 * e["dur"]
                if "chunk" in e:
                    by_chunk_leaf.setdefault(e["name"], []).append(1e3 * e["dur"])
            for name in LEAVES:
                by_leaf.setdefault(name, []).append(per.get(name, 0.0))
            chunks = {e["chunk"] for e in inside if "chunk" in e}
            if chunks:
                n_chunks.append(len(chunks))
        if shares:
            out[comp] = {
                "gc_ot_spans": len(shares),
                "share_median": statistics.median(shares),
                "share_min": min(shares),
                "busy_share_median": statistics.median(busy),
                "leaf_ms_median": {k: statistics.median(v)
                                   for k, v in by_leaf.items() if any(v)},
            }
            if n_chunks:
                out[comp].update(
                    chunked_spans=len(n_chunks),
                    chunks_median=statistics.median(n_chunks),
                    chunk_leaf_ms_median={
                        k: statistics.median(v)
                        for k, v in sorted(by_chunk_leaf.items())},
                )
    return out


def wire_oob(events: list) -> dict:
    out: dict = {}
    for e in events:
        if e.get("ph") == "i" and e.get("name") == "wire_oob":
            row = out.setdefault(
                e["comp"], {"frames": 0, "framed_bytes": 0, "wire_oob_bytes": 0})
            row["frames"] += 1
            row["framed_bytes"] += e["args"]["framed"]
            row["wire_oob_bytes"] += e["args"]["oob"]
    for row in out.values():
        row["share"] = row["wire_oob_bytes"] / row["framed_bytes"]
    return dict(sorted(out.items()))


def secure_levels(events: list) -> dict:
    """Per component, over its ``secure_level`` instants (one a secure
    level: protocol/rpc.py ``_crawl_counts_secure``): the levels, the
    most chunks a level crossed in, the most device programs of its
    ``otext`` + ``b2a`` spans (counter ``secure_chunk_programs``), the
    most of them a fetch's thread waited for and the most a stage
    awaited (counters ``secure_fetch_syncs``, ``secure_phase_waits``),
    the most device bytes its evaluator
    held in unopened chunks (gauge ``secure_t_rows_held_bytes``), the
    high word of the 64-bit OT pad index (gauge ``ot_index_high``) and
    the most bits a test compared and patterns a node had (gauges
    ``secure_string_bits``, ``child_patterns``) and every width a
    level's payload crossed in, u32 words, sorted (gauge
    ``secure_payload_words``)."""
    out: dict = {}
    for e in events:
        if e.get("ph") == "i" and e.get("name") == "secure_level":
            a = e["args"]
            row = out.setdefault(e["comp"], {
                "levels": 0, "chunks_max": 0, "secure_chunk_programs_max": 0,
                "secure_fetch_syncs_max": 0, "secure_phase_waits_max": 0,
                "t_rows_held_bytes_max": 0,
                "ot_index_high": 0, "string_bits_max": 0,
                "child_patterns_max": 0, "payload_words": []})
            row["levels"] += 1
            row["chunks_max"] = max(row["chunks_max"], a["chunks"])
            # a span log older than the counter has no ``programs``
            for arg, name in (("programs", "secure_chunk_programs"),
                              ("fetch_syncs", "secure_fetch_syncs"),
                              ("phase_waits", "secure_phase_waits")):
                row[f"{name}_max"] = max(row[f"{name}_max"], a.get(arg, 0))
            row["t_rows_held_bytes_max"] = max(
                row["t_rows_held_bytes_max"], a["t_rows_held"])
            row["ot_index_high"] = max(row["ot_index_high"], a["index_high"])
            row["string_bits_max"] = max(row["string_bits_max"], a["string_bits"])
            row["child_patterns_max"] = max(row["child_patterns_max"], a["patterns"])
            # a span log older than the gauge has no ``payload_words``
            w = a.get("payload_words")
            if w and w not in row["payload_words"]:
                row["payload_words"] = sorted(row["payload_words"] + [w])
    return dict(sorted(out.items()))


def plane_streams(events: list) -> dict:
    """Per component, over its ``plane_send`` instants (one a frame that
    went through the data plane's writer thread: protocol/rpc.py
    ``_dp_send_finish``): the frames (counter ``plane_stream_frames``),
    those handed over while the writer held another (counter
    ``plane_sends_overlapped``) and the most it held at a hand-over,
    that frame included (gauge ``plane_send_queue_high``); and over its
    ``wire_write`` spans that name a chunk, the seconds between the end
    of chunk k's and the start of chunk k+1's in one level (timer
    ``stream_gap``: the thread waited for the stage's next frame)."""
    out: dict = {}
    writes: dict = {}
    for e in events:
        if e.get("ph") == "i" and e.get("name") == "plane_send":
            row = out.setdefault(e["comp"], {
                "frames": 0, "sends_overlapped": 0, "send_queue_high": 0,
                "stream_gap_seconds": 0.0})
            row["frames"] += 1
            row["sends_overlapped"] += e["args"]["held"] > 1
            row["send_queue_high"] = max(
                row["send_queue_high"], e["args"]["held"])
        elif (e.get("ph") == "X" and e.get("name") == "wire_write"
              and "chunk" in e):
            writes.setdefault((e["comp"], e.get("level")), []).append(e)
    for (comp, _), spans in writes.items():
        spans.sort(key=lambda e: e["ts"])
        for a, b in zip(spans, spans[1:]):
            if b["chunk"] == a["chunk"] + 1 and comp in out:
                out[comp]["stream_gap_seconds"] += max(
                    0.0, b["ts"] - a["ts"] - a["dur"])
    for row in out.values():
        row["stream_gap_seconds"] = round(row["stream_gap_seconds"], 6)
    return dict(sorted(out.items()))


def clock_check(spans: list, capture: str, wall_ns_at_sync: int,
                sync_event: str) -> dict:
    from jax.profiler import ProfileData

    notes, sync = {}, []
    for plane in ProfileData.from_file(capture).planes:
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name == sync_event:
                    sync.append(ev.start_ns)
                elif ":" in name:
                    notes.setdefault(name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    if not sync:
        return {"error": f"no {sync_event!r} annotation in the capture"}
    mark_ns = min(sync)
    d_start, d_end, by_name = [], [], {}
    for v in notes.values():
        v.sort()
    for e in spans:
        found = notes.get(f"{e['comp']}:{e['name']}")
        if not found:
            continue
        # relative to the mark first: epoch nanoseconds do not fit a float
        t0 = (e["ts"] - wall_ns_at_sync / 1e9) * 1e9 + mark_ns
        t1 = t0 + e["dur"] * 1e9
        if not found[0][0] - 1e6 <= t0 <= found[-1][0] + 1e6:
            continue  # a span from before or after the capture
        i = bisect.bisect_left(found, (t0,))
        a0, a1 = min(found[max(i - 1, 0):i + 1], key=lambda a: abs(a[0] - t0))
        ds, de = abs(a0 - t0) / 1e3, abs(a1 - t1) / 1e3
        d_start.append(ds)
        d_end.append(de)
        by_name.setdefault(e["name"], []).append(max(ds, de))
    if not d_start:
        return {"error": "no span of the log lies in the capture"}
    both = d_start + d_end
    return {
        "offset_ns": int(mark_ns) - wall_ns_at_sync, "spans_compared": len(d_start),
        "annotations": sum(len(v) for v in notes.values()),
        "start_us": {"median": statistics.median(d_start), "max": max(d_start)},
        "end_us": {"median": statistics.median(d_end), "max": max(d_end)},
        "all_us": {"median": statistics.median(both), "max": max(both)},
        "by_name_us": {k: {"n": len(v), "median": statistics.median(v), "max": max(v)}
                       for k, v in sorted(by_name.items())},
    }


def device_busy(capture: str) -> dict:
    """The benchmark's own reduction (``benchmark/trace_reduce.py``, its
    public ``read_capture`` / ``reduce``) of the capture cut to one device
    plane at a time: that plane's ``busy_s``, the window the same."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import trace_reduce

    cap = trace_reduce.read_capture(capture)
    by_plane = {
        plane: trace_reduce.reduce({**cap, "devices": {plane: events}})
        for plane, events in sorted(cap["devices"].items())
    }
    ran = {plane: r for plane, r in by_plane.items() if r is not None}
    if not ran:
        return {"error": "no device plane with operations"}
    return {"window_s": next(iter(ran.values()))["window_s"],
            "busy_s": {plane: r["busy_s"] for plane, r in ran.items()}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace_dir")
    p.add_argument("--capture")
    p.add_argument("--wall-ns-at-sync", type=int)
    p.add_argument("--sync-event", default="bench_sync")
    args = p.parse_args(argv)
    events = obstrace.load_events(args.trace_dir)
    spans = [e for e in events if e.get("ph") == "X"]
    if not spans:
        print(f"no span under {args.trace_dir}", file=sys.stderr)
        return 1
    out = {"span_ms": span_ms(spans), "gc_ot_cover": gc_ot_cover(spans),
           "wire_oob": wire_oob(events),
           "secure_levels": secure_levels(events),
           "plane_streams": plane_streams(events)}
    if args.capture:
        if args.wall_ns_at_sync is None:
            p.error("--capture needs --wall-ns-at-sync")
        out["clock"] = clock_check(
            spans, args.capture, args.wall_ns_at_sync, args.sync_event)
        out["device_busy"] = device_busy(args.capture)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
