"""From a profiler capture to device busy and idle time, the operations that
took most of it, and what the host was doing in the longest idle gaps.

Input: the ``.xplane.pb`` that ``jax.profiler`` wrote (read with
``jax.profiler.ProfileData``, nothing else), and optionally the program's
spans as ``(name, start_ns, end_ns)`` on the profiler's clock.

What is read from the capture:

- device planes: planes named ``/device:TPU:<n>``; on each, the line
  ``XLA Ops`` holds one event per operation the chip ran, nested where an
  operation (a ``while``, a fusion's region) contains others;
- the window: the span from the first ``bench_level`` host annotation's
  start to the last one's end (the harness wraps every level of the crawl
  in one); with none in the capture, the span of the device events;
- busy: the union of the operation intervals, clipped to the window, on each
  device plane that ran anything; ``busy_s`` is the mean over those planes;
- ``device_ops``: self time by operation name (an operation's time less its
  nested children's), the ten largest;
- ``idle_gaps``: the complement of busy in the window on the first device
  plane, each gap given to the innermost program span open at its middle
  (else ``bench_level``, else ``between levels``), summed by name, the ten
  largest.

Times in the capture are nanoseconds from the capture's start.  The harness
puts a ``bench_sync`` annotation into it and notes the wall clock beside it,
which ``sync_offset_ns`` turns into the offset of wall-clock spans.
"""

from __future__ import annotations

import heapq
import json
import time

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
LEVEL_EVENT = "bench_level"
SYNC_EVENT = "bench_sync"


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def _self_times(events: list) -> dict:
    """{name: self ns}: each event's duration less its direct children's.
    ``events`` are (start, end, name) of ONE line, nested or disjoint."""
    out: dict = {}
    stack: list = []  # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -(ev[1] - ev[0]))):
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def _named(plane, name: str) -> list:
    """(start_ns, end_ns) of every event called ``name`` on any line."""
    return [
        (ev.start_ns, ev.start_ns + ev.duration_ns)
        for line in plane.lines for ev in line.events
        if ev.name == name or ev.name.startswith(name + "#")  # "name#k=v#" carries its stats
    ]


def op_name(event_name: str) -> str:
    """An operation's name as the trace gives it, without the HLO text the
    TPU plane appends (``%fusion.2 = u32[...] fusion(...)`` -> ``fusion.2``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:96]


def read_capture(path: str) -> dict:
    """The capture's device-op events, level annotations and sync mark."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, levels, sync, seen = {}, [], [], []
    for plane in data.planes:
        seen.append(plane.name)
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (ev.start_ns, ev.start_ns + ev.duration_ns, op_name(ev.name))
                        for ev in line.events
                    ]
        else:
            levels += _named(plane, LEVEL_EVENT)
            sync += _named(plane, SYNC_EVENT)
    return {"devices": devices, "levels": sorted(levels), "sync": sorted(sync),
            "planes": seen}


def sync_offset_ns(capture: dict, wall_ns_at_sync: int):
    """What to add to a wall-clock nanosecond to get the capture's clock."""
    if not capture["sync"]:
        return None
    return capture["sync"][0][0] - wall_ns_at_sync


def _owners(mids: list, spans: list) -> list:
    """For each of the ascending ``mids`` the name of the innermost span open
    there, or None: the shortest of the ``(name, start, end)`` that hold it,
    both ends counted in, and of several that short the first in ``spans``.
    One sweep in time order: a span joins the heap when a middle has reached
    its start and leaves from the top once a middle has passed its end (one
    that has ended deeper down is never the answer before it gets there)."""
    # (start, then the heap's key: length, place in spans; end, name)
    waiting = sorted((a, b - a, i, b, name) for i, (name, a, b) in enumerate(spans))
    open_, nxt, out = [], 0, []
    for mid in mids:
        while nxt < len(waiting) and waiting[nxt][0] <= mid:
            heapq.heappush(open_, waiting[nxt][1:])
            nxt += 1
        while open_ and open_[0][2] < mid:
            heapq.heappop(open_)
        out.append(open_[0][3] if open_ else None)
    return out


def reduce(capture: dict, host_spans: list = ()) -> dict | None:
    """busy_s, window_s, device_ops and idle_gaps of a capture, or None
    where it holds no device plane with operations."""
    devices = {k: v for k, v in capture["devices"].items() if v}
    if not devices:
        return None
    if capture["levels"]:
        lo, hi = capture["levels"][0][0], capture["levels"][-1][1]
    else:
        lo = min(ev[0] for evs in devices.values() for ev in evs)
        hi = max(ev[1] for evs in devices.values() for ev in evs)
    busy, ops = {}, {}
    for name, evs in sorted(devices.items()):
        inside = [ev for ev in evs if ev[1] > lo and ev[0] < hi]
        busy[name] = _clip(_union([[s, e] for s, e, _ in inside]), lo, hi)
        for op, ns in _self_times(inside).items():
            ops[op] = ops.get(op, 0.0) + ns
    ran = [b for b in busy.values() if b]
    if not ran:
        return None
    busy_ns = sum(sum(e - s for s, e in b) for b in ran) / len(ran)

    first = next(b for _, b in sorted(busy.items()) if b)
    edges = [lo] + [t for iv in first for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    t_search = time.perf_counter()
    mids = [(s + e) / 2 for s, e in gaps]
    # a span that ends before the window or starts after it holds no gap's
    # middle: a traced run's log is the whole process's, the capture 4 s of it
    owners = _owners(mids, [sp for sp in host_spans if sp[2] >= lo and sp[1] <= hi])
    by_name: dict = {}
    for (s, e), mid, owner in zip(gaps, mids, owners):
        if owner is None:
            in_level = any(a <= mid <= b for a, b in capture["levels"])
            owner = LEVEL_EVENT if in_level else "between levels"
        by_name[owner] = by_name.get(owner, 0.0) + (e - s)
    search_s = time.perf_counter() - t_search

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_planes": sorted(devices),
        "levels_in_capture": len(capture["levels"]),
        "device_ops": top(ops),
        "idle_gaps": top(by_name),
        "gap_search_s": search_s,
    }


def program_spans(jsonl_lines, offset_ns: int, names: set) -> list:
    """The program's own spans (``FHH_TRACE_DIR`` JSON lines: ``ph`` X,
    ``name``, ``comp``, ``ts`` and ``dur`` in wall-clock seconds) as
    (``comp:name``, start_ns, end_ns) on the capture's clock."""
    out = []
    for line in jsonl_lines:
        try:
            ev = json.loads(line)
        except ValueError:
            continue  # a torn last line
        if ev.get("ph") == "X" and ev.get("name") in names:
            t0 = ev["ts"] * 1e9 + offset_ns
            out.append((f"{ev['comp']}:{ev['name']}", t0, t0 + ev["dur"] * 1e9))
    return out
