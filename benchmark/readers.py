"""The per-layer metric readers: a small fixed set, chosen by the ``reader`` key
of a metric's data file (``benchmark/metrics/<name>.json``) and steered by its
``args``.  Each takes the run's record and returns a number, or None where it
finds nothing to read; the harness then leaves the metric out of the line.

The record (``Run``) holds what the harness took itself: per level of the
window the leader's wall seconds and each server's seconds in each program
span; the servers' counters over the window; plain readings (seconds of
set-up phases, compile counts, bytes); and the reduction of the capture.
"""

from __future__ import annotations

import dataclasses
import statistics


@dataclasses.dataclass
class Level:
    """One level of a crawl, as the harness saw it."""

    crawl: int
    level: int
    t0: float = 0.0            # host clock (perf_counter) at the first verb
    t1: float = 0.0            # ... with the pruned frontier held
    bucket: int = 0            # frontier bucket after the prune
    spans: dict = dataclasses.field(default_factory=dict)  # {registry: {span: seconds}}
    paths: object = None       # the frontier held after the level (None: died out)
    counts: object = None
    error: str | None = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    levels: list                                  # the window's Levels, those that raised left out
    counters: dict                                # {registry: {counter: delta over the window}}
    readings: dict                                # plain numbers, by key
    trace: dict | None = None                     # trace_reduce.reduce()'s result


_REDUCE = {"mean": statistics.fmean, "median": statistics.median, "max": max, "sum": sum}


def _per_level(run: Run, spans: list, servers: str) -> list:
    """For each level: the sum of ``spans`` in each server registry,
    reduced over the servers (``mean`` or ``max``)."""
    out = []
    for lv in run.levels:
        per_server = [
            sum(reg.get(s, 0.0) for s in spans)
            for name, reg in sorted(lv.spans.items()) if name.startswith("server")
        ]
        if per_server:
            out.append(_REDUCE[servers](per_server))
    return out


def span_ms_per_level(run: Run, args: dict):
    vals = _per_level(run, args["spans"], args.get("servers", "mean"))
    if not vals or not any(vals):
        return None  # the span was never entered in this cell
    return 1e3 * _REDUCE[args.get("levels", "mean")](vals)


def level_minus_spans_ms(run: Run, args: dict):
    inner = _per_level(run, args["spans"], args.get("servers", "max"))
    if not inner:
        return None
    vals = [lv.seconds - s for lv, s in zip(run.levels, inner)]
    return 1e3 * _REDUCE[args.get("levels", "median")](vals)


def level_percentile_ms(run: Run, args: dict):
    secs = [lv.seconds for lv in run.levels]
    if len(secs) < 2:
        return None
    return 1e3 * statistics.quantiles(secs, n=100, method="inclusive")[int(args["q"]) - 1]


def counter_per_level(run: Run, args: dict):
    total, found = 0, False
    for reg in args["registries"]:
        for c in args["counters"]:
            if c in run.counters.get(reg, {}):
                found = True
                total += run.counters[reg][c]
    if not found or not run.levels:
        return None
    return total / len(run.levels)


def reading(run: Run, args: dict):
    v = run.readings.get(args["key"])
    return None if v is None else v * args.get("scale", 1)


def ratio(run: Run, args: dict):
    num, den = run.readings.get(args["num"]), run.readings.get(args["den"])
    if num is None or not den:
        return None
    return args.get("scale", 1) * num / den


def trace_idle_share(run: Run, args: dict):
    t = run.trace
    if not t or not t.get("busy_s") or not t.get("window_s"):
        return None  # no capture, or no device plane in it
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


READERS = {
    f.__name__: f
    for f in (span_ms_per_level, level_minus_spans_ms, level_percentile_ms,
              counter_per_level, reading, ratio, trace_idle_share)
}


def read(spec: dict, run: Run):
    try:
        reader = READERS[spec["reader"]]
    except KeyError:
        raise ValueError(
            f"metric {spec['name']}: no reader {spec['reader']!r}, have {sorted(READERS)}"
        ) from None
    return reader(run, spec.get("args", {}))


def span_names(specs: list) -> list:
    """Every program span the cell's metric files read."""
    return sorted({s for spec in specs for s in spec.get("args", {}).get("spans", [])})


def counter_names(specs: list) -> dict:
    """{registry: [counters]} the cell's metric files read."""
    out: dict = {}
    for spec in specs:
        a = spec.get("args", {})
        for reg in a.get("registries", []):
            out.setdefault(reg, set()).update(a.get("counters", []))
    return {k: sorted(v) for k, v in out.items()}
