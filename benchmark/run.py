"""One run of one benchmark cell:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chip.  One process:
set the allocator's thresholds where the configuration states them
(``process.malloc``); fail unless JAX sees a TPU (no fallback); draw the
clients' points from the seed; generate both servers' keys on the device;
bring the collector pair and the leader up over localhost sockets; upload the
keys (timed: the ingest reading); warm up by one crawl through the widening levels and the servers'
own warm-up of the leaf level's programs; then the window: a new crawl from
level 0, level after level until ``--seconds`` have passed.  Once the window
has closed the crawl in flight goes on, untimed, to its leaf level where that
fits the mix's ``tail`` (a mix of whole crawls, ``window.close_on: "crawl"``,
closes its window where the crawl in flight ends instead, and has no tail);
then every level's frontier and counts, the tail's too, are compared with the
configuration's plain reference over the same points.  The last line of
standard output is the result object; see README.md beside this file.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import lane  # noqa: E402
import manifest  # noqa: E402
import readers  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402


def out_dir(cell_name: str) -> str:
    """Where a traced run keeps its capture and span log; git-ignored."""
    return os.path.join(manifest.ROOT, ".bench_out", cell_name)


# glibc's mallopt parameters, by the names a configuration's ``process.malloc``
# group uses
_MALLOPT = {"trim_threshold": -1, "top_pad": -2, "mmap_threshold": -3}


def pin_allocator(spec: dict | None) -> dict:
    """Set glibc malloc's thresholds as the configuration's ``process.malloc``
    group states them; nothing where it states none.  Left alone, malloc
    moves its mmap and trim thresholds with the process's history, so whether
    a request of 128 KiB to 32 MiB gets new pages (1 ms a MB at their first
    touch on the chip's host) or its own heap differs from process to
    process: the upload and the trusted level run at one of two speeds
    (PERF.md section 6, PRs 29 and 42).  Setting any of them ends the moving.
    Called once the cell is known, before the run allocates anything in bulk
    (points, keys, frames); a threshold that cannot be set fails the run."""
    import ctypes

    done = {}
    for name, value in sorted((spec or {}).items()):
        if name not in _MALLOPT:
            continue  # prose beside the numbers ("what")
        if ctypes.CDLL("libc.so.6").mallopt(_MALLOPT[name], int(value)) != 1:
            raise RuntimeError(f"mallopt({name}, {value}) was refused")
        done[name] = int(value)
    return done


class NoChip(RuntimeError):
    """JAX found no TPU, too few chips, or not the chip's engines."""


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it.  Fails unless it is a TPU the table of
    peaks knows, with the chips the cell asks for, and every stage resolved
    to the chip's engine.  The rehearsal test patches this function; the
    program is never steered off the chip from here."""
    import jax
    from fuzzyheavyhitters_tpu.protocol import rpc

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)
    if devs[0].device_kind not in peaks:
        raise NoChip(f"no peaks for device kind {devs[0].device_kind!r} in benchmark/peaks.json")
    tags = rpc.engine_tags()
    want = dict(platform="tpu", keygen="pallas", expand="pallas", ot2s="pallas", gc="pallas")
    if tags != want:
        raise NoChip(f"not the chip's engines: {tags}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak_bytes():
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return None  # XLA:CPU keeps no memory stats
    return max(int(ms["peak_bytes_in_use"]) for ms in stats)


class Capture:
    """A profiler capture inside the window (``--trace 1`` only), started and
    stopped where the window itself may close: at level boundaries, or where
    a crawl ends under ``window.close_on: "crawl"``, so that such a mix's
    capture holds whole crawls and no phase of one."""

    def __init__(self, out_dir: str, start_after_s: float, capture_s: float):
        self.dir, self.start_after_s, self.capture_s = out_dir, start_after_s, capture_s
        self.state, self.t_window, self.t_on = "waiting", None, None
        self.wall_ns_at_sync = None

    def at_boundary(self, now: float) -> None:
        import jax

        if self.state == "waiting" and now - self.t_window >= self.start_after_s:
            # the Python tracer records every call of the leader's loop and
            # halves the rate it is there to explain: device and TraceMe
            # events only
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.wall_ns_at_sync = time.time_ns()
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC_EVENT):
                time.sleep(0.001)
            self.state, self.t_on = "on", time.perf_counter()
        elif self.state == "on" and now - self.t_on >= self.capture_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "done"

    def reduce(self, fhh_dir: str, span_names) -> dict | None:
        files = sorted(glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if self.state != "done" or not files:
            return None
        t_read = time.perf_counter()
        cap = trace_reduce.read_capture(files[-1])
        spans = []
        offset = trace_reduce.sync_offset_ns(cap, self.wall_ns_at_sync)
        if offset is not None:
            for path in sorted(glob.glob(os.path.join(fhh_dir, "fhh_trace_*.jsonl*"))):
                with open(path, encoding="utf-8") as f:
                    spans += trace_reduce.program_spans(f, offset, set(span_names))
        t_reduce = time.perf_counter()
        out = trace_reduce.reduce(cap, spans)
        log(phase="capture", file=os.path.relpath(files[-1], manifest.ROOT),
            wall_ns_at_sync=self.wall_ns_at_sync, program_spans=len(spans),
            planes=cap["planes"], read_s=t_reduce - t_read,
            reduce_s=time.perf_counter() - t_reduce,
            **({"note": "no device plane with operations"} if out is None else
               {k: out[k] for k in ("busy_s", "window_s", "levels_in_capture", "gap_search_s")}))
        return out


def _warm_rule(lead, plan):
    def after(rec) -> bool:
        recs = lead.records
        if len(recs) >= plan.max_levels:
            return True
        if len(recs) < max(plan.min_levels, plan.steady_levels + 1):
            return False
        return len({r.bucket for r in recs[-(plan.steady_levels + 1):]}) == 1
    return after


async def _window(lead, n, cfg, plan, seconds, capture, close):
    """The measured window and, after it, the untimed tail.  Returns (the
    window's levels, its seconds, the tail's levels, how it closed).

    ``plan.close_on`` "level": the clock stops, and ``close`` takes its
    readings, when the level in flight at the deadline has finished.  The
    crawl in flight then goes on to its leaf level if the levels left, at the
    median of the last fifty, fit ``plan.tail_max_s``: so a run compares the
    final hitter set although no window holds a whole crawl.

    ``plan.close_on`` "crawl", for a mix whose crawls are short and whose
    levels cost from one to a hundred: no level stops anything; the crawl in
    flight at the deadline runs to its own end, none is started after it, and
    the clock stops when it has returned, so the window is whole crawls, each
    with its hand-over, over their own seconds, and the reading has no phase
    of the crawl in it.  A crawl that cannot end inside the run's time limit
    is mis-paired with such a mix and the limit refuses the run.  How it
    closed is a dict for the ``window`` log line (empty under "level")."""
    lead.records, lead.crawl = [], 0
    radix = max(1, int(cfg.crawl_radix_bits))
    by_crawl = plan.close_on == "crawl"
    t_start = time.perf_counter()
    deadline = t_start + seconds
    closed_at = None  # levels on record when the window closed
    whole, t_end = 0, None  # under "crawl": crawls that ran to their end, and when the last had
    if capture is not None:
        capture.t_window = t_start

    def after(rec) -> bool:
        nonlocal closed_at
        if by_crawl or closed_at is not None:
            return False  # a whole crawl, or the tail, runs to the crawl's own end
        if capture is not None:
            capture.at_boundary(rec.t1)
        if time.perf_counter() < deadline:
            return False
        closed_at = len(lead.records)
        close()
        left = -(-(cfg.data_len - rec.level - radix) // radix)
        recent = statistics.median(lv.seconds for lv in lead.records[-50:])
        return not 0 < left * recent <= plan.tail_max_s

    while True:
        try:
            ended = await lane.crawl(lead, n, after)
        except Exception as e:  # the level is on record with its error; the run ends
            ended = False
            log(phase="window", error=f"{type(e).__name__}: {e}")
            break
        if by_crawl and ended:
            whole, t_end = whole + 1, time.perf_counter()
        if (not ended or closed_at is not None or not plan.restart_when_crawl_ends
                or time.perf_counter() >= deadline):
            break
        if by_crawl and capture is not None:
            capture.at_boundary(t_end)
    if closed_at is None:
        closed_at = len(lead.records)
        close()
    levels, tail = lead.records[:closed_at], lead.records[closed_at:]
    if not (by_crawl and ended):
        t_end = levels[-1].t1 if levels else time.perf_counter()
    # "level" under such a mix: the crawl in flight raised, and its level is the last
    how = {"closed_on": "crawl" if ended else "level", "whole_crawls": whole,
           "overrun_s": t_end - deadline}
    return levels, t_end - t_start, tail, how if by_crawl else {}


def _counters(servers: dict, names: dict) -> dict:
    return {
        reg: {c: servers[reg].obs.counter_value(c) for c in cs}
        for reg, cs in names.items() if reg in servers
    }


def compare(cell, ref, points, cfg, n, levels, tail, counters) -> tuple:
    """(numbers compared, levels that failed).  Each number has the limit 0:
    the configuration guarantees exact counts and an exact hitter set, and
    names the exchange its lane runs.  ``levels`` are the window's and
    ``tail`` those run after it; the counters are the window's."""
    in_window, levels = len([lv for lv in levels if lv.error is None]), levels + tail
    thresh = max(1, int(cfg.threshold * n))  # the leader's own rule
    radix = max(1, int(cfg.crawl_radix_bits))
    depth_of = lambda lv: min(lv.level + radix, cfg.data_len)
    done = [lv for lv in levels if lv.error is None]
    want = ref.frontiers(points, cfg.ball_size, thresh, max((depth_of(lv) for lv in done), default=0))
    held = [(lv, ref.crawl_frontier(lv.paths, lv.counts)) for lv in done]
    differing = [(lv, got) for lv, got in held if got != want[depth_of(lv)]]
    if differing:
        lv, got = differing[0]
        exp = want[depth_of(lv)]
        print(f"first differing level: crawl {lv.crawl} level {lv.level}: held {len(got)} "
              f"prefixes, reference {len(exp)}; in one only {sorted(set(got) ^ set(exp), key=str)[:4]}; "
              f"counts differ at {[q for q in got if q in exp and got[q] != exp[q]][:4]}",
              file=sys.stderr)
    mismatches = []
    for ev in cell.config.get("lane_evidence", []):
        for reg, vals in sorted(counters.items()):
            seen = vals.get(ev["counter"], 0)
            ok = seen == 0 if ev["per_level"] == "zero" else seen >= max(1, in_window)
            if not ok:
                mismatches.append(f"{reg}:{ev['counter']}={seen} (want {ev['per_level']} per level)")
    raised = [lv for lv in levels if lv.error is not None]
    numbers = [
        ("levels_differing", len(differing), 0,
         f"of {len(done)} levels compared, {len(tail)} of them after the window, the leaf level "
         + ("among them" if any(depth_of(lv) == cfg.data_len for lv in done) else "NOT among them")),
        ("levels_raised", len(raised), 0, "; ".join(lv.error for lv in raised[:2])),
        ("lane_evidence_mismatches", len(mismatches), 0, "; ".join(mismatches)),
    ]
    return numbers, len(differing) + len(raised)


def e2e_readings(n, cfg, levels, window_s, upload_s, setup_s) -> dict:
    """The three readings the harness takes on the host's clock."""
    done = [lv for lv in levels if lv.error is None]
    return {
        "crawl_clients_per_s": n * len(done) / cfg.data_len / window_s if window_s else 0.0,
        "ingest_clients_per_s": n / upload_s,
        "setup_s": setup_s,
    }


def end_to_end(cell, have: dict) -> dict:
    out = {}
    for m in cell.end_to_end:
        # ``<reading>.<tag>`` is that reading under a bound of its own, for
        # the cells its ``workloads`` key lists
        reading = m["name"] if m["name"] in have else m["name"].rsplit(".", 1)[0]
        if reading not in have:
            raise manifest.ManifestError(f"end-to-end metric {m['name']!r}: the harness takes no such reading")
        out[m["name"]] = {"value": have[reading], "unit": m["unit"]}
    return out


async def run_cell(cell, seed: int, seconds: float, trace: bool, *,
                   config_overrides: dict | None = None, tamper_keys=None) -> dict:
    """Set-up, window, tail, comparison.  Returns the result object.
    ``config_overrides`` and ``tamper_keys`` are the controls' way in
    (``control.py``): the one changes fields of the program's Config, the
    other the key batches on their way to the upload, and neither anything
    of what is compared."""
    import jax
    import numpy as np

    device = require_tpu(cell.chips)

    from fuzzyheavyhitters_tpu.ops import ibdcf, prg
    from fuzzyheavyhitters_tpu.utils import compile_cache
    from fuzzyheavyhitters_tpu.utils.config import Config

    if compile_cache.enable() is None:
        raise RuntimeError("the persistent compile cache could not be set up")
    compile_cache.backend_compiles()  # registers the listener: set-up's compiles count
    if jax.default_backend() != "cpu":
        prg.CHACHA_UNROLL = True  # as bin/server does on an accelerator
    cfg = Config(**{**cell.config["config"], **(config_overrides or {})})
    n = int(cell.config["clients"])
    plan = traffic.plan(cell.mix)
    ref = manifest.reference(cell.config)
    span_names = readers.span_names(cell.per_layer)
    counter_names = readers.counter_names(cell.per_layer)
    for ev in cell.config.get("lane_evidence", []):
        for reg in ("server0", "server1"):
            counter_names[reg] = sorted({*counter_names.get(reg, []), ev["counter"]})
    t_imports = time.perf_counter()

    rng = np.random.default_rng(seed)
    points = traffic.client_points(cell.config, n, rng)
    t_points = time.perf_counter()
    k0, k1 = ibdcf.gen_l_inf_ball(points, cfg.ball_size, rng, engine=ibdcf.best_engine())
    jax.block_until_ready((k0, k1))
    t_keygen = time.perf_counter()
    # the leader's copy of the keys leaves the device (the fetch the upload
    # would make anyway): 16 GB holds the servers' two key planes, not four
    k0, k1 = jax.device_get((k0, k1))
    t_fetch = time.perf_counter()
    if tamper_keys is not None:
        k0, k1 = tamper_keys(k0), tamper_keys(k1)

    capture = None
    fhh_dir = os.path.join(out_dir(cell.name), "spans")
    if trace:
        capture = Capture(os.path.join(out_dir(cell.name), "profile"),
                          plan.trace_start_after_s, plan.trace_capture_s)

    async with lane.pair(cfg, span_names) as (lead, s0, s1):
        servers = {"server0": s0, "server1": s1}
        t = time.perf_counter()
        await lead.upload_keys(k0, k1)
        upload_s = time.perf_counter() - t
        del k0, k1

        t = time.perf_counter()
        await lane.crawl(lead, n, _warm_rule(lead, plan))
        warm = lead.records
        warm_s = time.perf_counter() - t
        # the leaf level (its own field, no children) is met only at a
        # crawl's end: the servers' own warm-up loads its programs, at the
        # bucket the frontier has settled in
        t = time.perf_counter()
        await lead.warmup(f_buckets=[warm[-1].bucket])
        leaf_warm_s = time.perf_counter() - t
        key_plane_bytes = sum(int(leaf.nbytes) for s in (s0, s1) for leaf in s.keys)
        gc.collect()

        compiles0 = compile_cache.backend_compiles()
        compile_s0 = compile_cache.backend_compile_seconds()
        counters0 = _counters(servers, counter_names)
        setup_s = time.perf_counter() - T_PROCESS
        usage0 = resource.getrusage(resource.RUSAGE_SELF)
        at_close = {}

        def close() -> None:
            if capture is not None:
                capture.stop()
            at_close.update(
                usage=resource.getrusage(resource.RUSAGE_SELF),
                counters=_counters(servers, counter_names),
                compiles=compile_cache.backend_compiles(),
                compile_s=compile_cache.backend_compile_seconds(),
                peak=memory_peak_bytes())

        levels, window_s, tail, how_closed = await _window(
            lead, n, cfg, plan, seconds, capture, close)
        tail_s = tail[-1].t1 - tail[0].t0 if tail else 0.0
        tail_compiles = compile_cache.backend_compiles() - at_close["compiles"]
        engines = s0.engine_tags()
    gc.collect()  # the servers' device state goes with them

    usage1, peak = at_close["usage"], at_close["peak"]
    window_compiles = at_close["compiles"] - compiles0
    window_compile_s = at_close["compile_s"] - compile_s0
    counters = {reg: {c: at_close["counters"][reg][c] - v for c, v in cs.items()}
                for reg, cs in counters0.items()}
    ok = [lv for lv in levels if lv.error is None]
    done = [lv.seconds for lv in ok]
    log(phase="setup", seed=seed, clients=n, engines=engines, setup_s=setup_s,
        imports_s=t_imports - T_PROCESS, points_s=t_points - t_imports,
        keygen_s=t_keygen - t_points, key_fetch_s=t_fetch - t_keygen, upload_s=upload_s,
        warmup_s=warm_s, warmup_levels=len(warm), leaf_warmup_s=leaf_warm_s,
        warmup_buckets=sorted({lv.bucket for lv in warm}),
        setup_compiles=compiles0, setup_compile_s=compile_s0,
        compile_cache_dir=compile_cache.enable(), key_plane_bytes=key_plane_bytes)
    log(phase="window", seconds=window_s, levels=len(levels), **how_closed,
        crawls=len({lv.crawl for lv in levels}),
        first_level=levels[0].level if levels else None,
        last_level=levels[-1].level if levels else None,
        buckets=sorted({lv.bucket for lv in levels}),
        level_ms_median=1e3 * statistics.median(done) if done else None,
        level_ms_max=1e3 * max(done) if done else None, level_samples=len(done),
        compiles=window_compiles, compile_s=window_compile_s,
        tail_levels=len(tail), tail_s=tail_s, tail_compiles=tail_compiles,
        tail_last_level=tail[-1].level if tail else None,
        # what the host did to the process meanwhile: a noisy neighbour shows
        # as involuntary switches, allocator churn as page faults
        host={k: getattr(usage1, "ru_" + k) - getattr(usage0, "ru_" + k)
              for k in ("utime", "stime", "minflt", "majflt", "nvcsw", "nivcsw")},
        span_ms_median={
            f"{reg}:{name}": round(1e3 * statistics.median(lv.spans[reg][name] for lv in ok), 2)
            for reg in (ok[0].spans if ok else {}) for name in ok[0].spans[reg]},
        level_ms=[round(1e3 * lv.seconds, 1) for lv in levels],
        bucket_by_level=[lv.bucket for lv in levels])

    t = time.perf_counter()
    numbers, failed = compare(cell, ref, points, cfg, n, levels, tail, counters)
    correct = bool(levels) and all(v <= limit for _, v, limit, _ in numbers)
    log(phase="compare", seconds=time.perf_counter() - t,
        numbers={name: {"value": v, "limit": limit} for name, v, limit, _ in numbers})

    have = e2e_readings(n, cfg, levels, window_s, upload_s, setup_s)
    e2e = end_to_end(cell, have)
    log(phase="readings", **have)  # all three, whichever of them the cell reports end to end
    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": len(levels) + len(tail), "failed": failed}
    if not trace:
        result["metrics"] = e2e
    else:
        reduced = capture.reduce(fhh_dir, span_names)
        run = readers.Run(
            levels=ok,
            counters=counters, trace=reduced,
            readings={
                "setup_compile_seconds": compile_s0, "window_compiles": window_compiles,
                "memory_peak_bytes": peak, "key_plane_bytes": key_plane_bytes,
                "upload_seconds": upload_s,
                # a cell whose rate is too unsteady on the host's clock to be
                # held to a bound reports it per layer
                "crawl_clients_per_s": have["crawl_clients_per_s"] or None,
            },
        )
        metrics = {}
        for spec in cell.per_layer:
            v = readers.read(spec, run)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        result["metrics"] = metrics
        if reduced is not None:
            dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            result["breakdown"] = {k: reduced[k] for k in ("device_ops", "idle_gaps")}
    result["device"] = dev
    for name, v, limit, note in numbers:
        print(f"compare {name}={v} limit={limit} {note}".rstrip(), file=sys.stderr)
    print(f"correct={correct} attempted={len(levels) + len(tail)} failed={failed}",
          file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        p.error("--seed is a whole number >= 0 and --seconds is > 0")
    cell = manifest.cell(args.workload)
    log(phase="process", malloc=pin_allocator(cell.config.get("process", {}).get("malloc")))
    if args.trace:
        # the program writes its spans with wall-clock times where
        # FHH_TRACE_DIR says (obs/trace.py, read at its first use); the
        # idle gaps of the capture are attributed from them
        out = out_dir(cell.name)
        shutil.rmtree(out, ignore_errors=True)
        os.makedirs(os.path.join(out, "spans"))
        os.environ["FHH_TRACE_DIR"] = os.path.join(out, "spans")
    try:
        result = asyncio.run(run_cell(cell, args.seed, args.seconds, bool(args.trace)))
    except NoChip as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
