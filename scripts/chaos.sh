#!/usr/bin/env bash
# Chaos-proxy recovery suite with a machine-readable artifact.
#
# Usage: scripts/chaos.sh [artifact.json]
#   - runs the full fault-injection/recovery surface on the CPU backend:
#     the socket-path suite (tests/test_resilience.py — control/data
#     plane chaos, sketch recovery via the challenge ratchet, sharded
#     mid-level retry, the FHH_MESH_FAULTS grammar),
#     the streaming-ingest suite (tests/test_ingest.py — admission
#     control, flood/slowclient chaos, kill-mid-window recovery), AND
#     the multi-chip suite (tests/test_multichip.py — sharded-vs-single
#     bit-identity, device-loss re-shard recovery), AND the multi-tenant
#     suite (tests/test_sessions.py — N=4 concurrent collections
#     bit-identical to solo, per-session gate isolation, the
#     flood-A + kill/restart-s1 tenant-isolation leg), AND the
#     malicious-sketch suite (tests/test_sketch_shard.py — the sharded
#     verify bit-identity matrix and the WINDOWED-MALICIOUS recovery
#     leg: kill/restart mid-window, the re-run replaying the identical
#     committed challenge root), AND the collector-fleet suite
#     (tests/test_fleet.py — live session migration, whole-host
#     host:kill failover: tenant A floods while the whole pair dies
#     mid-crawl of tenant B, B resumes bit-identical on the survivor),
#     INCLUDING the slow-marked multi-fault storm tier-1 skips
#   - writes a JSON artifact ({passed, failed, duration_s, tests}) to $1
#     (default: chaos_report.json); exits non-zero on any failure
#
# The fixed fault schedules live in the tests themselves (deterministic
# frame-ordinal / level triggers — see resilience/chaos.py for the
# FHH_FAULTS and FHH_MESH_FAULTS grammars); this script is the
# standalone/CI entry point, the same suites run (minus slow) inside
# tier-1.
set -uo pipefail
cd "$(dirname "$0")/.."

artifact="${1:-chaos_report.json}"
report="$(mktemp)"

JAX_PLATFORMS=cpu python -m pytest \
    tests/test_resilience.py tests/test_ingest.py \
    tests/test_multichip.py tests/test_sessions.py tests/test_sketch_shard.py \
    tests/test_fleet.py tests/test_radix.py \
    -m "" -q \
    -p no:cacheprovider --junitxml="$report"
rc=$?

# fhh-race runtime sanitizer stage: re-run one trusted + one secure e2e
# chaos recovery scenario with FHH_DEBUG_GUARDS=1, so every guarded-
# attribute access on the servers asserts its owning lock mid-fault —
# the dynamic validation of the static guard map under real chaos
# (utils/guards.py; the scenarios flow through the socket verb path, so
# the lock discipline is exactly the production one)
JAX_PLATFORMS=cpu FHH_DEBUG_GUARDS=1 python -m pytest \
    "tests/test_resilience.py::test_e2e_chaos_recovery_bit_identical" \
    "tests/test_sessions.py::test_tenant_isolation_flood_and_kill_restart_mid_crawl" \
    "tests/test_fleet.py::test_host_kill_mid_crawl_under_flood_tenant_b_bit_identical" \
    -q -p no:cacheprovider
guards_rc=$?
if [ $guards_rc -ne 0 ]; then
    echo "chaos suite: FHH_DEBUG_GUARDS sanitizer stage FAILED" >&2
    rc=1
fi

# fhh-taint runtime sanitizer stage: the same trusted + secure e2e
# recovery legs with FHH_DEBUG_TAINT=1, so the session/OT secret
# buffers register at their constructors and every obs sink boundary
# (log emit, metrics render, trace record, alert fire, report build)
# asserts no registered byte image crosses — the dynamic validation of
# the static secret-flow pass under real chaos (utils/taint_guard.py)
JAX_PLATFORMS=cpu FHH_DEBUG_TAINT=1 python -m pytest \
    "tests/test_resilience.py::test_e2e_chaos_recovery_bit_identical" \
    "tests/test_sessions.py::test_tenant_isolation_flood_and_kill_restart_mid_crawl" \
    "tests/test_fleet.py::test_host_kill_mid_crawl_under_flood_tenant_b_bit_identical" \
    -q -p no:cacheprovider
taint_rc=$?
if [ $taint_rc -ne 0 ]; then
    echo "chaos suite: FHH_DEBUG_TAINT sanitizer stage FAILED" >&2
    rc=1
fi

# fhh-trace stage: re-run one e2e chaos-recovery leg with distributed
# tracing ON, then merge + structurally validate the trace — a recovery
# wave (reconnect replays, plane resets, level re-runs) must still
# produce a parent-consistent single-trace timeline (obs/trace.py)
trace_dir="$(mktemp -d)"
JAX_PLATFORMS=cpu FHH_TRACE_DIR="$trace_dir" python -m pytest \
    "tests/test_resilience.py::test_e2e_chaos_recovery_bit_identical" \
    -q -p no:cacheprovider
trace_rc=$?
if [ $trace_rc -eq 0 ]; then
    python -m fuzzyheavyhitters_tpu.obs.trace merge \
        -d "$trace_dir" -o "$trace_dir/trace.json" > /dev/null \
        || trace_rc=$?
fi
if [ $trace_rc -ne 0 ]; then
    echo "chaos suite: traced e2e leg / trace validation FAILED" >&2
    rc=1
fi
rm -rf "$trace_dir"

python - "$report" "$artifact" "$guards_rc" "$trace_rc" "$taint_rc" <<'EOF'
import json, sys
import xml.etree.ElementTree as ET

suite = ET.parse(sys.argv[1]).getroot().find("testsuite")
tests = [
    {
        "name": f"{c.get('classname')}::{c.get('name')}",
        "time_s": float(c.get("time", 0)),
        "outcome": (
            "failed" if c.find("failure") is not None or c.find("error") is not None
            else "skipped" if c.find("skipped") is not None else "passed"
        ),
    }
    for c in suite.iter("testcase")
]
doc = {
    "schema": "fhh-chaos-report/1",
    "passed": sum(t["outcome"] == "passed" for t in tests),
    "failed": sum(t["outcome"] == "failed" for t in tests),
    "skipped": sum(t["outcome"] == "skipped" for t in tests),
    "duration_s": round(float(suite.get("time", 0)), 2),
    "debug_guards": "passed" if sys.argv[3] == "0" else "failed",
    "trace_validation": "passed" if sys.argv[4] == "0" else "failed",
    "debug_taint": "passed" if sys.argv[5] == "0" else "failed",
    # the collector-fleet legs (migration + host:kill failover), folded
    # out of the main run so fleet health is one key deep
    "fleet": {
        t["name"].split("::")[-1]: t["outcome"]
        for t in tests
        if "test_fleet" in t["name"]
        and ("migration" in t["name"] or "host_kill" in t["name"])
    },
    "tests": tests,
}
json.dump(doc, open(sys.argv[2], "w"), indent=1)
print(
    f"chaos suite: {doc['passed']} passed, {doc['failed']} failed, "
    f"{doc['skipped']} skipped in {doc['duration_s']}s, "
    f"debug_guards={doc['debug_guards']}, "
    f"trace_validation={doc['trace_validation']}, "
    f"debug_taint={doc['debug_taint']} -> {sys.argv[2]}"
)
EOF
rm -f "$report"
exit $rc
