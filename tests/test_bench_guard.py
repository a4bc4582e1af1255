"""The secure-device bench's contention guard, pinned with canned timers.

The guard exists for the shared chip's multi-minute ~15x-slow windows
(bench.bench_secure_device): when any measured side lands far above the
secure/trusted design ratio, the bench waits once, re-measures every
affected side, and reports ratios computed from the post-retry numbers.
Those semantics (trigger condition, min-merge, retry flag, ratio
consistency) are pure control flow around the timer — so they are testable
on CPU by patching the steady-state timer with a scripted value sequence;
the level programs themselves still run once each (the correctness pin
inside the bench asserts secure counts == trusted counts on every engine).
"""

import os

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _module_cpu(cpu_default):
    # importing bench flips prg.CHACHA_UNROLL to the chip-friendly unrolled
    # form; import it FIRST (so its module-level assignment has happened),
    # then force the scan form back both for this test's compiles and for
    # the rest of the suite (the flag is process-global and read at trace
    # time — leaking True makes every later CPU compile pathologically slow)
    import bench  # noqa: F401

    from fuzzyheavyhitters_tpu.ops import prg

    prg.CHACHA_UNROLL = False
    yield
    prg.CHACHA_UNROLL = False


def test_contention_retry_min_merges_and_reports(monkeypatch):
    import bench
    from fuzzyheavyhitters_tpu.protocol import secure

    assert secure.EQ_OT4  # the S = 2 default: the gc-path A/B leg runs too

    # call order inside bench_secure_device on a CPU host (no Pallas GC,
    # with_l512=False): gc_path, fe62, f255, trusted -> guard trips ->
    # retry fe62, f255, gc_path, trusted -> 2x-bucket point
    script = iter([
        0.100,  # gc_path   (contended window)
        0.100,  # fe62      (contended window)
        0.020,  # f255      (contended window too: also > 8x trusted)
        0.001,  # trusted   -> fe62/trusted = 100 > 8: retry
        0.002,  # retry fe62
        0.003,  # retry f255
        0.004,  # retry gc_path
        0.001,  # retry trusted
        0.003,  # 2x bucket
    ])
    monkeypatch.setattr(
        bench, "_steady_state_seconds",
        lambda thunk, force, warm_force, iters=20, trials=3: next(script),
    )
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)

    out = bench.bench_secure_device(n=128, L=4, f_bucket=1, with_l512=False)

    assert out["contention_retry"] is True
    # min-merge: the retried (clean) numbers replace the contended ones
    assert out["secure_device_ms_per_level_fe62"] == 2.0
    assert out["secure_device_ms_per_level_f255"] == 3.0
    assert out["secure_device_ms_per_level_fe62_gc_path"] == 4.0
    assert out["trusted_same_shape_ms_per_level"] == 1.0
    # ratios are computed AFTER the retry, from the reported numbers
    assert out["secure_over_trusted_ratio"] == 2.0
    assert out["ot4_speedup_vs_gc_path"] == 2.0


def test_no_retry_on_clean_window(monkeypatch):
    import bench

    script = iter([
        0.004,  # gc_path
        0.003,  # fe62
        0.003,  # f255
        0.001,  # trusted -> ratio 3: no retry
        0.005,  # 2x bucket
    ])
    monkeypatch.setattr(
        bench, "_steady_state_seconds",
        lambda thunk, force, warm_force, iters=20, trials=3: next(script),
    )
    monkeypatch.setattr(
        bench.time, "sleep",
        lambda s: (_ for _ in ()).throw(AssertionError("slept on clean window")),
    )

    out = bench.bench_secure_device(n=128, L=4, f_bucket=1, with_l512=False)
    assert "contention_retry" not in out
    assert out["secure_over_trusted_ratio"] == 3.0
    np.testing.assert_allclose(out["ot4_speedup_vs_gc_path"], 4 / 3, rtol=0.02)


def _pids_with_cmdline(marker: str) -> list[int]:
    pids = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if marker.encode() in f.read():
                    pids.append(int(pid))
        except OSError:
            pass  # raced a process exit
    return pids


@pytest.mark.skipif(
    not os.path.isdir("/proc"), reason="needs procfs to observe the child"
)
def test_subprocess_metric_kills_child_on_teardown():
    """A driver SIGTERM / Ctrl-C landing while the parent is blocked in
    communicate() must still TERM the child bench: the parent's
    SIGTERM->SystemExit handler raises a BaseException that skips the
    TimeoutExpired path, and a leaked child would keep crawling the
    accelerator after the bench is gone."""
    import signal

    import bench

    marker = f"fhh_teardown_probe_{os.getpid()}"
    old = signal.signal(
        signal.SIGALRM,
        lambda *_: (_ for _ in ()).throw(KeyboardInterrupt()),
    )
    try:
        signal.setitimer(signal.ITIMER_REAL, 1.0)
        with pytest.raises(KeyboardInterrupt):
            bench._subprocess_metric(
                f"import time  # {marker}\ntime.sleep(120)", timeout_s=60
            )
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
    # the child was reaped before the interrupt propagated
    assert _pids_with_cmdline(marker) == []


_PARENT_PROBE = """
import json, sys
import bench

def fake(code, timeout_s):
    if "bench_keygen_leg" in code:
        return {"headline": 1.0, "sweep": {}}
    return json.loads(sys.argv[1])

bench._subprocess_metric = fake
rc = bench.main(["--out", "out.json", "--sections", "upload"])
from jax._src import xla_bridge
print(json.dumps({"rc": rc, "backend": xla_bridge.backends_are_initialized()}))
"""


@pytest.mark.parametrize(
    "leg,rc", [({"upload_keys_per_sec": 1.0}, 0), ({"error": "child rc=1"}, 1)]
)
def test_parent_stays_off_the_backend_and_errors_set_the_exit_code(
    tmp_path, leg, rc
):
    """One process per chip: ``bench.main`` (the parent of every leg,
    keygen included) must never initialise a JAX backend — a parent that
    holds the chip starves its children — and a leg that came back as
    ``{"error": ...}`` makes the exit code non-zero while the final JSON
    line still prints (skipped legs stay 0)."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo)
    p = subprocess.run(
        [sys.executable, "-c", _PARENT_PROBE, json.dumps(leg)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    lines = p.stdout.strip().splitlines()
    assert lines, p.stderr[-2000:]
    assert json.loads(lines[-1]) == {"rc": rc, "backend": False}
    final = json.loads(lines[-2])  # bench's own last line still printed
    assert final["metric"] == "ibdcf_keygen_keys_per_sec_at_data_len_512"
