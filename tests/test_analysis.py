"""fhh-lint: rule fixtures, suppression semantics, baseline machinery,
CLI plumbing, and the repo self-lint.

Each rule gets positive (seeded violation detected) and negative (idiomatic
clean code passes) fixtures; the self-lint test at the bottom is the tier-1
enforcement point: the tree must be clean at default severity under the
checked-in baseline, with no pytest marker so the driver's default
invocation always runs it.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from fuzzyheavyhitters_tpu.analysis import (
    ALL_RULES,
    LintConfig,
    apply_baseline,
    lint_paths,
    lint_source,
    load_baseline,
    load_config,
    write_baseline,
)
from fuzzyheavyhitters_tpu.analysis.rules import RULES_BY_NAME

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, relpath="fuzzyheavyhitters_tpu/protocol/fake.py", cfg=None,
          rule=None):
    cfg = cfg or LintConfig()
    rules = [RULES_BY_NAME[rule]] if rule else None
    return lint_source(textwrap.dedent(src), relpath, cfg, rules)


def _names(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# rule: host-sync-in-hot-loop
# ---------------------------------------------------------------------------


def test_host_sync_in_loop_detected():
    src = """
    import numpy as np
    def run(levels, x):
        for level in levels:
            y = np.asarray(x)  # device fetch per level
        return y
    """
    fs = _lint(src, rule="host-sync-in-hot-loop")
    assert _names(fs) == ["host-sync-in-hot-loop"]
    assert fs[0].line == 5


def test_host_sync_via_hot_root_transitive():
    src = """
    import numpy as np
    def helper(x):
        return np.asarray(x)
    def tree_crawl(x):
        return helper(x)
    """
    fs = _lint(src, rule="host-sync-in-hot-loop")
    assert len(fs) == 1 and "helper" in fs[0].message


def test_host_sync_item_and_block_until_ready():
    src = """
    def run_level(x):
        a = x.item()
        x.block_until_ready()
        return a
    """
    assert len(_lint(src, rule="host-sync-in-hot-loop")) == 2


def test_host_sync_cast_inside_jit():
    src = """
    import jax
    @jax.jit
    def f(x):
        return bool(x.sum())
    """
    fs = _lint(src, "some/other/module.py", rule="host-sync-in-hot-loop")
    assert len(fs) == 1 and "jit-compiled" in fs[0].message


def test_host_sync_clean_cases():
    src = """
    import numpy as np
    import jax.numpy as jnp
    def setup(x):
        return np.asarray(x)  # not hot: no loop, not a hot root
    def run_level(x):
        return jnp.asarray(x)  # device-side, never flagged
    def other(p):
        return bool(p)  # plain cast outside jit
    """
    assert _lint(src, rule="host-sync-in-hot-loop") == []


def test_host_sync_not_hot_outside_hot_modules():
    src = """
    import numpy as np
    def f(xs):
        for x in xs:
            y = np.asarray(x)
        return y
    """
    assert _lint(src, "fuzzyheavyhitters_tpu/workloads/w.py",
                 rule="host-sync-in-hot-loop") == []


# ---------------------------------------------------------------------------
# rule: secret-to-sink
# ---------------------------------------------------------------------------


def test_secret_to_emit_detected():
    src = """
    from .. import obs
    def f(gc_seed):
        obs.emit("level.done", seed=gc_seed)
    """
    fs = _lint(src, rule="secret-to-sink")
    assert len(fs) == 1 and "gc_seed" in fs[0].message


def test_secret_to_print_and_raise_detected():
    src = """
    def f(self):
        print(self.cw_seed)
        raise ValueError(f"bad key: {self._sec_seed}")
    """
    fs = _lint(src, rule="secret-to-sink")
    assert len(fs) == 2


def test_secret_sink_clean_cases():
    src = """
    from .. import obs
    def f(level, seconds, seed_len):
        obs.emit("level.done", level=level, fss_s=seconds)
        raise ValueError(f"bad level {level}")
    """
    # NB 'seed_len' segments are ('seed','len') — present but unused: only
    # flow INTO a sink counts
    assert _lint(src, rule="secret-to-sink") == []


def test_secret_kwarg_name_counts_as_flow():
    src = """
    def f(emit, x):
        emit("evt", mac_key=x)
    """
    fs = _lint(src, rule="secret-to-sink")
    assert len(fs) == 1


# ---------------------------------------------------------------------------
# rule: recompile-churn
# ---------------------------------------------------------------------------


def test_jit_wrapper_in_function_detected():
    src = """
    import jax, numpy as np
    def to_ints(v):
        return np.asarray(jax.jit(canon)(v))
    """
    fs = _lint(src, rule="recompile-churn")
    assert len(fs) == 1 and "hoist" in fs[0].message


def test_jit_wrapper_at_module_level_clean():
    src = """
    import jax
    def canon(v):
        return v
    canon_jit = jax.jit(canon)
    @jax.jit
    def g(x):
        return x
    """
    assert _lint(src, rule="recompile-churn") == []


def test_static_arg_unhashable_literal_detected():
    src = """
    import jax
    from functools import partial
    @partial(jax.jit, static_argnames=("shape",))
    def f(x, shape):
        return x
    def caller(x):
        return f(x, shape=[1, 2])
    """
    fs = _lint(src, rule="recompile-churn")
    assert len(fs) == 1 and "unhashable" in fs[0].message


def test_static_arg_loop_variable_detected():
    src = """
    import jax
    from functools import partial
    @partial(jax.jit, static_argnums=(1,))
    def f(x, width):
        return x
    def caller(x, widths):
        for w in widths:
            x = f(x, w)
        return x
    """
    fs = _lint(src, rule="recompile-churn")
    assert len(fs) == 1 and "loop variable" in fs[0].message


def test_static_arg_clean_call():
    src = """
    import jax
    from functools import partial
    @partial(jax.jit, static_argnames=("width",))
    def f(x, width):
        return x
    def caller(x):
        return f(x, width=8)
    """
    assert _lint(src, rule="recompile-churn") == []


# ---------------------------------------------------------------------------
# rule: unguarded-shared-state
# ---------------------------------------------------------------------------

_SHARED_PATH = "fuzzyheavyhitters_tpu/obs/fake.py"


def test_unguarded_write_detected():
    src = """
    import threading
    _lock = threading.Lock()
    _cache = {}
    def put(k, v):
        _cache[k] = v
    """
    fs = _lint(src, _SHARED_PATH, rule="unguarded-shared-state")
    assert len(fs) == 1 and "_cache" in fs[0].message


def test_unguarded_global_rebind_and_method_detected():
    src = """
    import threading
    _lock = threading.Lock()
    _items = []
    _count = 0
    def add(v):
        global _count
        _count += 1
        _items.append(v)
    """
    fs = _lint(src, _SHARED_PATH, rule="unguarded-shared-state")
    assert len(fs) == 2


def test_locked_write_clean():
    src = """
    import threading
    _lock = threading.RLock()
    _cache = {}
    _n = 0
    def put(k, v):
        global _n
        with _lock:
            _cache[k] = v
            _n += 1
    """
    assert _lint(src, _SHARED_PATH, rule="unguarded-shared-state") == []


def test_shared_state_rule_scoped_to_configured_modules():
    src = """
    _cache = {}
    def put(k, v):
        _cache[k] = v
    """
    assert _lint(src, "fuzzyheavyhitters_tpu/workloads/w.py",
                 rule="unguarded-shared-state") == []


# ---------------------------------------------------------------------------
# rules: broad-except, bare-print
# ---------------------------------------------------------------------------


def test_broad_except_detected_and_reraise_clean():
    src = """
    def f():
        try:
            g()
        except Exception:
            pass
    def g():
        try:
            h()
        except:
            return None
    def ok():
        try:
            h()
        except Exception:
            cleanup()
            raise
    def ok2():
        try:
            h()
        except ValueError:
            return None
    """
    fs = _lint(src, rule="broad-except")
    assert len(fs) == 2
    assert "bare" in fs[1].message


def test_broad_except_pytest_skip_counts_as_raise():
    src = """
    import pytest
    def probe():
        try:
            g()
        except Exception:
            pytest.skip("no backend")
    """
    assert _lint(src, rule="broad-except") == []


def test_bare_print_detected_and_scoped():
    src = """
    def f(x):
        print("crawl done", x)
    """
    assert len(_lint(src, rule="bare-print")) == 1
    # out of scope: tests and the allowlisted plot scripts
    assert _lint(src, "tests/test_x.py", rule="bare-print") == []
    assert _lint(
        src,
        "fuzzyheavyhitters_tpu/workloads/ride_austin_visualization.py",
        rule="bare-print",
    ) == []


# ---------------------------------------------------------------------------
# rule: unbounded-await
# ---------------------------------------------------------------------------


def test_unbounded_await_reads_and_waits_detected():
    src = """
    import asyncio

    async def f(reader, ev, tasks):
        hdr = await reader.readexactly(8)
        line = await reader.readline()
        await ev.wait()
        done, pending = await asyncio.wait(tasks)
    """
    found = _lint(src, rule="unbounded-await")
    assert len(found) == 4
    assert all(f.rule == "unbounded-await" for f in found)


def test_unbounded_await_frame_reads_detected():
    """The wire layer's read primitives (protocol/wire.py) are network
    reads like ``readexactly``: a frame's out-of-band buffer filled by
    ``readinto``, a whole body by ``read_body``."""
    src = """
    import asyncio

    async def f(reader, wire, buf, n):
        await reader.readinto(buf)
        meta, bufs = await wire.read_body(reader, n)
        await asyncio.wait_for(reader.readinto(buf), 5.0)
    """
    found = _lint(src, rule="unbounded-await")
    assert [f.rule for f in found] == ["unbounded-await"] * 2


def test_unbounded_await_dial_and_disguised_wait_for_detected():
    src = """
    import asyncio

    async def f(fut):
        r, w = await asyncio.open_connection("h", 1)
        await asyncio.wait_for(fut, None)
        await asyncio.wait_for(fut, timeout=None)
    """
    found = _lint(src, rule="unbounded-await")
    assert len(found) == 3


def test_unbounded_await_bounded_forms_clean():
    src = """
    import asyncio

    async def f(reader, tasks, fut, deadline):
        hdr = await asyncio.wait_for(reader.readexactly(8), 5.0)
        done, pending = await asyncio.wait(tasks, timeout=30)
        resp = await asyncio.wait_for(fut, deadline.remaining())
        body = await reader.read(n, timeout=2.0)
        return await fut  # awaiting a plain future is not a net call
    """
    assert _lint(src, rule="unbounded-await") == []


def test_unbounded_await_scoped_to_transport_modules():
    src = """
    async def f(reader):
        return await reader.readexactly(8)
    """
    assert len(_lint(src, rule="unbounded-await")) == 1
    assert _lint(
        src, "fuzzyheavyhitters_tpu/resilience/fake.py", rule="unbounded-await"
    )  # resilience is transport scope too
    assert _lint(
        src, "fuzzyheavyhitters_tpu/parallel/fake.py", rule="unbounded-await"
    )  # ... and parallel (mesh transport awaits need deadlines too)
    assert _lint(
        src, "fuzzyheavyhitters_tpu/ops/fake.py", rule="unbounded-await"
    ) == []
    assert _lint(src, "tests/test_x.py", rule="unbounded-await") == []


def test_unbounded_await_suppression():
    src = """
    async def f(reader):
        # fhh-lint: disable=unbounded-await (serve loop: waits for the
        # next command by design)
        return await reader.readexactly(8)
    """
    assert _lint(src, rule="unbounded-await") == []


# ---------------------------------------------------------------------------
# rule: unbounded-queue
# ---------------------------------------------------------------------------


def test_unbounded_queue_detected():
    """The exact bug class the streaming front door exists to prevent:
    a buffer with no bound between a producer and a slower consumer."""
    src = """
    import asyncio
    import collections

    q = asyncio.Queue()
    d = collections.deque()
    s = queue.SimpleQueue()
    zero = asyncio.Queue(maxsize=0)
    none = collections.deque(maxlen=None)
    """
    fs = _lint(src, rule="unbounded-queue")
    assert len(fs) == 5
    assert all(f.rule == "unbounded-queue" for f in fs)


def test_unbounded_queue_bounded_forms_clean():
    src = """
    import asyncio
    import collections

    q = asyncio.Queue(maxsize=64)
    qpos = asyncio.Queue(64)
    d = collections.deque(maxlen=8)
    dpos = collections.deque([], 8)
    dyn = asyncio.Queue(maxsize=cap)
    """
    assert _lint(src, rule="unbounded-queue") == []


# ---------------------------------------------------------------------------
# rule: span-discipline
# ---------------------------------------------------------------------------


def test_span_discipline_flags_non_context_manager_spans():
    """A span created outside a with statement records nothing (never
    entered) or dangles forever (entered, never exited) — both read as
    instrumentation while measuring nothing."""
    src = """
    def leak(reg):
        sp = reg.span("gc_ot", level=1)     # never entered
        ctx = self.obs.span("ingest")       # manually entered, leakable
        ctx.__enter__()
        reg.span("fss")                     # bare expression statement
    """
    fs = _lint(src, rule="span-discipline")
    assert len(fs) == 3
    assert all(f.rule == "span-discipline" for f in fs)


def test_span_discipline_with_forms_and_other_attrs_clean():
    src = """
    def ok(reg, cs):
        with reg.span("level", level=0) as sp:
            with cs.obs.span("fss", level=0):
                pass
        sp2 = reg.current_span()            # not span()
        n = numpy.span(3)                   # attr named span, still a
        # span-shaped call: deliberately flagged only as a with-item
        return sp, sp2, n
    """
    fs = _lint(src, rule="span-discipline")
    # numpy.span(3) IS flagged (attr name is the signal — suppressions
    # cover false positives); the with-forms and current_span are clean
    assert len(fs) == 1 and fs[0].line == 7


def test_span_discipline_flags_telemetry_in_jit_bodies():
    src = """
    import jax

    @jax.jit
    def kernel(x, reg):
        obs.emit("level.done", n=3)         # records once per COMPILE
        reg.observe("level_latency", 0.1)   # ditto
        return x + 1

    def host(reg):
        obs.emit("level.done", n=3)         # host-side: fine
        reg.observe("level_latency", 0.1)
    """
    fs = _lint(src, rule="span-discipline")
    assert len(fs) == 2
    assert all("jit" in f.message for f in fs)


def test_span_discipline_scope_and_suppression():
    src = """
    def leak(reg):
        sp = reg.span("gc_ot")
    """
    # out of scope (span_modules): clean
    assert _lint(
        src, relpath="fuzzyheavyhitters_tpu/workloads/x.py",
        rule="span-discipline",
    ) == []
    suppressed = """
    def managed(reg):
        # fhh-lint: disable=span-discipline (enter/exit managed across seal boundaries)
        sp = reg.span("ingest")
        sp.__enter__()
    """
    assert _lint(suppressed, rule="span-discipline") == []


def test_unbounded_queue_scoped_and_suppressible():
    src = """
    import collections
    d = collections.deque()
    """
    assert len(_lint(src, rule="unbounded-queue")) == 1
    assert _lint(
        src, "fuzzyheavyhitters_tpu/resilience/fake.py",
        rule="unbounded-queue",
    )
    assert _lint(
        src, "fuzzyheavyhitters_tpu/ops/fake.py", rule="unbounded-queue"
    ) == []
    assert _lint(src, "tests/test_x.py", rule="unbounded-queue") == []
    sup = """
    import collections
    # fhh-lint: disable=unbounded-queue (bounded by construction: the
    # refill loop never holds more than `depth` entries)
    d = collections.deque()
    """
    assert _lint(sup, rule="unbounded-queue") == []


# ---------------------------------------------------------------------------
# rule: metric-naming
# ---------------------------------------------------------------------------


def test_metric_naming_bad_registry_names_detected():
    src = """
    def f(reg, n):
        reg.count("Fresh-Compiles", n)
        reg.gauge("queue.depth", n)
        reg.observe("levelLatency", 0.5)
    """
    fs = _lint(src, rule="metric-naming")
    assert _names(fs) == ["metric-naming"] * 3
    assert [f.line for f in fs] == [3, 4, 5]


def test_metric_naming_valid_and_nonname_literals_clean():
    src = """
    def f(reg, log, n):
        reg.count("fresh_compiles", n)
        reg.count("fresh_compiles:rt_keygen", n)
        reg.observe("level_latency", 0.5)
        reg.timer_add("xla_compile", 0.5)
        log.count("alert fired {rule}")  # spaces/braces: str.count search
        return "some. punctuation!"  # not even identifier-like
    """
    assert _lint(src, rule="metric-naming") == []


def test_metric_naming_exported_literal_needs_unit_suffix():
    src = """
    GOOD = ("fhh_data_bytes_sent_total", "fhh_session_queue_depth_keys")
    BAD = "fhh_alert"
    """
    fs = _lint(src, rule="metric-naming")
    assert _names(fs) == ["metric-naming"]
    assert fs[0].line == 3
    # f-string fragments are assembly, never whole series names
    frag = """
    def render(name):
        return f"fhh_{name}_total 1"
    """
    assert _lint(frag, rule="metric-naming") == []


def test_metric_naming_scoped_to_metric_modules():
    src = """
    def f(reg, n):
        reg.count("Fresh-Compiles", n)
    """
    # tests/ ARE in scope (they hand-roll scrape keys); workloads are not
    assert len(_lint(src, "tests/test_x.py", rule="metric-naming")) == 1
    assert _lint(
        src,
        "fuzzyheavyhitters_tpu/workloads/fake.py",
        rule="metric-naming",
    ) == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_inline_suppression_same_line():
    src = """
    def f(x):
        print(x)  # fhh-lint: disable=bare-print (demo tool)
    """
    assert _lint(src, rule="bare-print") == []


def test_suppression_standalone_comment_applies_to_next_code_line():
    src = """
    def f(x):
        # fhh-lint: disable=bare-print (a justification
        # that continues over two comment lines)
        print(x)
    """
    assert _lint(src, rule="bare-print") == []


def test_suppression_is_per_rule():
    src = """
    def f(x):
        print(x.cw_seed)  # fhh-lint: disable=bare-print
    """
    # bare-print silenced; secret-to-sink still fires
    names = _names(_lint(src))
    assert names == ["secret-to-sink"]


def test_suppression_multiple_rules_one_comment():
    src = """
    def f(x):
        print(x.cw_seed)  # fhh-lint: disable=bare-print,secret-to-sink
    """
    assert _lint(src) == []


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------

_BASE_SRC = """
def run_level(x):
    import numpy as np
    a = np.asarray(x)
    b = np.asarray(x)
    return a, b
"""


def _base_findings():
    return _lint(_BASE_SRC, rule="host-sync-in-hot-loop")


def test_baseline_absorbs_up_to_count(tmp_path):
    fs = _base_findings()
    assert len(fs) == 2
    path = str(tmp_path / "b.json")
    write_baseline(path, fs)
    counts = load_baseline(path)
    res = apply_baseline(fs, counts)
    assert res.new == [] and res.absorbed == 2 and res.stale == []


def test_baseline_growth_is_new(tmp_path):
    fs = _base_findings()
    path = str(tmp_path / "b.json")
    write_baseline(path, fs[:1])  # baseline holds count=1
    res = apply_baseline(fs, load_baseline(path))
    assert len(res.new) == 1 and res.absorbed == 1
    # the reported NEW finding is the later one in line order
    assert res.new[0].line == max(f.line for f in fs)


def test_baseline_shrink_reports_stale(tmp_path):
    fs = _base_findings()
    path = str(tmp_path / "b.json")
    write_baseline(path, fs)
    res = apply_baseline(fs[:1], load_baseline(path))
    assert res.new == [] and res.absorbed == 1
    assert res.stale == [
        ("host-sync-in-hot-loop", "fuzzyheavyhitters_tpu/protocol/fake.py", 1)
    ]


def test_baseline_remove_via_update(tmp_path):
    path = str(tmp_path / "b.json")
    write_baseline(path, _base_findings())
    write_baseline(path, [])  # burn-down complete
    assert load_baseline(path) == {}


def test_baseline_partial_update_keeps_unscanned_entries(tmp_path):
    """write_baseline(keep=...) — the CLI passes entries for files outside
    the scanned path set so a partial --update-baseline run cannot erase
    another subtree's grandfathered findings."""
    fs = _base_findings()  # all in fuzzyheavyhitters_tpu/protocol/fake.py
    path = str(tmp_path / "b.json")
    keep = {"host-sync-in-hot-loop": {"other/subtree.py": 3},
            "recompile-churn": {"gone/now_clean.py": 0}}
    write_baseline(path, fs, keep=keep)
    counts = load_baseline(path)
    assert counts["host-sync-in-hot-loop"]["other/subtree.py"] == 3
    assert counts["host-sync-in-hot-loop"][
        "fuzzyheavyhitters_tpu/protocol/fake.py"
    ] == 2
    assert "recompile-churn" not in counts  # zero-count entries dropped


def test_baseline_stale_scoped_to_scanned_paths():
    """A partial-scope run must not report unscanned files' baseline
    entries as stale burn-down wins."""
    counts = {"host-sync-in-hot-loop": {"pkg/unscanned.py": 8}}
    res = apply_baseline([], counts, scanned={"pkg/scanned.py"})
    assert res.stale == []
    res = apply_baseline([], counts, scanned={"pkg/unscanned.py"})
    assert res.stale == [("host-sync-in-hot-loop", "pkg/unscanned.py", 8)]


def test_cli_update_baseline_drops_deleted_files_keeps_unscanned(tmp_path):
    """Partial --update-baseline: entries for files outside the scan scope
    survive IF the file still exists; deleted files' entries drop out."""
    pkg = tmp_path / "pkg"
    sub = pkg / "sub"
    sub.mkdir(parents=True)
    (pkg / "live.py").write_text("def f(x):\n    print(x)\n")
    (sub / "other.py").write_text("def g(x):\n    print(x)\n")
    base = tmp_path / "lint_baseline.json"
    base.write_text(json.dumps({
        "schema": "fhh-lint-baseline/1",
        "counts": {"bare-print": {
            "pkg/live.py": 1,          # scanned: rewritten from findings
            "pkg/sub/other.py": 1,     # unscanned but alive: kept
            "pkg/deleted.py": 4,       # gone from disk: dropped
        }},
    }))
    cfg_toml = tmp_path / "pyproject.toml"
    cfg_toml.write_text(
        "[tool.fhh-lint]\nprint_scope = [\"pkg\"]\n"
        "baseline = \"lint_baseline.json\"\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "fuzzyheavyhitters_tpu.analysis",
         "pkg/live.py", "--update-baseline", "--root", str(tmp_path)],
        cwd=str(tmp_path), capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    counts = load_baseline(str(base))
    assert counts == {"bare-print": {
        "pkg/live.py": 1, "pkg/sub/other.py": 1,
    }}, counts


def test_cli_rejects_non_python_file_and_empty_scan(tmp_path):
    """A non-.py file argument (or a path set yielding zero .py files) is
    a usage error (exit 2), never a silent green."""
    (tmp_path / "wrapper.sh").write_text("echo hi\n")
    empty = tmp_path / "empty"
    empty.mkdir()
    env = dict(os.environ, PYTHONPATH=REPO)
    for arg in ("wrapper.sh", "empty"):
        proc = subprocess.run(
            [sys.executable, "-m", "fuzzyheavyhitters_tpu.analysis",
             arg, "--root", str(tmp_path)],
            cwd=str(tmp_path), capture_output=True, text=True, env=env,
            timeout=300,
        )
        assert proc.returncode == 2, (arg, proc.stdout, proc.stderr)


def test_baseline_rejects_unknown_schema(tmp_path):
    path = tmp_path / "b.json"
    path.write_text(json.dumps({"schema": "nope", "counts": {}}))
    with pytest.raises(ValueError):
        load_baseline(str(path))


# ---------------------------------------------------------------------------
# config loading
# ---------------------------------------------------------------------------


def test_pyproject_config_loads():
    cfg = load_config(REPO)
    assert "run_level" in cfg.hot_roots
    assert "seed" in cfg.secret_lexicon
    assert cfg.severity_overrides.get("host-sync-in-hot-loop") == "warning"
    assert cfg.baseline == "lint_baseline.json"


def test_config_defaults_without_pyproject(tmp_path):
    cfg = load_config(str(tmp_path))
    assert cfg.hot_roots  # built-in defaults apply
    assert cfg.baseline == "lint_baseline.json"


def test_pyproject_and_dataclass_defaults_do_not_drift():
    """pyproject.toml [tool.fhh-lint] is the operative tuning and the
    LintConfig defaults mirror it (fixture tests build bare LintConfig()s).
    If this fails you edited one copy — update the other to match."""
    operative = load_config(REPO)
    defaults = LintConfig()
    for key in (
        "hot_modules", "hot_roots", "secret_lexicon", "sink_calls",
        "print_scope", "print_allowed", "shared_state_modules",
        "await_modules", "readback_modules", "queue_modules",
        "span_modules", "metric_modules", "metric_calls",
        "metric_unit_suffixes", "race_modules", "guards",
        "default_paths", "baseline",
    ):
        assert getattr(operative, key) == getattr(defaults, key), key


def test_config_tables_name_code_that_exists():
    """The tables name functions and classes BY NAME, so a deletion or a
    rename leaves them pointing at nothing, in silence: every function
    name in the taint tables (sources by return, sinks, wire calls,
    declassifiers) and in ``hot_roots`` is defined somewhere in the
    package, and every ``Class.attr`` of the race guard map is a class
    of the package that assigns both the attribute and its lock."""
    import ast
    import builtins

    funcs, classes = set(), {}
    pkg = os.path.join(REPO, "fuzzyheavyhitters_tpu")
    for root, _, files in os.walk(pkg):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name), encoding="utf-8") as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    funcs.add(node.name)
                elif isinstance(node, ast.ClassDef):
                    attrs = classes.setdefault(node.name, set())
                    for sub in ast.walk(node):
                        if (isinstance(sub, ast.Attribute)
                                and isinstance(sub.ctx, ast.Store)
                                and isinstance(sub.value, ast.Name)
                                and sub.value.id == "self"):
                            attrs.add(sub.attr)
    cfg = load_config(REPO)
    tables = {
        "taint (return sources)": [k for k in cfg.taint if "." not in k],
        "taint_sinks": [n for n in cfg.taint_sinks
                        if not hasattr(builtins, n)],
        "taint_wire_calls": cfg.taint_wire_calls,
        "taint_declassifiers": cfg.taint_declassifiers,
        "hot_roots": cfg.hot_roots,
    }
    gone = {t: [n for n in names if n not in funcs]
            for t, names in tables.items()}
    assert not any(gone.values()), gone
    for key in cfg.taint:
        if "." in key:
            assert key.split(".")[0] in classes, key
    for key, lock in cfg.guards.items():
        cls, attr = key.split(".")
        assert cls in classes, key
        assert {attr, lock} <= classes[cls], (key, lock)


# ---------------------------------------------------------------------------
# self-lint: the repo is clean under the checked-in baseline
# ---------------------------------------------------------------------------


def test_self_lint_repo_clean_under_baseline():
    """Tier-1 enforcement: zero non-baselined findings at ANY severity over
    the package + tests, under the checked-in baseline.  A finding here
    means: fix it, suppress it with a justification, or consciously grow
    the baseline — never merge it silently."""
    cfg = load_config(REPO)
    findings, errors = lint_paths(
        ["fuzzyheavyhitters_tpu", "tests"], cfg, REPO
    )
    assert errors == []
    counts = load_baseline(os.path.join(REPO, cfg.baseline))
    res = apply_baseline(findings, counts)
    assert res.new == [], "new lint findings:\n" + "\n".join(
        f.render() for f in res.new
    )
    # the baseline must not rot silently either: stale entries mean a
    # finding was fixed — bank it with --update-baseline
    assert res.stale == [], (
        "baseline entries no longer needed (run "
        "`python -m fuzzyheavyhitters_tpu.analysis --update-baseline`): "
        f"{res.stale}"
    )


# ---------------------------------------------------------------------------
# rule: chunked-device-readback
# ---------------------------------------------------------------------------

_READBACK_SCOPE = "fuzzyheavyhitters_tpu/protocol/secure.py"


def test_chunked_readback_loop_fetches_detected():
    """Every readback form — the sanctioned ``_fetch`` included — trips
    the rule when it sits inside a per-chunk loop in a readback module:
    a loop of fetches is one device round trip per chunk no matter how
    each individual fetch is dressed."""
    src = """
    import numpy as np
    import jax

    async def crawl(chunks, reg):
        out = []
        for c in chunks:
            out.append(await _fetch(c, reg))
        for c in chunks:
            out.append(np.asarray(c))
        for c in chunks:
            out.append(jax.device_get(c))
        for c in chunks:
            c.copy_to_host_async()
        return out
    """
    found = _lint(src, _READBACK_SCOPE, rule="chunked-device-readback")
    assert len(found) == 4
    assert all(f.rule == "chunked-device-readback" for f in found)


def test_chunked_readback_whole_level_fetch_clean():
    """The sanctioned shape — stack on device inside the loop, ONE fetch
    after it — is clean, as are readbacks outside any loop."""
    src = """
    import numpy as np

    async def crawl(chunks, reg):
        parts = []
        for c in chunks:
            parts.append(transform(c))  # device-side, no readback
        whole = await _fetch(stack(parts), reg)
        direct = np.asarray(whole)
        return whole, direct
    """
    assert _lint(src, _READBACK_SCOPE, rule="chunked-device-readback") == []


def test_chunked_readback_scoped_to_readback_modules():
    src = """
    async def f(chunks):
        return [await _fetch(c) for c in chunks]
    """
    # comprehensions are loops too
    assert _lint(src, _READBACK_SCOPE, rule="chunked-device-readback")
    assert _lint(
        src, "fuzzyheavyhitters_tpu/ops/fake.py",
        rule="chunked-device-readback",
    )
    # rpc.py and parallel/ joined the scope with the multi-chip refactor
    # (the crawl verbs' expand/open stages and the sharded mesh paths
    # must never regrow per-chunk fetch loops); the sanctioned wire
    # fetches there carry inline suppressions with justifications
    assert _lint(
        src, "fuzzyheavyhitters_tpu/protocol/rpc.py",
        rule="chunked-device-readback",
    )
    assert _lint(
        src, "fuzzyheavyhitters_tpu/parallel/server_mesh.py",
        rule="chunked-device-readback",
    )
    # the control/driver layers stay out: their wire-input conversions
    # are host numpy by construction
    assert _lint(
        src, "fuzzyheavyhitters_tpu/protocol/leader_rpc.py",
        rule="chunked-device-readback",
    ) == []
    assert _lint(src, "tests/test_x.py", rule="chunked-device-readback") == []


def test_chunked_readback_device_side_asarray_clean():
    """jnp.asarray is a device-side cast, not a readback — must not trip."""
    src = """
    import jax.numpy as jnp

    def f(chunks):
        return [jnp.asarray(c) for c in chunks]
    """
    assert _lint(src, _READBACK_SCOPE, rule="chunked-device-readback") == []


def test_every_rule_has_fixture_coverage():
    """Each shipped rule appears in at least one positive fixture — here,
    or (the fhh-race pair) in tests/test_concurrency.py — guards against
    a rule being added but never exercised."""
    covered = {
        "host-sync-in-hot-loop",
        "secret-to-sink",
        "recompile-churn",
        "unguarded-shared-state",
        "broad-except",
        "bare-print",
        "chunked-device-readback",
        "unbounded-await",
        "unbounded-queue",
        "span-discipline",
        "metric-naming",
        # fixtures in tests/test_concurrency.py
        "guarded-state-unlocked",
        "stale-read-across-await",
        # fixtures in tests/test_taint.py
        "secret-to-sink-flow",
        "secret-branch",
        "unmasked-wire",
    }
    assert {r.name for r in ALL_RULES} == covered


def test_cli_json_strict_on_repo():
    """The CLI contract the driver and scripts/lint.sh rely on: strict
    mode exits 0 on the current tree and the JSON document parses."""
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "fuzzyheavyhitters_tpu.analysis",
            "fuzzyheavyhitters_tpu",
            "tests",
            "--strict",
            "--format",
            "json",
        ],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["schema"] == "fhh-lint-report/1"
    assert doc["findings"] == [] and doc["failing"] == 0


def test_cli_exit_codes(tmp_path):
    """Seeded violation -> exit 1 under --no-baseline; clean file -> 0."""
    bad = tmp_path / "fuzzyheavyhitters_tpu"
    bad.mkdir()
    (bad / "mod.py").write_text(
        "def f(x):\n    print(x)\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [
            sys.executable, "-m", "fuzzyheavyhitters_tpu.analysis",
            "fuzzyheavyhitters_tpu", "--no-baseline",
            "--root", str(tmp_path),
        ],
        cwd=str(tmp_path), capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "bare-print" in proc.stdout
    (bad / "mod.py").write_text("def f(x):\n    return x\n")
    proc = subprocess.run(
        [
            sys.executable, "-m", "fuzzyheavyhitters_tpu.analysis",
            "fuzzyheavyhitters_tpu", "--no-baseline",
            "--root", str(tmp_path),
        ],
        cwd=str(tmp_path), capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
