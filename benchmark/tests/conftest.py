"""The benchmark's own tests: everything on XLA:CPU, at tiny sizes.

Run as ``python -m pytest benchmark/tests -q -p no:cacheprovider`` from the
root of the checkout.  The flags are those of ``tests/conftest.py`` (one
virtual device is enough here; the fusion-emitter flag is what lets
interpret-mode Pallas kernels return on this jaxlib).  The platform check of
``run.py`` is patched in the tests, never in the program or the harness.
"""

import json
import os
import shutil
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_cpu_use_fusion_emitters" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_backend_optimization_level=1"
        " --xla_cpu_use_fusion_emitters=false"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

import manifest  # noqa: E402
import run  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "rehearsal", "count": 1}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A copy of the benchmark with a tiny configuration, a mix, two cells and
    a span-read metric ADDED as new files and new entries, nothing edited:
    what a later PR may do.  ``manifest.ROOT`` points at it for the test."""
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, "configs", "zipf-flagship-trusted.json"), encoding="utf-8") as f:
        flagship = json.load(f)
    cells = []
    for lane_name, secure, evidence in (
        ("trusted", False, [{"counter": "gc_tests", "per_level": "zero"}]),
        ("secure", True, [{"counter": "gc_tests", "per_level": "positive"},
                          {"counter": "ot_path_ot2s", "per_level": "positive"}]),
    ):
        name = f"tiny-{lane_name}"
        conf = dict(flagship, name=name, clients=256, lane=lane_name, lane_evidence=evidence,
                    reduced=["clients", "data_len", "num_sites"],
                    config=dict(flagship["config"], data_len=16, num_sites=8,
                                threshold=0.03, f_max=64, secure_exchange=secure))
        _write(os.path.join(root, "benchmark", "configs", f"{name}.json"), conf)
        bench["configs"].append({
            "name": name, "source": "a test", "file": f"benchmark/configs/{name}.json",
            "reduced": conf["reduced"], "why": "rehearsal size"})
        bench["workloads"].append({
            "name": name, "config": name, "traffic": "tiny-levels", "chips": 1,
            "why": "rehearsal"})
        cells.append(name)
    _write(os.path.join(root, "benchmark", "traffic", "tiny-levels.json"), {
        "name": "tiny-levels", "kind": "crawl_levels",
        "warmup": {"steady_levels": 2, "min_levels": 17, "max_levels": 17},  # a whole crawl, its end included
        "window": {"restart_when_crawl_ends": True}, "tail": {"max_s": 5.0},
        "trace": {"start_after_s": 0.0, "capture_s": 0.2}})
    # the cells' own rate under a bound of its own: ``<reading>.<tag>``
    bench["end_to_end"].append({
        "name": "crawl_clients_per_s.tiny", "unit": "clients/s", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": cells})
    verb = {"name": "verb_ms_per_level", "unit": "ms", "better": "lower", "layer": "wire",
            "source": "program_span", "moves": "crawl_clients_per_s.tiny", "workloads": cells}
    _write(os.path.join(root, "benchmark", "metrics", "verb_ms_per_level.json"), dict(
        verb, reader="span_ms_per_level",
        args={"spans": ["verb:tree_prune"], "servers": "mean", "levels": "mean"}))
    bench["per_layer"].append(verb)
    # metrics that are there, again for the new cells: ``<metric>.<tag>``, an
    # entry and no file
    for m in list(bench["per_layer"]):
        if m["moves"] == "crawl_clients_per_s" and m["name"] != "otext_ms_per_level":
            bench["per_layer"].append(dict(
                m, name=m["name"] + ".tiny", moves="crawl_clients_per_s.tiny", workloads=cells))
    bench["per_layer"].append({
        "name": "crawl_clients_per_s.trusted.tiny", "unit": "clients/s", "better": "higher",
        "source": "host_clock", "layer": "leader and wire", "moves": "setup_s",
        "workloads": ["tiny-trusted"]})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    monkeypatch.setattr(manifest, "ROOT", root)
    return root


@pytest.fixture
def no_chip_check(monkeypatch):
    """The platform and engine check, patched in the TEST."""
    monkeypatch.setattr(run, "require_tpu", lambda chips: dict(DEVICE))
