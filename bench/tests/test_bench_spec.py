"""``BENCHMARK.json`` keeps to the shape its harness and its checks rely
on, and every name in it resolves to a file of its own."""
import json
import os
import re

import pytest

import harness

SPEC = os.path.join(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(SPEC) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    assert os.path.getsize(SPEC) <= 64 * 1024


def test_names_units_and_text(spec):
    entries = (spec["configs"] + spec["workloads"] + spec["end_to_end"]
               + spec["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    texts = ([c[k] for c in spec["configs"] for k in ("why", "source")]
             + [w["why"] for w in spec["workloads"]]
             + [m["layer"] for m in spec["per_layer"]] + spec["command"])
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text, text
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[group]]
        assert len(names) == len(set(names)), group
    metric_names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_every_name_resolves_to_a_file(spec):
    bench = harness.BENCH
    used = set()
    for w in spec["workloads"]:
        used.add(w["config"])
        assert w["chips"] in (1, 4)
        traffic = os.path.join(bench, "traffic", w["traffic"] + ".json")
        assert os.path.isfile(traffic), traffic
        with open(traffic) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(bench, "drivers",
                                           driver + ".py"))
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))
    for c in spec["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_bounds_and_run_length(spec):
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert [m for m in spec["end_to_end"] if m["name"] == "setup_s"]
    r = spec["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    # a full check of 24 cells fits the driver's time
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_cell_reports_what_it_needs(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    layers = {}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for w in spec["workloads"]:
        cell = harness.Cell(spec, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names


def _run(cwd, *args):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_chip_no_result():
    p = _run(harness.ROOT, "--workload", "appc.fit", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""


def test_benchmark_alone_runs_nothing(tmp_path):
    """A directory with only BENCHMARK.json and ``bench/`` has no system
    under test: a nonzero exit and no result."""
    import shutil

    shutil.copy(SPEC, tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), "--workload", "appc.fit", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
