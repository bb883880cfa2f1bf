"""``bench/run.py`` refuses to report off a TPU, and BENCHMARK.json's
parts are all where the harness looks for them."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = harness.CHECKOUT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pubmed-journals.fit",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


def test_off_a_tpu_it_exits_non_zero_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "needs 1 TPU chip" in proc.stderr


def test_with_only_the_benchmark_files_it_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_parts(workload):
    cell = harness.load_cell(workload)
    assert harness.driver_of(cell).setup
    assert cell.limits and cell.end_to_end and cell.per_layer
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in cell.end_to_end + cell.per_layer:
        assert harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py").read


def _one_line(text) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and not re.search(r"[\t\n\r]", text)


def test_names_units_and_lengths_keep_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(_one_line(word) for word in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
    assert isinstance(SPEC["run_seconds"], int) \
        and 1 <= SPEC["run_seconds"] <= 51
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, shown in keys.items():
        for entry in SPEC[section]:
            extra = {"workloads"} if section in ("end_to_end",
                                                 "per_layer") else set()
            assert shown <= set(entry) <= shown | extra, entry
    named = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
        + SPEC["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in SPEC["per_layer"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"]) and layers
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [entry["name"] for entry in SPEC[section]]
        assert len(names) == len(set(names)), section
    assert all(_one_line(m["layer"]) for m in SPEC["per_layer"])
    assert all(m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
               for m in SPEC["per_layer"])
    cells = {w["name"]: w for w in SPEC["workloads"]}
    reports = {name: {m["name"] for m in SPEC["end_to_end"]
                      if name in m.get("workloads", cells)}
               for name in cells}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells), m["name"]
    for m in SPEC["per_layer"]:
        assert all(m["moves"] in reports[c]
                   for c in m.get("workloads", cells)), m["name"]
    assert 1 <= len(SPEC["configs"]) <= 24
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert any(w["config"] == c["name"] for w in cells.values())
    assert 1 <= len(cells) <= 24
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    assert len(pairs) == len(cells)
    for w in cells.values():
        assert _one_line(w["why"]) and w["chips"] in (1, 4)
        assert "setup_s" in reports[w["name"]] and len(reports[w["name"]]) >= 2
    for path in harness.BENCH.rglob("*"):
        if "__pycache__" not in path.parts:
            rel = path.relative_to(ROOT).as_posix()
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
