"""BENCHMARK.json against the files it names, and the command's gates."""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import doors, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_has_its_file_and_every_file_agrees(manifest):
    bench = os.path.join(ROOT, manifest["paths"][0])
    configs = {c["name"]: c for c in manifest["configs"]}
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert os.path.exists(os.path.join(bench, "systems", config["system"] + ".py"))
        groups = doors.model_groups(config)
        assert groups, "a configuration has at least one model group that names its family"
        for group in groups.values():
            assert os.path.exists(os.path.join(bench, "families", group["family"] + ".py"))
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        with open(os.path.join(bench, "workloads", w["name"] + ".json")) as f:
            wl = json.load(f)
        assert wl["config"] == w["config"] and w["config"] in configs
        assert os.path.exists(os.path.join(bench, "traffic", wl["kind"] + ".py"))
        assert os.path.exists(os.path.join(bench, "checks", wl["check"]["kind"] + ".py"))
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["per_layer"]:
        with open(os.path.join(bench, "metrics", m["name"] + ".json")) as f:
            decl = json.load(f)
        for key in ("unit", "better", "source", "layer", "moves"):
            assert decl[key] == m[key], (m["name"], key)
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(bench, "readers", decl["reader"] + ".py"))
    declared = {os.path.basename(p)[:-5] for p in glob.glob(os.path.join(bench, "metrics", "*.json"))}
    assert declared == {m["name"] for m in manifest["per_layer"]}


def test_a_metric_with_a_list_is_those_cells_and_one_with_no_list_is_every_cells(manifest):
    """An end-to-end metric that lists its cells is theirs alone and its list is
    never empty (the driver refuses a metric that no cell reports, so a new one
    comes with its first cell); ``setup_s`` has no list and is every cell's."""
    declared = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    assert "workloads" not in declared["setup_s"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert "workloads" not in m or (m["workloads"] and set(m["workloads"]) <= cells), m["name"]
    for w in manifest["workloads"]:
        names = {m["name"] for m in run.load_cell(manifest, ROOT, w["name"])["end_to_end"]}
        assert names == {n for n, m in declared.items() if w["name"] in m.get("workloads", cells)}
        assert "setup_s" in names and len(names) >= 2
    # a later `benchmark` PR declares one with its first cell: that cell reports it and no other does
    first, *others = sorted(cells)
    later = dict(manifest, end_to_end=[*manifest["end_to_end"], dict(declared["retrieve_p50_ms"], name="answer_p50_ms", workloads=[first])])
    assert "answer_p50_ms" in {m["name"] for m in run.load_cell(later, ROOT, first)["end_to_end"]}
    for other in others:
        assert "answer_p50_ms" not in {m["name"] for m in run.load_cell(later, ROOT, other)["end_to_end"]}


def test_a_name_with_no_file_is_an_error_that_names_the_file():
    with pytest.raises(doors.MissingKind, match=r"benchmark/families/no_such_family\.py does not exist"):
        doors.family({"family": "no_such_family"})
    with pytest.raises(doors.MissingKind, match="names nothing"):
        doors.find("systems", None, "configs/c.json `system`")


def test_a_full_check_fits_its_budget_with_24_cells(manifest):
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_bounds(manifest):
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1


def _run(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_the_command_refuses_a_cpu_and_prints_no_result(manifest):
    cell = manifest["workloads"][0]["name"]
    proc = _run(["benchmark/run.py", "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode == 2 and proc.stdout.strip() == "" and "refusing" in proc.stderr


def test_the_command_refuses_a_bare_directory(manifest, tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark", ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    cell = manifest["workloads"][0]["name"]
    proc = _run(["benchmark/run.py", "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
