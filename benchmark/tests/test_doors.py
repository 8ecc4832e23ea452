"""A later PR brings a second model family, system kind, check kind and
traffic kind as new files and new entries alone, and the harness finds each
by its name.

In a temporary copy of ``BENCHMARK.json`` and ``benchmark/`` this test does
what such a PR may do and nothing else: it writes new files (a family that
re-exports ``bert_encoder`` with another ``flops``, a system kind that
subclasses ``vector_store``, a check kind with one number of its own, a
traffic kind that reports ``answer_p50_ms``, a toy configuration and a cell
that name all four) and adds entries to ``BENCHMARK.json`` (the
configuration, the cell, and ``answer_p50_ms`` itself with the cell in its
list: the driver refuses an end-to-end metric that no cell reports, so the
metric comes as a new entry with its first cell, which a ``benchmark`` PR may
add and which changes no entry that was there).
The cell then runs through ``rehearse_cpu.rehearse`` on the CPU, in a process
of its own whose ``benchmark`` package is the copy.  Four more cells each
name one kind that has no file: each has to end in its line, ``correct``
false, the missing file named.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "door-toy.answer"

NEW_FILES = {
    "benchmark/families/door_encoder.py": '''"""bert_encoder under another name, its work reckoned otherwise."""
from benchmark.families import bert_encoder
from benchmark.families.bert_encoder import built_differs, embed, make_params, stack_layers, token_ids  # noqa: F401


def flops(group, useful_tokens):
    return 3.0 * bert_encoder.flops(group, useful_tokens)
''',
    "benchmark/systems/door_store.py": '''"""vector_store, subclassed."""
from benchmark.systems import vector_store


class System(vector_store.System):
    def start(self):
        super().start()
        self.started_as = "door_store"
''',
    "benchmark/checks/door_check.py": '''"""retrieval's numbers and one of its own."""
from benchmark.checks import retrieval


def collect(system, traffic, workload):
    return dict(retrieval.collect(system, traffic, workload), started_as=system.started_as)


def numbers(collected, params, config, workload, seed, precision="f32"):
    out = retrieval.numbers(collected, params, config, workload, seed, precision=precision)
    k = collected["sample"]["k"]
    out["short_answers"] = sum(1 for _q, hits in collected["sample"]["all_answers"] if len(hits or []) != k)
    out["not_door_store"] = int(collected["started_as"] != "door_store")
    return out
''',
    "benchmark/traffic/door_answer.py": '''"""retrieve_open, its median reported as answer_p50_ms."""
from benchmark.traffic import retrieve_open


class Traffic(retrieve_open.Traffic):
    def run_window(self):
        window = super().run_window()
        window["metrics"] = {"answer_p50_ms": window["metrics"].pop("retrieve_p50_ms")}
        return window
''',
}

BROKEN = {  # cell -> (the key that names a kind with no file, the file the error has to name)
    "door-toy.no-system": ("system", "benchmark/systems/no_such_kind.py"),
    "door-toy.no-family": ("family", "benchmark/families/no_such_kind.py"),
    "door-toy.no-check": ("check", "benchmark/checks/no_such_kind.py"),
    "door-toy.no-traffic": ("traffic", "benchmark/traffic/no_such_kind.py"),
}

DRIVER = """
import json, sys
from benchmark import doors, rehearse_cpu, run
assert rehearse_cpu.ROOT == sys.argv[1], (rehearse_cpu.ROOT, sys.argv[1])
out = {}
for cell in sys.argv[2:]:
    out[cell] = rehearse_cpu.rehearse(cell, seed=2**31 + 77, seconds=2.0, trace=False)
manifest = json.load(open("BENCHMARK.json"))
config = run.load_cell(manifest, sys.argv[1], sys.argv[2])["config"]
out["flops"] = [doors.model_flops(config, [16, 32]), doors.model_flops(dict(config, model=dict(config["model"], family="bert_encoder")), [16, 32])]
print("RESULT " + json.dumps(out))
"""


def _digests(top: str) -> dict:
    out = {}
    for folder, _dirs, files in os.walk(top):
        if "__pycache__" in folder or ".scratch" in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(f.read()).hexdigest()
    return out


def _json(path):
    with open(path) as f:
        return json.load(f)


def _write_json(path, doc) -> None:
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("doors"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(tmp, "benchmark"), ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    before = _digests(tmp)
    manifest_before = _json(os.path.join(tmp, "BENCHMARK.json"))

    # ---- what a later PR may do: new files ...
    for rel, text in NEW_FILES.items():
        assert rel not in before
        with open(os.path.join(tmp, rel), "w") as f:
            f.write(text)
    config = _json(os.path.join(tmp, "benchmark", "rehearsal", "configs", "toy.json"))
    workload = _json(os.path.join(tmp, "benchmark", "rehearsal", "workloads", "toy.retrieve.json"))
    cells = {CELL: ("door-toy", {"system": "door_store", "family": "door_encoder", "check": "door_check", "traffic": "door_answer"})}
    for cell, (key, _file) in BROKEN.items():
        cells[cell] = (cell.replace(".", "-"), {**cells[CELL][1], key: "no_such_kind"})
    manifest = _json(os.path.join(tmp, "BENCHMARK.json"))
    for cell, (cfg_name, kinds) in cells.items():
        cfg = dict(config, name=cfg_name, system=kinds["system"], model=dict(config["model"], family=kinds["family"]))
        wl = dict(workload, config=cfg_name, kind=kinds["traffic"], check=dict(workload["check"], kind=kinds["check"]))
        wl["limits"] = dict(workload["limits"], short_answers=0, not_door_store=0)
        _write_json(os.path.join(tmp, "benchmark", "configs", cfg_name + ".json"), cfg)
        _write_json(os.path.join(tmp, "benchmark", "workloads", cell + ".json"), wl)
        # ... and new entries
        manifest["configs"].append({"name": cfg_name, "source": "none: a test's toy", "file": f"benchmark/configs/{cfg_name}.json", "reduced": [], "why": "test_doors"})
        manifest["workloads"].append({"name": cell, "config": cfg_name, "traffic": kinds["traffic"], "chips": 1, "why": "test_doors"})
    manifest["end_to_end"].append({"name": "answer_p50_ms", "unit": "ms", "better": "lower", "bound": 0.1, "source": "host_clock", "workloads": list(cells)})
    _write_json(os.path.join(tmp, "BENCHMARK.json"), manifest)

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)  # the program comes from the repo, benchmark/ from the copy
    proc = subprocess.run([sys.executable, "-c", DRIVER, tmp, CELL, *BROKEN], cwd=tmp, env=env, capture_output=True, text=True, timeout=600)
    result = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and result, proc.stderr[-4000:]
    return {
        "out": json.loads(result[-1][len("RESULT "):]),
        "stderr": proc.stderr,
        "before": before,
        "after": _digests(tmp),
        "manifest_before": manifest_before,
        "manifest_after": manifest,
    }


def test_the_new_cell_runs_on_new_files_alone(ran):
    line = ran["out"][CELL]
    assert all(k in line for k in ("correct", "attempted", "failed", "metrics", "device", "compared")), line
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0, (line, ran["stderr"][-3000:])
    assert set(line["metrics"]) == {"setup_s", "answer_p50_ms"}
    assert line["metrics"]["answer_p50_ms"]["unit"] == "ms" and line["metrics"]["answer_p50_ms"]["value"] > 0
    # the check kind's own numbers beside retrieval's, each with its limit
    assert line["compared"]["short_answers"] == {"value": 0, "limit": 0}
    assert line["compared"]["not_door_store"] == {"value": 0, "limit": 0}
    assert {"emb_gap", "score_gap", "rank_gap", "wrong"} <= set(line["compared"])
    # the family's own flops are what the readers are given
    changed, plain = ran["out"]["flops"]
    assert changed == 3.0 * plain > 0


def test_no_file_that_was_there_has_changed(ran):
    before, after = ran["before"], ran["after"]
    changed = sorted(p for p in before if p != "BENCHMARK.json" and after.get(p) != before[p])
    assert not changed, changed
    added = sorted(set(after) - set(before))
    assert set(NEW_FILES) <= set(added) and all(p.startswith("benchmark/") for p in added)
    assert not [p for p in added if not p.startswith(("benchmark/families/", "benchmark/systems/", "benchmark/checks/", "benchmark/traffic/", "benchmark/configs/", "benchmark/workloads/"))]
    # BENCHMARK.json gained entries and lost or changed none
    old, new = ran["manifest_before"], ran["manifest_after"]
    for key in ("command", "paths", "run_seconds", "per_layer"):
        assert new[key] == old[key]
    for key in ("configs", "workloads"):
        assert new[key][: len(old[key])] == old[key]
    assert new["end_to_end"][: len(old["end_to_end"])] == old["end_to_end"]
    (added_metric,) = new["end_to_end"][len(old["end_to_end"]) :]
    assert added_metric["name"] == "answer_p50_ms" and added_metric["workloads"] == [CELL, *BROKEN]


@pytest.mark.parametrize("cell", sorted(BROKEN))
def test_a_name_with_no_file_ends_the_run_in_its_line(ran, cell):
    line = ran["out"][cell]
    assert line["correct"] is False and line["failed"] >= 1 and line["attempted"] >= line["failed"], line
    assert all(k in line for k in ("correct", "attempted", "failed", "metrics", "device", "compared"))
    assert BROKEN[cell][1] in line["error"] and "MissingKind" in line["error"], line["error"]
