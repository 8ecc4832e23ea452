"""How ``get_tokenizer`` decides that a local HuggingFace tokenizer is
absent: by looking for its files (``local_tokenizer_dir``), so that a
process with none never imports ``transformers`` (and, through it,
``torch``): 18 s of every server start until PR 36.

Nothing here loads ``transformers``: the absent case runs in a child
process whose caches point at an empty directory, and the present case
swaps ``HFTokenizer`` for a recorder.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from pathway_tpu.internals import device_counters, tracing
from pathway_tpu.models import tokenizer as tok_mod
from pathway_tpu.models.tokenizer import (
    HashTokenizer,
    get_tokenizer,
    hub_cache_roots,
    local_tokenizer_dir,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: every variable a cache root is read from, in the order they are looked
#: at, and what its value is joined with to give the hub cache
_BELOW = {
    "HF_HUB_CACHE": (),
    "HUGGINGFACE_HUB_CACHE": (),
    "TRANSFORMERS_CACHE": (),
    "HF_HOME": ("hub",),
    "XDG_CACHE_HOME": ("huggingface", "hub"),
    "HOME": (".cache", "huggingface", "hub"),
}
_CACHE_VARS = tuple(_BELOW)


@pytest.fixture
def no_caches(monkeypatch, tmp_path):
    """A machine with no hub cache: ``HOME`` is an empty directory and no
    other variable is set."""
    for var in _CACHE_VARS:
        monkeypatch.delenv(var, raising=False)
    home = tmp_path / "home"
    home.mkdir()
    monkeypatch.setenv("HOME", str(home))
    return home


def _snapshot(hub, repo: str, *files: str, rev: str = "0123abc"):
    path = hub / repo / "snapshots" / rev
    path.mkdir(parents=True)
    for name in files:
        (path / name).write_text("{}")
    return path


# ---------------------------------------------------------------- (a) absent

_CHILD = """
import json, sys
import jax  # as a server has, before it builds its embedder
from pathway_tpu.models.tokenizer import get_tokenizer
out = {}
if sys.argv[1] == "get_tokenizer":
    out["tokenizer"] = type(get_tokenizer("bge-large", 30522)).__name__
else:
    import dataclasses
    from pathway_tpu.models import encoder
    from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder
    tiny = dataclasses.replace(encoder.MINILM_L6, hidden=32, layers=1, heads=2, mlp_dim=64)
    kwargs = {"config": tiny} if sys.argv[1] == "bge-large" else {}
    out["tokenizer"] = type(TPUEncoderEmbedder(sys.argv[1], **kwargs).encoder.tokenizer).__name__
out["loaded"] = [m for m in ("transformers", "torch", "huggingface_hub") if m in sys.modules]
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("what", ["get_tokenizer", "all-MiniLM-L6-v2", "bge-large"])
def test_no_local_tokenizer_means_no_transformers_import(what, tmp_path):
    """With empty caches the hashing stand-in is returned (by
    ``get_tokenizer`` alone, by ``TPUEncoderEmbedder()``'s default and by a
    preset's name) and the process holds neither ``transformers`` nor
    ``torch``."""
    env = {k: v for k, v in os.environ.items() if k not in _CACHE_VARS}
    env.update(
        HF_HOME=str(tmp_path / "hf"),
        HF_HUB_CACHE=str(tmp_path / "hf" / "hub"),
        XDG_CACHE_HOME=str(tmp_path / "xdg"),
        HOME=str(tmp_path),
        PYTHONPATH=ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    )
    done = subprocess.run(
        [sys.executable, "-c", _CHILD, what],
        env=env, cwd=str(tmp_path), capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = next(ln for ln in done.stdout.splitlines() if ln.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    assert out == {"tokenizer": "HashTokenizer", "loaded": []}


# ------------------------------------------------------- (b) where it looks


@pytest.mark.parametrize("files", [("tokenizer.json",), ("vocab.txt",), ("config.json", "spiece.model")])
def test_a_directory_that_holds_a_tokenizer_file(files, no_caches, tmp_path):
    model = tmp_path / "my-model"
    model.mkdir()
    for name in files:
        (model / name).write_text("{}")
    assert local_tokenizer_dir(str(model)) == str(model)


def test_a_directory_with_no_tokenizer_file(no_caches, tmp_path):
    model = tmp_path / "weights-only"
    model.mkdir()
    (model / "config.json").write_text("{}")
    (model / "model.safetensors").write_text("")
    assert local_tokenizer_dir(str(model)) is None


@pytest.mark.parametrize("var", _CACHE_VARS)
def test_a_snapshot_under_each_documented_root(var, no_caches, monkeypatch, tmp_path):
    base = tmp_path / ("at-" + var)
    hub = base.joinpath(*_BELOW[var])
    snap = _snapshot(hub, "models--bge-large", "tokenizer.json")
    monkeypatch.setenv(var, str(base))
    assert hub_cache_roots()[0] == str(hub)
    assert local_tokenizer_dir("bge-large") == str(snap)


def test_an_organisation_in_the_name(no_caches):
    hub = no_caches / ".cache" / "huggingface" / "hub"
    snap = _snapshot(hub, "models--BAAI--bge-large-en-v1.5", "vocab.txt", "tokenizer_config.json")
    assert local_tokenizer_dir("BAAI/bge-large-en-v1.5") == str(snap)
    assert local_tokenizer_dir("bge-large-en-v1.5") is None
    assert local_tokenizer_dir("other/bge-large-en-v1.5") is None


def test_a_snapshot_with_no_tokenizer_file(no_caches):
    hub = no_caches / ".cache" / "huggingface" / "hub"
    _snapshot(hub, "models--bge-large", "config.json", "model.safetensors")
    assert hub_cache_roots() == [str(hub)]
    assert local_tokenizer_dir("bge-large") is None


def test_a_later_snapshot_that_has_one(no_caches):
    hub = no_caches / ".cache" / "huggingface" / "hub"
    _snapshot(hub, "models--bge-large", "config.json", rev="0aaa")
    snap = _snapshot(hub, "models--bge-large", "vocab.json", rev="1bbb")
    assert local_tokenizer_dir("bge-large") == str(snap)


def test_roots_that_do_not_exist(no_caches, monkeypatch, tmp_path):
    monkeypatch.setenv("HF_HUB_CACHE", str(tmp_path / "nowhere"))
    monkeypatch.setenv("HF_HOME", str(tmp_path / "nor-here"))
    assert hub_cache_roots() == []
    assert local_tokenizer_dir("bge-large") is None


def test_roots_are_looked_through_in_the_documented_order(no_caches, monkeypatch, tmp_path):
    hubs = []
    for var in _CACHE_VARS:
        base = tmp_path / ("at-" + var)
        hub = base.joinpath(*_BELOW[var])
        hub.mkdir(parents=True)
        monkeypatch.setenv(var, str(base))
        hubs.append(str(hub))
    assert hub_cache_roots() == hubs
    # found under the last root though five roots before it hold nothing
    snap = _snapshot(tmp_path / "at-HOME" / ".cache" / "huggingface" / "hub", "models--m", "tokenizer.json")
    assert local_tokenizer_dir("m") == str(snap)


# --------------------------------------- (c) files found: the HF path, as before


class _Recorder:
    names: list[str] = []

    def __init__(self, name: str):
        type(self).names.append(name)


def test_files_found_builds_the_hf_tokenizer(no_caches, monkeypatch):
    _snapshot(no_caches / ".cache" / "huggingface" / "hub", "models--org--m", "tokenizer.json")
    monkeypatch.setattr(_Recorder, "names", [])
    monkeypatch.setattr(tok_mod, "HFTokenizer", _Recorder)
    assert isinstance(get_tokenizer("org/m", 30522), _Recorder)
    assert _Recorder.names == ["org/m"]  # by its name, as before: transformers resolves the revision
    # no files, no call
    assert isinstance(get_tokenizer("org/other", 30522), HashTokenizer)
    assert isinstance(get_tokenizer(None, 30522), HashTokenizer)
    assert _Recorder.names == ["org/m"]


def test_files_that_will_not_load_fall_back_to_the_stand_in(no_caches, monkeypatch, tmp_path):
    model = tmp_path / "broken"
    model.mkdir()
    (model / "tokenizer.json").write_text("not json")
    asked = []

    def refuse(name):
        asked.append(name)
        raise OSError("cannot load")

    monkeypatch.setattr(tok_mod, "HFTokenizer", refuse)
    tok = get_tokenizer(str(model), 1234)
    assert asked == [str(model)]
    assert isinstance(tok, HashTokenizer) and tok.vocab_size == 1234


# ------------------------------------------------------------ (d) the span


def _resolve_spans():
    return [e for e in tracing.chrome_events(all_spans=True) if e["name"] == "tokenizer_resolve"]


@pytest.mark.parametrize("kind", ["hash", "hf"])
def test_one_call_is_one_tokenizer_resolve_span(kind, no_caches, monkeypatch):
    hub = no_caches / ".cache" / "huggingface" / "hub"
    _snapshot(hub, "models--present", "tokenizer.json")
    monkeypatch.setattr(tok_mod, "HFTokenizer", _Recorder)
    tracing.reset()
    try:
        before = device_counters.snapshot().get("span_count.tokenizer_resolve", 0)
        get_tokenizer("present" if kind == "hf" else "absent", 30522)
        snap = device_counters.snapshot()
        assert snap["span_count.tokenizer_resolve"] - before == 1
        assert snap["span_ns.tokenizer_resolve"] > 0
        (event,) = _resolve_spans()
        assert event["args"]["kind"] == kind
        assert event["args"]["roots"] == [str(hub)]
    finally:
        tracing.reset()
