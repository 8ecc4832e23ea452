"""``chip_smoke.py`` and the rules that keep the program on the chip.

The smoke itself only passes on a TPU; what tier-1 can hold is that it
refuses everything else, that its body is sound at a toy size, and that
the pieces it leans on (compile-cache placement, the per-dispatch row
bound, bucketed search k, the one-process-per-chip refusal) behave.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import BGE_LARGE, MINILM_L6

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dataclasses.replace(
    MINILM_L6, layers=2, hidden=64, heads=4, mlp_dim=128, dtype=jnp.float32
)


def _python(code_or_script: list[str], **env: str | None) -> subprocess.CompletedProcess:
    full = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    for key, value in env.items():
        if value is None:
            full.pop(key, None)
        else:
            full[key] = value
    return subprocess.run(
        [sys.executable, *code_or_script],
        env=full,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_chip_smoke_refuses_cpu_and_names_it():
    proc = _python([os.path.join(REPO, "chip_smoke.py")])
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line without a chip


def test_result_line_is_last_and_holds_only_ok_and_device(monkeypatch, capsys):
    """The driver parses the last stdout line and refuses any key beyond
    ``ok`` and ``device`` {platform, kind, count}; the report goes on the
    line before it."""
    import json
    import types

    import jax

    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [chip])
    model = {"layers": 24, "hidden": 1024, "heads": 16, "mlp_dim": 4096, "dtype": "bfloat16"}
    monkeypatch.setattr(chip_smoke, "run_smoke", lambda **kw: {"model": model, "requests_ok": 80})
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert json.loads(lines[-2]) == {
        "report": {"model": model, "requests_ok": 80},
        "claim": None,
    }

    def not_held(**kw):
        raise chip_smoke.SmokeFailure("a phase did not hold")

    monkeypatch.setattr(chip_smoke, "run_smoke", not_held)
    assert chip_smoke.main() == 1
    assert capsys.readouterr().out == ""


def test_smoke_body_runs_tiny_on_cpu(tmp_path):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)

    report = chip_smoke.run_smoke(
        model="tiny",
        config=TINY,
        workdir=str(tmp_path / "work"),
        n_chunks=400,
        n_upsert=40,
        n_queries=8,
        delta_cap=128,
        request_timeout_s=120,
    )
    assert report["platform"] == "cpu"  # main() is what refuses this
    assert report["chunks_indexed"] == 440
    assert report["main_size"] > 0
    assert report["main_size"] >= 400 - report["delta_cap"]
    assert report["compiles_second_pass"] == 0
    assert report["requests_ok"] >= 16
    assert {128, 512} <= set(report["query_length_buckets"])
    assert report["tokenizer"] == "HashTokenizer"


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_dir_rule(tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set, that directory and no other;
    unset, a fixed path inside the checkout."""
    want = str(tmp_path / "cache") if placed else os.path.join(REPO, ".jax_cache")
    proc = _python(
        ["-c", "import pathway_tpu.parallel, jax; print(jax.config.jax_compilation_cache_dir)"],
        JAX_COMPILATION_CACHE_DIR=want if placed else None,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == want


def test_rows_per_dispatch_bounds_attention_memory():
    """BGE-large's defaults (max_batch 1024, lengths to 512) put 17 GB of
    f32 attention logits into one dispatch as written (XLA fits the
    program into 10.8 GB of temporaries on a v5e); the executor splits by
    the padded length so the scores fit its budget, in power-of-two rows."""
    from pathway_tpu.parallel.executor import JittedEncoder

    enc = JittedEncoder(TINY)  # tiny params, BGE-large arithmetic below
    enc.config = BGE_LARGE
    for length in (16, 128, 512):
        rows = enc._rows_per_dispatch(length)
        assert rows & (rows - 1) == 0 and 8 <= rows <= enc.max_batch
        logits = rows * BGE_LARGE.heads * length * length * 4
        assert logits <= enc._dispatch_bytes
    assert enc._rows_per_dispatch(512) < enc._rows_per_dispatch(128) <= 1024
    assert enc._rows_per_dispatch(16) == enc.max_batch


@pytest.mark.parametrize("words", [0, 100], ids=["packs-into-one-dispatch", "packs-and-splits"])
def test_long_row_splits_the_batch_and_keeps_results(words):
    """One long text takes the batch to the 512 bucket, where a dispatch
    holds 8 rows.  The shorter texts share rows: forty of up to 42 tokens
    lie beside the long one in 3 rows, so nothing is left to split; forty
    of 103 to 142 tokens need 11 rows, and split."""
    from pathway_tpu.parallel.executor import JittedEncoder

    enc = JittedEncoder(TINY, max_batch=64)
    texts = [f"t{i} " + "w " * (words + i) for i in range(40)] + ["long " * 400]
    whole = enc.encode(texts)
    np.testing.assert_allclose(whole[-1:], enc.encode(texts[-1:]), atol=1e-5)
    enc._dispatch_bytes //= 64  # force a split at the 512 bucket
    assert enc._rows_per_dispatch(512) < len(texts)
    assert len(list(enc._chunks(texts, None))) == (2 if words else 1)
    np.testing.assert_allclose(enc.encode(texts), whole, atol=1e-5)


def test_search_k_is_bucketed_not_compiled_per_value():
    """A delta that grows by one row per query used to move the main
    segment's fetch, and with it a compiled program, per query."""
    from pathway_tpu.internals import device_counters
    from pathway_tpu.stdlib.indexing.adapters import KnnAdapter

    vecs = np.random.default_rng(0).normal(size=(300, 32)).astype(np.float32)
    adapter = KnnAdapter(32, capacity=512, delta_cap=128, auto_merge=False)
    adapter.add([(i, vecs[i]) for i in range(256)])
    adapter.search([vecs[0]], [10], [None])
    before = device_counters.compile_count()
    for i in range(256, 290):
        adapter.add([(i, vecs[i])])
        hits = adapter.search([vecs[i]], [10], [None])[0]
        assert hits[0][0] == i and len(hits) == 10
    # overwriting main rows shadows them: fetch grows, within one bucket
    adapter.add([(i, vecs[i + 1]) for i in range(5)])
    assert len(adapter.search([vecs[7]], [10], [None])[0]) == 10
    assert device_counters.compile_count() - before <= 1


def test_device_classes_refuse_a_multiprocess_run(monkeypatch):
    from pathway_tpu.internals.config import pathway_config
    from pathway_tpu.parallel import ShardedKnnIndex
    from pathway_tpu.parallel.executor import JittedEncoder

    monkeypatch.setattr(pathway_config, "processes", 2)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="belongs to\\s+one process"):
        JittedEncoder(TINY)
    with pytest.raises(RuntimeError, match="2 processes"):
        ShardedKnnIndex(8)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # pinned to the host on purpose
    assert len(ShardedKnnIndex(8)) == 0


def test_unknown_embedder_model_is_an_error():
    from pathway_tpu.xpacks.llm.embedders import TPUEncoderEmbedder

    with pytest.raises(ValueError, match="bge-large"):
        TPUEncoderEmbedder("bge-lagre")


def test_native_build_is_keyed_not_trusted_by_mtime():
    from pathway_tpu.internals import native

    if native.load() is None:
        pytest.skip("no native extension on this machine")
    assert native._build_key() in os.path.basename(native.load().__file__)
    assert "-march=native" in native._FLAGS  # why the CPU is part of the key
