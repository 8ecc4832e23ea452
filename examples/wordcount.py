"""Streaming wordcount over a jsonlines directory.

The canonical demo graph, as a standalone program.  Lintable without running: ``python -m pathway_tpu.cli lint
examples/wordcount.py``.  The analyzer's accepted warnings for it live
in ``scripts/lint_baseline.json``: a file source feeding a groupby is a
full exchange (PW-X002) and unwindowed state (PW-S001) — both are the
point of the demo, not bugs.
"""

import json
import os
import tempfile

import pathway_tpu as pw


class WordSchema(pw.Schema):
    word: str


data_dir = tempfile.mkdtemp(prefix="pw_wordcount_")
with open(os.path.join(data_dir, "words.jsonl"), "w", encoding="utf-8") as f:
    for w in ["to", "be", "or", "not", "to", "be"]:
        f.write(json.dumps({"word": w}) + "\n")

words = pw.io.jsonlines.read(data_dir, schema=WordSchema, mode="static")
counts = words.groupby(pw.this.word).reduce(
    pw.this.word, n=pw.reducers.count()
)


def on_change(key, row, time, is_addition):
    if is_addition:
        print(f"{row['word']}: {row['n']}")


pw.io.subscribe(counts, on_change=on_change)
pw.run()
