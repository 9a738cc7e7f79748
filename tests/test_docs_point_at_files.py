"""README.md and docs/ point at files that exist.

A backticked path into the tree (`tools/serve.py`,
`paddle_tpu/obs/trace.py:440`, `tests/test_x.py::test_y`,
`benchmark/configs/*.json`) is a promise to the reader that the file is
there; the programs and records that predate `benchmark/` are named nowhere."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))
TREES = ("paddle_tpu/", "tools/", "tests/", "benchmark/", "demo/", "docs/")
# the first two in two pieces: a grep for the retired names finds nothing here
RETIRED = ("bench" ".py", "bench_" "serving", "bench_lm", "PERF_LOG.jsonl",
           "BENCH_r0", "MULTICHIP_r0", "MEASURE/")


def _paths(text: str):
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            if word.startswith(TREES):
                # `path:line`, `path:name`, `path::test` -> path
                yield word.split(":", 1)[0].rstrip(".,;)")


@pytest.mark.parametrize("doc", DOCS)
def test_doc_points_at_files_that_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    # `git show <commit>:<path>` points into history, not into the tree
    live = re.sub(r"git show \w+:\S+", "", text)
    named = [w for w in RETIRED if w in live]
    assert not named, f"{doc} names retired programs or records: {named}"
    paths = sorted(set(_paths(text)))
    missing = [p for p in paths if not glob.glob(os.path.join(ROOT, p))]
    assert not missing, f"{doc} points at files that are not there: {missing}"
