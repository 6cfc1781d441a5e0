"""The repo's account of itself: the front page and ``docs/`` name files
that exist, and the program and the benchmark divide by the same peaks.

No JAX work.  ``test_document_names_what_exists`` stands where the README
table's drift gate stood: the table is written by hand from
``PERF_LEDGER.jsonl`` now, and what can still rot without a test is a
document citing a file that is gone.
"""

import glob
import json
import os
import re

import pytest

from paddle_tpu.analysis.flops import chip_peak_bandwidth, chip_peak_flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: directories a document's path is looked up under, as written
_ROOTS = ("paddle_tpu/", "tests/", "benchmark/", "demo/", "docs/", "csrc/")
#: the root benchmark script, its README generator and its captures, which
#: went in PR 46 (spelled so that a grep for the names finds no file;
#: the module ``paddle_tpu.models.image_bench`` stays and is not meant)
_GONE = re.compile(r"(?<!\w)bench\.py|readme[_]bench|BENCH[_]r")

_DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))


@pytest.mark.parametrize("key,of_the_program", [
    ("bf16_flops_per_s", chip_peak_flops),
    ("hbm_bytes_per_s", chip_peak_bandwidth),
], ids=["flops", "bandwidth"])
def test_program_and_benchmark_divide_by_the_same_peak(key, of_the_program):
    """The live ``train_mfu`` gauge (``analysis.flops``) and the ledger's
    ``mfu_pct`` (``benchmark/peaks.json``) are the same fraction only while
    the two tables agree.  ``hbm_bytes`` is not compared: it is the
    published 16e9 there and 16 GiB in the program (``lint --hbm``'s
    denominator), by design."""
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks
    for kind, row in peaks.items():
        assert of_the_program(kind) == row[key], (kind, key)


def _paths_named(text):
    """Backticked tokens that read as a path of this repo, each as
    ``(token, path to look up)``."""
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    for token in re.findall(r"`([^`\n]+)`", text):
        if re.search(r"[\s<*…]", token):
            continue
        path = token.split("::")[0].split("{")[0]
        path = re.sub(r":[\d,:-]+$", "", path)
        if path.startswith(_ROOTS):
            yield token, path
        elif re.fullmatch(r"(?:[\w.-]+/)+[\w.-]+\.py", path):
            yield token, os.path.join("paddle_tpu", path)


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_document_names_what_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    missing = sorted({token for token, path in _paths_named(text)
                      if not os.path.exists(os.path.join(ROOT, path))})
    assert not missing, f"{document} names paths that do not exist: {missing}"
    gone = sorted(set(_GONE.findall(text)))
    assert not gone, f"{document} still cites {gone} (deleted in PR 46)"
