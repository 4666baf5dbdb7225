"""One benchmark, one record of speed, and documents that cite what exists.

The benchmark is ``benchmark/`` (declared by ``BENCHMARK.json``) and the
record of speed is ``PERF_LEDGER.jsonl``.  A second benchmark or a JSON
record beside them is read by nothing and quoted as evidence anyway, so
the root is held to the one, and every file a document names in
backticks has to exist."""

import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ("README.md", "docs/serving.md", "docs/compile-cache.md",
        "docs/comms-compression.md", "docs/monitoring.md",
        "docs/static-analysis.md", "docs/tutorials/inference.md",
        "docs/tutorials/mixture-of-experts.md",
        "docs/tutorials/sparse-attention.md")
# where a document's short paths are rooted, besides its own directory
BASES = ("", "deepspeed_tpu", "deepspeed_tpu/runtime", "tests", "docs")
# files a document says a run WRITES: they exist only after that run
PRODUCED_BY_A_RUN = {
    "heartbeat.json",       # a router worker's liveness file (serving.md)
    "key_anatomy.json",     # the compile cache's key dump (compile-cache.md)
    "device_scopes.json",   # an AOT entry's map of device scopes (the same)
}
# a backticked span that is one path, with an optional :line, ::test or
# #anchor behind it
_PATH = re.compile(r"([\w./-]+\.(?:py|json|md))(?:[:#][\w:.,#-]*)?$")


def _at_root(pattern):
    """Root files of that pattern that git would commit (what
    ``.gitignore`` names is a run's or the driver's, not the repo's)."""
    with open(os.path.join(REPO, ".gitignore")) as fh:
        ignored = [ln.strip() for ln in fh if ln.strip()]
    names = (os.path.basename(p)
             for p in glob.glob(os.path.join(REPO, pattern)))
    return sorted(n for n in names
                  if not any(fnmatch.fnmatch(n, i) for i in ignored))


def test_root_holds_one_benchmark_and_no_other_record():
    assert _at_root("*.json") == ["BENCHMARK.json"]
    # no script beside the package and benchmark/: a second benchmark or
    # a smoke of its own would live here
    assert _at_root("*.py") == ["__graft_entry__.py"]


@pytest.mark.parametrize("doc", DOCS)
def test_document_cites_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as fh:
        text = fh.read()
    bases = BASES + (os.path.dirname(doc),)
    dead = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        m = _PATH.match(span)
        if m is None or m.group(1) in PRODUCED_BY_A_RUN:
            continue
        if not any(os.path.exists(os.path.join(REPO, b, m.group(1)))
                   for b in bases):
            dead.add(span)
    assert not dead, f"{doc} cites files that do not exist: {sorted(dead)}"
