"""Every file a document names exists: README.md, PARITY.md and each
docs/*.md may cite a `*.py`, `*.json`, `*.md` or `*.sh` file (in backticks, as
a link's target, or as a `python <file>` line of a fenced block) only if the
tree holds it, so a deletion cannot leave a document pointing at nothing. A
path is relative to the root, to the document's directory, or to the package
(`ops/core_ops.py` is `paddle_tpu/ops/core_ops.py`)."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PARITY.md"] + sorted(
    os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
)

# a path: word characters, dots, dashes and slashes ending in one of the four
# suffixes; `<...>` placeholders and globs are not paths
_PATH = re.compile(r"(?<![\w<*./-])([\w.-]+(?:/[\w.-]+)*\.(?:py|json|md|sh))(?![\w*>-])")
_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_CODE = re.compile(r"`([^`]+)`")
_LINK = re.compile(r"\]\(([^)\s#]+)")
_COMMAND = re.compile(r"^\s*(?:\$ )?(?:[A-Z_]+=\S+ )*python3? (\S+\.py)", re.M)

# Files the program writes at run time (checkpoint and repository manifests,
# flight-recorder bundles, a replica's spec): named in the documents, never in
# the tree.
RUNTIME = {
    "LATEST.json", "MANIFEST.json", "EMBEDDING_MANIFEST.json", "env.json",
    "event.json", "metrics.json", "perfetto_trace.json", "spec.json",
}
# The reference's own tree, which PARITY.md and MIGRATION.md map to this one.
REFERENCE = re.compile(r"^(\.\.\./|paddle/|python/paddle/|go/|benchmark/fluid/|fluid/)")


def named_paths(text):
    """Paths in the prose's backticks and links; of a fenced block (a layout,
    a session) only the files its `python` lines run."""
    prose = _FENCE.sub("", text)
    spans = _CODE.findall(prose) + _LINK.findall(prose)
    paths = {m for span in spans for m in _PATH.findall(span)}
    for block in _FENCE.findall(text):
        paths.update(_COMMAND.findall(block))
    return sorted(paths)


def resolves(path, doc):
    if path in RUNTIME or REFERENCE.match(path):
        return True
    return any(
        os.path.exists(os.path.normpath(os.path.join(ROOT, base, path)))
        for base in ("", os.path.dirname(doc), "paddle_tpu"))


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    with open(os.path.join(ROOT, doc)) as fh:
        paths = named_paths(fh.read())
    assert paths, "%s names no file: the extractor is broken" % doc
    dangling = [p for p in paths if not resolves(p, doc)]
    assert not dangling, "%s names files the tree does not hold: %s" % (
        doc, dangling)
