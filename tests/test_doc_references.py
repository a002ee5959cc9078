"""Every file a document names in single backticks exists.

The documents are the README, each `docs/*.md` and the verify skill's
notes: a reader follows their backticked file names, so a name left
behind by a deleted file or a renamed module fails here, one case per
document, with all of that document's misses in one assertion.

A span is a file name when, after a trailing `::test_name`, `:N` or
`:N-M` is stripped, it matches `FILE`. Spans holding a placeholder or
glob character (`< > * { } $`) or starting with `/` are not checked.
A bare name exists when some tracked file has that basename; a path
when it resolves under one of `BASES`. Paths into the upstream
reference (`gloo/...`) are not in this repo and are skipped.
"""

import os
import re
import subprocess

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = (["README.md"]
        + sorted(os.path.join("docs", n)
                 for n in os.listdir(os.path.join(_REPO, "docs"))
                 if n.endswith(".md"))
        + [".claude/skills/verify/SKILL.md"])

FILE = re.compile(r"[A-Za-z0-9_.][A-Za-z0-9_./-]*"
                  r"\.(py|md|json|jsonl|cc|h|yml|toml|txt|sh)")
SPAN = re.compile(r"(?<!`)`([^`\n]+)`(?!`)")
FENCE = re.compile(r"^```.*?^```", re.S | re.M)
SUFFIX = re.compile(r"(::\w+|:\d+(-\d+)?)$")
BASES = ("", "gloo_tpu", "csrc", "csrc/tpucoll", "benchmark")

# Names that are not files of this repo, each with its reason.
NOT_OURS = {
    "docs/cuda.md": "the upstream reference's own doc, cited as the "
                    "counterpart of docs/device_plane.md",
}


def _basenames():
    try:
        out = subprocess.run(["git", "ls-files", "-z"], cwd=_REPO,
                             capture_output=True, check=True).stdout
    except (OSError, subprocess.CalledProcessError):
        # A checkout without git metadata: every file on disk.
        return {n for _, _, names in os.walk(_REPO) for n in names}
    return {os.path.basename(p) for p in out.decode().split("\0")
            if p and os.path.exists(os.path.join(_REPO, p))}


_BASENAMES = _basenames()


def _named_files(text):
    for m in SPAN.finditer(FENCE.sub("", text)):
        span = m.group(1)
        if span.startswith("/") or any(c in span for c in "<>*{}$"):
            continue
        name = SUFFIX.sub("", span)
        if FILE.fullmatch(name):
            yield span, name


def _exists(name):
    if name.startswith("gloo/") or name in NOT_OURS:
        return True
    if "/" not in name:
        return name in _BASENAMES
    return any(os.path.isfile(os.path.join(_REPO, base, name))
               for base in BASES)


@pytest.mark.parametrize("doc", DOCS)
def test_named_files_exist(doc):
    with open(os.path.join(_REPO, doc), encoding="utf-8") as f:
        text = f.read()
    missing = sorted({span for span, name in _named_files(text)
                      if not _exists(name)})
    assert not missing, f"{doc} names files that do not exist: {missing}"
