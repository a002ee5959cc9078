"""Env hygiene, two invariants:

1. No raw getenv outside common/env.h. Every TPUCOLL_* knob must go
   through the strict parsers (envBytes/envCount/envFlag/envChoice/
   envString) so malformed values throw loudly instead of atoll-ing
   "8MB" into 8 — the exact misconfiguration class PR 6 made the
   transport knobs immune to.

2. Code <-> docs agreement on the TPUCOLL_* surface: every variable the
   code reads is documented somewhere under docs/ (the matrix lives in
   docs/env.md), and every variable the docs name is actually read by
   code — a doc describing a deleted knob is worse than no doc.
"""

from __future__ import annotations

import re
from typing import Dict, List, Set, Tuple

from ..engine import Corpus, Rule, Violation

ENV_HEADER = "csrc/tpucoll/common/env.h"

# The strict accessors defined by common/env.h; reads through these are
# the sanctioned way to consult the environment from C++.
ACCESSORS = ("envBytes", "envCount", "envFlag", "envChoice", "envString")

_PY_READ = re.compile(
    r"""(?:os\.environ(?:\.get)?|os\.getenv|environ(?:\.get)?
        |\benv(?:\.get)?)\s*
        [\(\[]\s*f?['"](TPUCOLL_\w+)""", re.X)
_DOC_VAR = re.compile(r"\b(TPUCOLL_\w+)\b")


class EnvHygieneRule(Rule):
    name = "env-hygiene"
    description = ("no raw getenv outside common/env.h; the TPUCOLL_* "
                   "surface read by code and the one described in docs/ "
                   "are the same set")

    env_header = ENV_HEADER
    cpp_roots = ("csrc/tpucoll/**/*.cc", "csrc/tpucoll/**/*.h",
                 "csrc/tpucoll/*.cc", "csrc/tpucoll/*.h")
    py_roots = ("gloo_tpu/**/*.py", "gloo_tpu/*.py", "tools/*.py")
    doc_roots = ("docs/*.md", "README.md")

    def run(self, corpus: Corpus) -> List[Violation]:
        out: List[Violation] = []
        code_vars: Dict[str, Tuple[str, int]] = {}   # var -> first site

        cpp_paths: List[str] = []
        for pat in self.cpp_roots:
            cpp_paths.extend(corpus.glob(pat))
        for path in sorted(set(cpp_paths)):
            cpp = corpus.cpp(path)
            if cpp is None:
                continue
            # (1) raw getenv bans. ::getenv, std::getenv, secure_getenv
            # all count; common/env.h is the single sanctioned caller.
            if path != self.env_header:
                for m in re.finditer(r"\b(?:secure_)?getenv\s*\(",
                                     cpp.code):
                    line = cpp.line_of(m.start())
                    if line in cpp.if0_lines:
                        continue
                    fn = self._enclosing(cpp, line)
                    out.append(self.violation(
                        f"raw-getenv:{path}:{fn}", path, line,
                        f"raw getenv in {fn} — route the read through "
                        f"the strict parsers in {self.env_header} "
                        f"(envBytes/envCount/envFlag/envChoice/"
                        f"envString)"))
            # (2a) vars read through the sanctioned accessors.
            for acc in ACCESSORS:
                for line, var in cpp.string_args(acc):
                    if var.startswith("TPUCOLL_"):
                        code_vars.setdefault(var, (path, line))
            # Raw getenv reads still contribute to the doc cross-check
            # (the var is real even while the accessor is wrong).
            for m in re.finditer(
                    r'getenv\s*\(\s*"(TPUCOLL_\w+)"',
                    cpp.code_keep_strings):
                code_vars.setdefault(m.group(1),
                                     (path, cpp.line_of(m.start())))

        py_paths: List[str] = []
        for pat in self.py_roots:
            py_paths.extend(corpus.glob(pat))
        for path in sorted(set(py_paths)):
            text = corpus.text(path)
            if text is None:
                continue
            for m in _PY_READ.finditer(text):
                line = text.count("\n", 0, m.start()) + 1
                code_vars.setdefault(m.group(1), (path, line))

        doc_vars: Dict[str, Tuple[str, int]] = {}
        doc_paths: List[str] = []
        for pat in self.doc_roots:
            doc_paths.extend(corpus.glob(pat))
        for path in sorted(set(doc_paths)):
            text = corpus.text(path)
            if text is None:
                continue
            for m in _DOC_VAR.finditer(text):
                line = text.count("\n", 0, m.start()) + 1
                doc_vars.setdefault(m.group(1), (path, line))

        for var in sorted(set(code_vars) - set(doc_vars)):
            path, line = code_vars[var]
            out.append(self.violation(
                f"undocumented:{var}", path, line,
                f"{var} is read by code but appears nowhere under "
                f"docs/ — add it to the env matrix (docs/env.md)"))
        for var in sorted(set(doc_vars) - set(code_vars)):
            path, line = doc_vars[var]
            out.append(self.violation(
                f"docs-only:{var}", path, line,
                f"{var} is documented but never read by csrc/ or "
                f"gloo_tpu/ — stale doc, or the knob lost its reader"))
        return out

    @staticmethod
    def _enclosing(cpp, line: int) -> str:
        best = "<file scope>"
        for fn in cpp.functions():
            if fn.line <= line and cpp.line_of(
                    len(cpp.code)) >= line:
                # closest preceding definition whose body spans the line
                body_start = fn.body_line
                body_end = body_start + fn.body.count("\n")
                if body_start <= line <= body_end + 1:
                    best = fn.name
        return best
