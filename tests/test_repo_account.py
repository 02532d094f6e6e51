"""The repo's account of itself names only what is in the tree (PR 29).

Three guards, none with an allow-list: a stale mention is repaired where
it stands.

- every document's links, path-like code tokens and `make` targets exist;
- every Makefile target's scripts and modules exist;
- there is one measuring stack: nothing outside `benchmark/` reads the
  deleted rungs' environment knobs, and the library's peak table and the
  benchmark's give a v5e the same two numbers.
"""

import fnmatch
import functools
import glob
import importlib.util
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = (["README.md", "PARITY.md", "MIGRATION.md"]
        + sorted(os.path.relpath(p, ROOT)
                 for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))
        + [".claude/skills/verify/SKILL.md"])

_PATH_PREFIXES = ("paddle_tpu/", "tools/", "tests/", "docs/", "benchmark/")
# a bare name is the repo's own file when it is a script or a document, or
# an upper-case record (`BENCHMARK.json`); a lower-case `.json` is what a run
# writes or a user passes
_BARE_FILE = re.compile(r"^([A-Za-z_][\w.-]*\.(py|md)|[A-Z][\w.-]*\.json)$")


def _tree_files():
    """The files git would track: the tree less `.git` and whatever a name
    pattern of `.gitignore` matches (it has no negations)."""
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = [".git"] + [ln.strip().rstrip("/").split("/")[-1]
                              for ln in f if ln.strip()]

    def kept(names):
        return [n for n in names
                if not any(fnmatch.fnmatch(n, pat) for pat in ignored)]

    for d, dirs, files in os.walk(ROOT):
        dirs[:] = kept(dirs)
        for f in kept(files):
            yield os.path.join(d, f)


@functools.cache
def _basenames():
    return {os.path.basename(p) for p in _tree_files()}


def _makefile():
    """{target: [recipe lines]} of the root Makefile."""
    targets, cur = {}, None
    with open(os.path.join(ROOT, "Makefile")) as f:
        for line in f.read().replace("\\\n", " ").splitlines():
            m = re.match(r"^([a-z][\w-]*):", line)
            if m:
                cur = targets.setdefault(m.group(1), [])
            elif line.startswith("\t") and cur is not None:
                cur.append(line.strip())
            elif line.strip() and not line.startswith("#"):
                cur = None
    return targets


MAKE_TARGETS = _makefile()


def _code_words(text):
    """Words of inline code spans and of fenced blocks, with the fences'
    own lines left out."""
    spans_words, fenced = [], False
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
            continue
        spans = [line] if fenced else re.findall(r"`([^`\n]+)`", line)
        spans_words.extend(span.split() for span in spans)
    return spans_words


def _clean(word):
    """`tests/x.py::test_a`, `pkg/m.py:12-30`, `(tools/x.py),` -> the path."""
    word = word.strip("()[]{}<>,;\"'")
    word = word.split("::")[0]
    word = re.sub(r":[\d,:-]*$", "", word)
    return word.rstrip(".,:")


def _missing(doc):
    text = open(os.path.join(ROOT, doc)).read()
    here = os.path.dirname(os.path.join(ROOT, doc))
    bad = []
    for target in re.findall(r"\]\(([^)\s]+)\)", text):
        target = target.split("#")[0]
        if not target or re.match(r"^[a-z]+:", target):
            continue  # an anchor of this page, or a URL
        if not os.path.exists(os.path.join(here, target)):
            bad.append(f"link {target}")
    for span in _code_words(text):
        for i, raw in enumerate(span):
            word = _clean(raw)
            if raw == "make" and i + 1 < len(span):
                target = _clean(span[i + 1])
                if (re.match(r"^[a-z][\w-]*$", target)
                        and target not in MAKE_TARGETS):
                    bad.append(f"make {target}")
            if word.startswith(_PATH_PREFIXES):
                if any(c in word for c in "<>{}$"):
                    continue  # a pattern the reader fills in
                if not glob.glob(os.path.join(ROOT, word)):
                    bad.append(word)
            elif (_BARE_FILE.match(word) and word not in _basenames()
                  and (len(span) == 1 or (i and span[i - 1] == "python"))):
                bad.append(word)  # named alone or run, not an argument
    return bad


@pytest.mark.parametrize("doc", DOCS)
def test_doc_paths_exist(doc):
    """Every link target, every code token that is a path under the repo's
    directories, every bare file name that stands alone or is run with
    `python` (it must be some file's) and every quoted `make <target>`
    exists."""
    assert _missing(doc) == []


@pytest.mark.parametrize("target", sorted(MAKE_TARGETS))
def test_makefile_target_resolves(target):
    """Each script of the target's recipe exists and each `$(PY) -m
    module` it starts resolves."""
    recipe = " ".join(MAKE_TARGETS[target])
    assert recipe, f"{target} has no recipe"
    for path in re.findall(r"(?<![\w/.-])([\w./-]+\.py)\b", recipe):
        assert os.path.exists(os.path.join(ROOT, path)), path
    for module in re.findall(r"\$\(PY\)\s+-m\s+([\w.]+)", recipe):
        assert importlib.util.find_spec(module) is not None, module


def test_one_measuring_stack():
    """No file outside `benchmark/` names an environment knob of the
    deleted rungs or of their peak tables, and the two peak tables that
    are left (the benchmark's, with its source; the library's, which may
    not import the benchmark) agree on the v5e."""
    knobs = re.compile("PT_" + "BENCH_|PT_" + "TPU_PEAK|PT_" + "TPU_HBM")
    history = {"CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md",
               "PERF_LEDGER.jsonl"}
    hits = []
    for path in _tree_files():
        rel = os.path.relpath(path, ROOT)
        if rel.startswith("benchmark" + os.sep) or rel in history:
            continue
        try:
            text = open(path, encoding="utf-8").read()
        except UnicodeDecodeError:
            continue
        if knobs.search(text):
            hits.append(rel)
    assert hits == []

    from paddle_tpu.observability import profiling

    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        devices = json.load(f)["devices"]
    assert "TPU v5 lite" in devices
    for kind, row in devices.items():
        flops, hbm, _ici = next(
            p for pat, p in profiling._TPU_PEAKS if pat in kind.lower())
        assert (flops, hbm) == (row["bf16_flops_per_s"],
                                row["hbm_bytes_per_s"]), kind
