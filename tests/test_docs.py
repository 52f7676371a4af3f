"""Docs honesty: every config key must be documented with its default
(ref: docs/_docs/02-ug-configuration.md documents the reference's full table),
and the metric-family reference in docs/observability.md must stay in
lockstep with the instruments the code actually registers."""

import functools
import glob
import os
import re

import pytest

from hyperspace_tpu import config

DOCS = os.path.join(os.path.dirname(__file__), "..", "docs", "configuration.md")
OBS_DOCS = os.path.join(os.path.dirname(__file__), "..", "docs", "observability.md")
PKG = os.path.join(os.path.dirname(__file__), "..", "hyperspace_tpu")
ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


def test_every_config_key_documented():
    text = open(DOCS).read()
    missing = [
        v
        for k, v in vars(config.keys).items()
        if not k.startswith("_") and isinstance(v, str) and f"`{v}`" not in text
    ]
    assert not missing, f"undocumented config keys: {missing}"


def test_documented_defaults_match_code():
    text = open(DOCS).read()
    # spot-check numeric defaults that appear verbatim in the table
    for key, default in config.DEFAULTS.items():
        if isinstance(default, bool):
            assert f"`{str(default).lower()}`" in text or key in (), key
        elif isinstance(default, int) and default >= 100:
            assert f"`{default}`" in text, f"{key} default {default} not documented"


def _registered_metric_families():
    """Every hs_* family name at a registry registration site. The pattern
    anchors on the ``counter(``/``gauge(``/``histogram(`` call so incidental
    hs_-prefixed strings (contextvar names, column prefixes) don't count."""
    pat = re.compile(
        r"""(?:counter|gauge|histogram)\(\s*["'](hs_[a-z0-9_]+)["']""", re.DOTALL
    )
    fams = set()
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        fams |= set(pat.findall(open(path).read()))
    return fams


def test_metric_families_documented_and_no_doc_drift():
    code = _registered_metric_families()
    assert len(code) > 20  # the regex found the registration sites at all
    text = open(OBS_DOCS).read()
    doc = set(re.findall(r"\bhs_[a-z0-9_]+[a-z0-9]", text))
    # histogram expositions add _bucket/_sum/_count series; the doc may show
    # them, but they document their base family
    doc_base = {
        re.sub(r"_(bucket|sum|count)$", "", f) if
        re.sub(r"_(bucket|sum|count)$", "", f) in code else f
        for f in doc
    }
    undocumented = sorted(code - doc_base)
    assert not undocumented, f"metric families missing from docs/observability.md: {undocumented}"
    phantom = sorted(doc_base - code)
    assert not phantom, f"docs/observability.md documents families the code never registers: {phantom}"


def test_doc_files_referenced_in_code_exist():
    docs_dir = os.path.join(os.path.dirname(DOCS))
    for name in ("configuration.md", "mutable-data.md", "architecture.md"):
        assert os.path.exists(os.path.join(docs_dir, name)), name


# --- every file a document names is in the tree ------------------------------

_FILE_EXTS = (".py", ".json", ".md")


def _subdirs(path):
    return {
        d for d in os.listdir(path)
        if os.path.isdir(os.path.join(path, d)) and not d.startswith((".", "__"))
    }


def _expand_braces(tok):
    m = re.search(r"\{([^{}]*)\}", tok)
    if m is None:
        return [tok]
    return [
        t
        for alt in m.group(1).split(",")
        for t in _expand_braces(tok[: m.start()] + alt + tok[m.end():])
    ]


def _named_paths(text):
    """Repo-relative paths a document names: words inside backticks and on
    ``python`` / ``python3 -m`` command lines. A word counts when it starts
    with a top-level directory of the repo, when it starts with a
    subdirectory of the package and ends in ``.py`` or ``/``, or when it is a
    bare file name ending in .py, .json or .md. ``/root/reference``, ``HS/...``
    and upstream's ``docs/_docs/`` are citations of the reference, and
    ``<placeholders>`` name nothing."""
    top, pkg = _subdirs(ROOT), _subdirs(PKG)
    words = []
    for m in re.finditer(r"`([^`\n]+)`", text):
        words += m.group(1).split()
    for m in re.finditer(r"python3?[ \t]+([^\n`]*)", text):
        args = m.group(1).split()
        words += args
        if len(args) > 1 and args[0] == "-m" and args[1].split(".")[0] in top:
            mod = args[1].replace(".", "/")
            words.append(mod + ".py" if os.path.exists(os.path.join(ROOT, mod + ".py")) else mod + "/")
    out = set()
    for w in words:
        w = re.sub(r"(::.*|:[\d,\-–]+)$", "", w.strip(".,;:()[]\"'"))
        if not w or "<" in w or ">" in w or w.startswith(("/", "HS/")) or "_docs/" in w:
            continue
        for t in _expand_braces(w):
            first = t.split("/")[0]
            if "/" in t and first in top:
                out.add(t)
            elif "/" in t and first in pkg and t.endswith((".py", "/")):
                out.add("hyperspace_tpu/" + t)
            elif "/" not in t and t.endswith(_FILE_EXTS):
                out.add(t)
    return out


@functools.lru_cache(maxsize=None)
def _basenames():
    return {
        f
        for d in _subdirs(ROOT)
        for _, _, files in os.walk(os.path.join(ROOT, d))
        for f in files
    }


def _in_tree(path):
    if "/" not in path:  # a root file, or a module spoken of by its file name
        return os.path.exists(os.path.join(ROOT, path)) or path in _basenames()
    return bool(glob.glob(os.path.join(ROOT, path), recursive=True))


@pytest.mark.parametrize(
    "doc",
    ["README.md"] + sorted(
        os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
    ),
)
def test_files_a_document_names_exist(doc):
    named = _named_paths(open(os.path.join(ROOT, doc)).read())
    missing = sorted(p for p in named if not _in_tree(p))
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
