"""The repo's account of itself stays true: a document names only files that
are there, and a registered knob is read by some code.  Both guards exist so
that what PR 31 deleted (a second measuring apparatus, its reports and its
knobs, cited by every document) does not grow back unnoticed."""

import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("vescale_tpu/", "scripts/", "benchmark/", "tests/", "examples/", "docs/")
BARE_SUFFIXES = (".py", ".sh", ".json", ".md")
RUNNERS = ("python", "python3", "bash", "sh")  # `python x.py`: x.py is a name too


def _ignore_patterns():
    dirs, files = {".git"}, set()
    with open(os.path.join(REPO, ".gitignore")) as f:
        for line in f:
            pat = line.strip().lstrip("/")
            if not pat or pat.startswith("#"):
                continue
            (dirs if pat.endswith("/") else files).add(pat.rstrip("/"))
    return dirs, files


def _repo_files():
    """Every file git would track, as repo-relative paths: the working tree
    less what the root ``.gitignore`` names (the driver's checkout has no
    ``.git`` to ask)."""
    ign_dirs, ign_files = _ignore_patterns()
    out = []
    for root, dirs, files in os.walk(REPO):
        rel_root = os.path.relpath(root, REPO)
        rel_root = "" if rel_root == "." else rel_root + "/"
        dirs[:] = [d for d in dirs if d not in ign_dirs]
        for f in files:
            rel = rel_root + f
            if not any(fnmatch.fnmatch(rel, p) or fnmatch.fnmatch(f, p) for p in ign_files):
                out.append(rel)
    return out


FILES = _repo_files()
BASENAMES = {os.path.basename(p) for p in FILES}

DOCUMENTS = sorted(
    ["README.md", ".claude/skills/verify/SKILL.md"]
    + [p for p in FILES if fnmatch.fnmatch(p, "docs/*.md")]
    + [p for p in FILES if fnmatch.fnmatch(p, "examples/*/README.md")]
)


def _as_glob(name: str) -> str:
    """A documented name as a glob: ``<placeholder>`` and ``{a,b}`` match
    anything, and what follows the file (``::test``, ``:123``) is dropped."""
    name = re.split(r"::|:\d", name)[0]
    name = re.sub(r"<[^>]*>|\{[^}]*\}", "*", name)
    return name.rstrip("/.,;")


def _spans(text: str):
    """What a document sets as code: each line of a fenced block, then each
    inline backticked span of the rest."""
    fences = re.split(r"^```.*$", text, flags=re.M)
    for block in fences[1::2]:
        yield from block.splitlines()
    yield from re.findall(r"`([^`\n]+)`", "".join(fences[0::2]))


def _dangling(text: str):
    bad = []
    for span in _spans(text):
        tokens = span.split()
        for prev, token in zip([None] + tokens, tokens):
            if token.startswith(TOP_DIRS):
                if not glob.glob(os.path.join(REPO, _as_glob(token))):
                    bad.append(span)
            elif ("/" not in token and token.endswith(BARE_SUFFIXES)
                  and (len(tokens) == 1 or prev in RUNNERS)):
                if not fnmatch.filter(BASENAMES, _as_glob(token)):
                    bad.append(span)
    return bad


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        bad = _dangling(f.read())
    assert not bad, f"{doc} names files the repo does not hold: {sorted(set(bad))}"


# The budgets of the two records every session reads whole (ROADMAP D18): (file's bytes, a line's bytes; a table's
# row may run longer).  PERF.md had grown to 512 KB in lines of up to 26 KB that no tool shows whole.
RECORD_BUDGETS = {"PERF.md": (256 * 1024, 6 * 1024), "CHANGES.md": (None, 1536)}


@pytest.mark.parametrize("doc", sorted(RECORD_BUDGETS))
def test_a_record_can_be_opened_whole(doc):
    whole, a_line = RECORD_BUDGETS[doc]
    with open(os.path.join(REPO, doc), "rb") as f:
        text = f.read()
    fold = ("the budget is written in ROADMAP.md, D18; fold PERF.md's section 6 (a paragraph older than the newest ten "
            "PRs becomes one line) and strike from section 7 what ROADMAP carries; a CHANGES.md line says what changed, "
            "where, and the ledger's verdict")
    size = len(text)
    assert whole is None or size <= whole, f"{doc} is {size} bytes, over its {whole}: {fold}"
    long = [(n, len(line)) for n, line in enumerate(text.split(b"\n"), 1)
            if len(line) > a_line and not line.lstrip().startswith(b"|")]
    assert not long, f"{doc}: (line, bytes) over {a_line} outside a table: {long}: {fold}"


def test_every_registered_knob_is_read_by_some_code():
    from vescale_tpu.analysis import envreg

    sources = []
    for p in FILES:
        if p.endswith(".py") and p != "vescale_tpu/analysis/envreg.py" and not p.startswith("tests/"):
            with open(os.path.join(REPO, p)) as f:
                sources.append(f.read())
    blob = "\n".join(sources)
    names = sorted(envreg._REGISTRY)
    assert names, "the registry lost its names?"
    unread = [n for n in names if n not in blob]
    assert not unread, f"registered in envreg.py and read by no code: {unread}"


def _imports(path):
    """Every module a file imports, wherever in it (``import`` nodes inside functions too), as dotted names
    from the repo's root: a relative import is resolved against the file's package."""
    import ast

    package = os.path.dirname(path).replace("/", ".").split(".")
    with open(os.path.join(REPO, path)) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - node.level + 1] if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            yield module
            yield from (f"{module}.{alias.name}" for alias in node.names)      # ``from . import sibling``


def test_a_model_family_imports_no_other_family_and_no_kernel_imports_a_model():
    """The arrows point one way: ``serve/hybrid_engine`` -> a family's module (one that defines the seam's
    ``serve_decode``) -> ``models/blocks.py``, ``models/mamba2.py``, ``kernels/``, ``moe/``."""
    models = {p[len("vescale_tpu/models/"):-3]: p for p in FILES if fnmatch.fnmatch(p, "vescale_tpu/models/*.py")}
    families = {}
    for name, path in models.items():
        with open(os.path.join(REPO, path)) as f:
            if re.search(r"^def serve_decode\(", f.read(), flags=re.M):
                families[name] = path
    assert len(families) >= 5, f"the families behind HybridServeEngine's seam: {sorted(families)}"
    crossed = sorted({(name, other) for name, path in families.items() for module in _imports(path)
                      for other in families if other != name and module.startswith(f"vescale_tpu.models.{other}")})
    assert not crossed, f"a family's module imports another family's: {crossed}"
    upward = sorted({(p, module) for p in FILES if fnmatch.fnmatch(p, "vescale_tpu/kernels/*.py")
                     for module in _imports(p) if module.startswith("vescale_tpu.models")})
    assert not upward, f"a kernel imports a model: {upward}"
