"""What the repository's documents, Makefile and CI say is there, is there
(CPU, tier-1, a second: text and `os.path.exists`, no jax).

- every repo-relative path a document names exists;
- every `.PHONY` name has a rule, and every `make <target>` a document names
  is a rule of the Makefile;
- the `TRLX_TPU_*` variables the package reads are the ones RUNBOOK.md names.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    "README.md",
    "RUNBOOK.md",
    "docs/api.md",
    "docs/configs.md",
    "docs/index.md",
    "Makefile",
    ".github/workflows/tests.yml",
    ".claude/skills/verify/SKILL.md",
)

# Directories whose files a document names by their path from the root.
ROOTS = ("trlx_tpu", "tests", "benchmark", "docs", "examples")
# Where a document's shortened path (`trainer/base.py`, `references/x.py`,
# `api.md` beside docs/index.md) may start.
BASES = ("", "trlx_tpu", "benchmark", "docs", "tests")
# Not the tree's to hold: what a run writes, what lives outside the checkout,
# and the reference's own tree (`trlx/...`, its `docs/source/`), which the
# documents cite by file and line.
EXEMPT = (
    "chiprun_out/",
    "benchmark_out/",
    "chip_smoke_out/",
    ".jax_cache/",
    ".scratch/",
    "ckpts/",
    "incidents/",
    "leases/",
    "plugins/profile/",
    "/tmp/",
    "/root/",
    "ACCEPTANCE.json",  # acceptance_network.py writes it
    "trlx/",
    "docs/source/",
)

_ROOTED = re.compile(r"(?<![\w./<>-])((?:%s)/[\w.*/-]*[\w*/])" % "|".join(ROOTS))
_NAMED = re.compile(r"(?<![\w./<>*-])((?:\.\./)?(?:[\w-]+/)*[\w-]+\.(?:py|md|jsonl|json|yml|yaml|cpp))(?![\w-])")


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _exists(path):
    if "*" in path:
        return bool(glob.glob(os.path.join(REPO, path)))
    return os.path.exists(os.path.join(REPO, path))


@functools.lru_cache(maxsize=None)
def _basenames():
    return {name for root in ROOTS for _, _, files in os.walk(os.path.join(REPO, root)) for name in files}


def named_paths(doc):
    """(path as written, resolved?) for each path `doc` names. A path under
    one of ROOTS must exist as written (a `*` globs). A shortened path must
    exist under one of BASES or beside the document. A bare name must be a
    top-level file or, for a lower-case `.py` / `.md` / `.cpp`, the name of a
    file under ROOTS; a bare lower-case `.json` / `.jsonl` / `.yml` is a run
    directory's file (`metrics.jsonl`, `spans.jsonl`, `config.json`) and is
    not judged."""
    text = _read(doc)
    here = os.path.dirname(doc)
    out = {}
    for m in _ROOTED.finditer(text):
        path = m.group(1).rstrip(".")
        out[path] = _exists(path)
    for m in _NAMED.finditer(text):
        path = m.group(1)
        if path in out or path.split("/")[0] in ROOTS:
            continue
        if "/" in path:
            starts = [os.path.join(b, path) for b in BASES] + [os.path.normpath(os.path.join(here, path))]
            out[path] = any(_exists(p) for p in starts)
            continue
        if _exists(path):
            out[path] = True
        elif path[0].isupper():  # the root's records are the upper-case names
            out[path] = False
        elif path.endswith((".py", ".md", ".cpp")):
            out[path] = path in _basenames()
    return out


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_files_that_exist(doc):
    dead = sorted(
        path
        for path, ok in named_paths(doc).items()
        if not ok and not any(e in path for e in EXEMPT)
    )
    assert dead == [], f"{doc} names files that are not in the tree: {dead}"


def _make_rules():
    return set(re.findall(r"^([A-Za-z][\w-]*):", _read("Makefile"), flags=re.M))


def test_every_phony_target_has_a_rule_and_every_make_target_a_document_names_exists():
    rules = _make_rules()
    phony = set(re.search(r"^\.PHONY:(.*)$", _read("Makefile"), flags=re.M).group(1).split())
    assert phony - rules == set()
    # `make a b` in code: after a backtick, at a line's start, after `run:`,
    # `;` or an environment assignment. Prose ("changes make the ...") is not.
    command = re.compile(r"(?:`|^\s*|run: |; |\b[A-Z_]+=\S+ )make ((?:-n )?(?:[a-z][\w-]*[ \t]*)+)", flags=re.M)
    missing = sorted(
        (doc, target)
        for doc in DOCUMENTS
        for targets in command.findall(_read(doc))
        for target in targets.split()
        if target != "-n" and target not in rules
    )
    assert missing == [], f"documents name make targets the Makefile lacks: {missing}"


def _env_names(text):
    return set(re.findall(r"TRLX_TPU_[A-Z0-9_]+", text))


def _package_env():
    names = set()
    for root, _, files in os.walk(os.path.join(REPO, "trlx_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    names |= _env_names(f.read())
    return names


def test_every_env_variable_the_package_reads_is_in_the_runbook():
    assert sorted(_package_env() - _env_names(_read("RUNBOOK.md"))) == []


def test_every_env_variable_the_runbook_names_is_read_somewhere():
    read = _package_env()
    for sub in ("tests", "benchmark", "."):
        top = os.path.join(REPO, sub)
        for name in sorted(os.listdir(top)):
            path = os.path.join(top, name)
            if name.endswith(".py") and os.path.isfile(path):
                with open(path) as f:
                    read |= _env_names(f.read())
    assert sorted(_env_names(_read("RUNBOOK.md")) - read) == []
