"""The documents name files that exist, and no code names a harness that is gone.

A backticked path in ``README.md``, the ``verify`` skill or ``COVERAGE.md``
that ends in ``.py``, ``.json``, ``.md`` or ``.cpp`` is checked where it can be
placed: under a tracked top-level name it must exist as written, under a
package directory (``runtime/config.py``) it must exist in ``deepspeed_tpu/``,
and a bare file name must be some tracked file's. A ``:line`` or ``::name``
suffix is stripped; a path with a placeholder (``<cell>``, ``*``) is skipped.
A dead path is repaired by cutting or re-pointing the sentence.
"""

import functools
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md", "COVERAGE.md"]
# files the PROGRAM writes at run time, named bare in the documents
WRITTEN_AT_RUN_TIME = {"manifest.json"}
# the harnesses benchmark/ superseded (deleted in PR 46) and their switches
GONE = ["bench.py", "bench_ladder", "perf_sentinel", "trace_explain", "decode_profile", "pod_validate", "DS_TPU_BENCH_"]


@functools.cache
def tracked():
    """What git would commit; in a checkout without ``.git``, every file outside the directories ``.gitignore`` names."""
    out = subprocess.run(["git", "ls-files"], cwd=ROOT, capture_output=True, text=True)
    if out.returncode == 0:
        return [p for p in out.stdout.splitlines() if os.path.exists(os.path.join(ROOT, p))]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f if line.strip().endswith("/")} | {".git"}
    files = []
    for where, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in ignored]
        files += [os.path.relpath(os.path.join(where, n), ROOT) for n in names]
    return files


def dead_paths(document, files):
    tops = {p.split("/")[0] for p in files}
    package_dirs = {p.split("/")[1] for p in files if p.startswith("deepspeed_tpu/") and p.count("/") > 1}
    basenames = {os.path.basename(p) for p in files} | WRITTEN_AT_RUN_TIME
    dead = []
    with open(os.path.join(ROOT, document)) as f:
        for number, line in enumerate(f, 1):
            for quoted in re.findall(r"`([^`\s]+)`", line):
                path = re.sub(r":[\d,\-]+$", "", quoted.split("::")[0])
                if not re.search(r"\.(py|json|md|cpp)$", path) or re.search(r"[<>*{}$]", path):
                    continue
                first = path.split("/")[0]
                if "/" not in path:
                    alive = path in basenames
                elif first in tops:
                    alive = os.path.exists(os.path.join(ROOT, path))
                elif first in package_dirs:
                    alive = os.path.exists(os.path.join(ROOT, "deepspeed_tpu", path))
                else:
                    continue
                if not alive:
                    dead.append(f"{document}:{number}: {quoted}")
    return dead


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_the_document_names_exists(document):
    assert dead_paths(document, tracked()) == []


def test_the_reader_of_paths_finds_a_dead_one(tmp_path, monkeypatch):
    """The check itself: a planted document with one path of each kind, dead and alive."""
    monkeypatch.setattr(sys.modules[__name__], "ROOT", str(tmp_path))
    (tmp_path / "tools").mkdir()
    (tmp_path / "deepspeed_tpu" / "runtime").mkdir(parents=True)
    for name in ("tools/alive.py", "deepspeed_tpu/runtime/config.py", "top.md"):
        (tmp_path / name).write_text("")
    (tmp_path / "doc.md").write_text("`tools/alive.py:12` `tools/dead.py::f` `runtime/config.py` `runtime/gone.py`\n"
                                     "`top.md` `nowhere.json` `manifest.json` `tools/<cell>.json` `other/thing.py` `word`\n")
    files = ["tools/alive.py", "deepspeed_tpu/runtime/config.py", "top.md", "doc.md"]
    assert dead_paths("doc.md", files) == ["doc.md:1: tools/dead.py::f", "doc.md:1: runtime/gone.py", "doc.md:2: nowhere.json"]


@functools.cache
def lines_that_name_one():
    """``(where, line)`` of every line that names one of ``GONE``: every tracked ``*.py`` but this one (a docstring
    that sends a reader to a deleted file is a dead path too) and the three documents, each read once."""
    any_of_them = re.compile(r"(^|[^_a-z])(" + "|".join(map(re.escape, GONE)) + ")")
    found = []
    for path in tracked():
        if not (path in DOCUMENTS or path.endswith(".py")) or path == "tests/" + os.path.basename(__file__):
            continue
        with open(os.path.join(ROOT, path), errors="replace") as f:
            found += [(f"{path}:{number}", line) for number, line in enumerate(f, 1) if any_of_them.search(line)]
    return found


@pytest.mark.parametrize("name", GONE)
def test_no_code_names_a_harness_that_is_gone(name):
    pattern = re.compile(r"(^|[^_a-z])" + re.escape(name))
    assert [where for where, line in lines_that_name_one() if pattern.search(line)] == []
