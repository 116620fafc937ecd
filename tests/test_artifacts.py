"""Committed-artifact integrity guard.

The strategy comparison under ``outputs/`` is the repo's equivalent of the
reference's committed deliverable (`/root/reference/outputs/`,
`/root/reference/README.md:44-49`). During round 4 a stray smoke run
silently truncated ``outputs/dp/log.csv`` to 3 rows while the README and
PNGs still described the 2000-step run (round-4 VERDICT weak #1). Two
defenses now exist:

- the trainer refuses to truncate an existing log.csv on a fresh run
  unless ``overwrite: true`` (tested in test_checkpoint.py), and
- this test cross-checks every ``outputs/<run>`` row of the README results
  table against the committed CSV: the DATA row count (header excluded —
  the file itself has steps+1 lines) must equal the README's step count,
  and the final loss must match the table to its printed precision. If an
  artifact is clobbered again, this goes red.
"""

from __future__ import annotations

import csv
import fnmatch
import os
import re
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# | `outputs/dp` | (1,8,1) | 2000 | 4.2116 | 283.5 s |
_ROW = re.compile(
    r"^\|\s*`outputs/(?P<name>\w+)`\s*\|[^|]*\|\s*(?P<steps>\d+)\s*\|"
    r"\s*\*{0,2}(?P<loss>[0-9.]+)\*{0,2}\s*\|"
    r"\s*\*{0,2}(?P<wall>[0-9.]+) s\*{0,2}[¹²³]?\s*\|"
)


def _table_rows() -> dict[str, tuple[int, str, str]]:
    rows = {}
    with open(os.path.join(REPO, "README.md")) as f:
        for line in f:
            m = _ROW.match(line.strip())
            if m:
                rows[m["name"]] = (int(m["steps"]), m["loss"], m["wall"])
    return rows


def test_readme_table_parses():
    rows = _table_rows()
    # The committed deliverable: every strategy plus the TPU flagship.
    assert {"dp", "tp", "pp", "3d", "fsdp", "tpu_dp"} <= set(rows), rows


def test_committed_logs_match_readme():
    for name, (steps, loss_str, wall_str) in _table_rows().items():
        path = os.path.join(REPO, "outputs", name, "log.csv")
        assert os.path.exists(path), f"{path} missing but listed in README"
        with open(path) as f:
            rows = list(csv.DictReader(f))
        assert len(rows) == steps, (
            f"outputs/{name}/log.csv has {len(rows)} data rows; README says "
            f"{steps} steps — artifact was clobbered or README is stale"
        )
        assert int(rows[-1]["step"]) == steps
        final = float(rows[-1]["loss"])
        decimals = len(loss_str.split(".")[1]) if "." in loss_str else 0
        assert f"{final:.{decimals}f}" == loss_str, (
            f"outputs/{name} final loss {final} != README {loss_str}"
        )
        wall = float(rows[-1]["elapsed_time"])
        wdec = len(wall_str.split(".")[1]) if "." in wall_str else 0
        assert f"{wall:.{wdec}f}" == wall_str, (
            f"outputs/{name} total wall-clock {wall} != README {wall_str} s"
        )


# `ops/attention.py` or ``main.py``; scripts/x.py and configs/x.yaml bare; BENCH_*.json.
# Lower case only: this repo's files are, and the reference project's files that
# comments also cite (`MLP.py`, `/root/reference/model/GPTModel.py`) are not ours to find.
_NAMED_FILE = re.compile(
    r"`(/?[a-z0-9_./*-]+\.py)`|\b(scripts/[\w*-]+\.py)|\b(configs/[\w*-]+\.yaml)|\b(BENCH_[\w*]+\.json)"
)


def _tracked() -> list[str]:
    try:
        out = subprocess.run(["git", "ls-files"], cwd=REPO, capture_output=True, text=True, check=True)
        return out.stdout.split()
    except (OSError, subprocess.CalledProcessError):  # a checkout without git holds tracked files only
        return [os.path.relpath(os.path.join(d, f), REPO) for d, _, files in os.walk(REPO) for f in files]


@pytest.mark.parametrize("group", ["README.md", "configs", "dtc_tpu", "scripts"])
def test_no_document_names_a_missing_file(group):
    """Every ``<path>.py`` in backticks, ``scripts/<name>.py``,
    ``configs/<name>.yaml`` and ``BENCH_*.json`` that a file of the group
    names is a tracked file (whole path or its tail; a glob must match
    one): an instruction to run a deleted script, or a record that cites a
    deleted file, goes red. CHANGES.md, ROADMAP.md and PERF.md are history
    and are not read."""
    tracked = _tracked()
    documents = [p for p in tracked if p == group
                 or (p.startswith(group + "/") and p.endswith((".py", ".yaml", ".md", ".sh")))]
    assert documents
    missing = []
    for doc in documents:
        with open(os.path.join(REPO, doc), encoding="utf-8") as f:
            for n, line in enumerate(f, 1):
                for groups in _NAMED_FILE.findall(line):
                    name = next(g for g in groups if g)
                    if name.startswith("/"):  # an absolute path is outside the repo
                        continue
                    if not any(fnmatch.fnmatchcase(p, name) or fnmatch.fnmatchcase(p, "*/" + name)
                               for p in tracked):
                        missing.append(f"{doc}:{n} names {name}")
    assert not missing, missing
