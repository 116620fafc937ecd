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
import os
import re

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


def _bench_file(path, detail: dict | None, malformed: bool = False) -> None:
    """Write one committed-BENCH-shaped wrapper file (the real files wrap
    the run's stdout tail; the detail dict rides the '# bench-detail:'
    line — see bench._bench_detail)."""
    import json

    if malformed:
        body = {"tail": ["not", "a", "string"]}
    elif detail is None:
        body = {"n": 1, "rc": 0, "tail": "no detail line here\n"}
    else:
        body = {"n": 1, "rc": 0, "tail": "# bench-detail: " + json.dumps(detail)}
    with open(path, "w") as f:
        json.dump(body, f)


def test_decode_drift_guard_degrades_gracefully(tmp_path, capsys):
    """ISSUE 5 satellite: the guard must warn — never raise, never flag —
    when NO committed BENCH file carries decode rows, fall back past a
    decode-less newest file to an older one that has them, and still
    catch a real >20% ms/token regression against that fallback."""
    from bench import decode_drift_guard

    d = str(tmp_path)
    run = {"decode_b8": {"ms_per_token": 10.0}, "devices": 1}

    # No BENCH files at all: silent no-op.
    assert decode_drift_guard(dict(run), d) == []

    # Files exist but none carry decode rows (one malformed for good
    # measure): warn, return [], raise nothing.
    _bench_file(os.path.join(d, "BENCH_r01.json"), {"moe_e8": {"mfu": 0.3}})
    _bench_file(os.path.join(d, "BENCH_r02.json"), None, malformed=True)
    extra = dict(run)
    assert decode_drift_guard(extra, d) == []
    assert "no committed BENCH" in capsys.readouterr().out
    assert "decode_regressions" not in extra

    # An OLDER file gains decode rows; the newest still has none — the
    # guard degrades to the newest file WITH rows instead of going blind.
    _bench_file(
        os.path.join(d, "BENCH_r01.json"),
        {"decode_b8": {"ms_per_token": 5.0}},
    )
    extra = dict(run)  # 10.0 vs 5.0 = +100%: flag
    flags = decode_drift_guard(extra, d)
    assert len(flags) == 1 and "BENCH_r01.json" in flags[0]
    assert extra["decode_regressions"] == flags

    # Within the 20% band: clean.
    extra = {"decode_b8": {"ms_per_token": 5.5}}
    assert decode_drift_guard(extra, d) == []


def test_decode_drift_guard_same_config_only(tmp_path):
    """ISSUE 11 satellite: rows compare only when their
    decode_attention/kv_cache_dtype labels match — a label re-pointed at
    a different backend/cache dtype must not be judged against its old
    self. Rows committed before the fields existed normalize to the
    config they actually ran ("fused"/"auto")."""
    from bench import decode_drift_guard

    d = str(tmp_path)
    _bench_file(
        os.path.join(d, "BENCH_r01.json"),
        {
            "decode_b8": {"ms_per_token": 5.0},  # pre-ISSUE-11: no fields
            "decode_b8_int8": {
                "ms_per_token": 4.0, "decode_attention": "fused_layers",
                "kv_cache_dtype": "int8",
            },
        },
    )
    # Same label, DIFFERENT config: not comparable — no flag despite 3x.
    extra = {"decode_b8": {
        "ms_per_token": 15.0, "decode_attention": "fused_layers",
        "kv_cache_dtype": "auto",
    }}
    assert decode_drift_guard(extra, d) == []
    # Same label, matching config (normalized old row): flags as before.
    extra = {"decode_b8": {
        "ms_per_token": 15.0, "decode_attention": "fused",
        "kv_cache_dtype": "auto",
    }}
    assert len(decode_drift_guard(extra, d)) == 1
    # int8 row vs its committed int8 self: matching explicit fields.
    extra = {"decode_b8_int8": {
        "ms_per_token": 9.0, "decode_attention": "fused_layers",
        "kv_cache_dtype": "int8",
    }}
    assert len(decode_drift_guard(extra, d)) == 1


def test_decode_drift_guard_spec_keys(tmp_path):
    """ISSUE 19 satellite: the same-config rule gains the speculative
    keys (spec_k / draft_layers / spec_acceptance) — a spec row's
    ms-per-ACCEPTED-token must never be judged against a plain row's
    sequential ms/token (or vice versa), and rows committed before
    ISSUE 19 normalize to spec-off (spec_k 0 / draft_layers 0 /
    acceptance "off"), the config they actually ran — the same
    normalization pattern as ISSUE 11's kv_cache_dtype above."""
    from bench import decode_drift_guard

    d = str(tmp_path)
    _bench_file(
        os.path.join(d, "BENCH_r01.json"),
        {
            "decode_b8": {  # pre-ISSUE-19: no spec fields
                "ms_per_token": 5.0, "decode_attention": "fused_layers",
                "kv_cache_dtype": "auto",
            },
            "spec_b8_k4": {
                "ms_per_accepted_token": 2.0,
                "decode_attention": "fused_layers",
                "kv_cache_dtype": "auto", "spec_k": 4, "draft_layers": 2,
                "spec_acceptance": "greedy",
            },
        },
    )
    base = {
        "decode_attention": "fused_layers", "kv_cache_dtype": "auto",
    }
    # A label re-pointed from plain to speculative: not comparable — no
    # flag despite 3x (accepted-token ms is a different metric).
    extra = {"decode_b8": dict(
        base, ms_per_token=15.0, spec_k=4, draft_layers=2,
        spec_acceptance="greedy",
    )}
    assert decode_drift_guard(extra, d) == []
    # Spec-off run vs the normalized pre-ISSUE-19 row: still guarded.
    extra = {"decode_b8": dict(
        base, ms_per_token=15.0, spec_k=0, draft_layers=0,
        spec_acceptance="off",
    )}
    assert len(decode_drift_guard(extra, d)) == 1
    # Spec row vs its committed spec self (the spec_* family, guarded on
    # ms-per-ACCEPTED-token): matching explicit keys flag; a different
    # spec_k (2 vs 4) is a different config — silent.
    spec = dict(base, spec_k=4, draft_layers=2, spec_acceptance="greedy")
    extra = {"spec_b8_k4": dict(spec, ms_per_accepted_token=9.0)}
    assert len(decode_drift_guard(extra, d)) == 1
    extra = {"spec_b8_k4": dict(spec, ms_per_accepted_token=9.0, spec_k=2)}
    assert decode_drift_guard(extra, d) == []
