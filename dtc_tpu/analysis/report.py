"""Report assembly and the committed-baseline drift gate.

A fingerprint is the STRUCTURED summary of one entry point's lowered
graph — collective census (counts + bytes), donation coverage, dtype
counts, recompile counts, mesh — not a hash of the HLO text (text carries
incidental metadata; the structured fields are the invariants). Baselines
are those fingerprints committed under ``dtc_tpu/analysis/baselines/``:
the gate recomputes and diffs, so ANY graph change — even one no rule
hard-fails, like two extra all-gathers or a dot flipping f32 — fails
loudly with a per-field diff until a human re-blesses it with
``--write-baseline``.

Baselines record the jax version that produced them: a version mismatch
downgrades drift to a warning (XLA's CPU pipeline legitimately changes
between releases; the gate is only authoritative on the env it was
blessed on — this container's jax).
"""

from __future__ import annotations

import json
import os
from typing import Any, Iterable

from dtc_tpu.analysis import hlo, memory, numerics
from dtc_tpu.analysis.lowering import Artifact
from dtc_tpu.analysis.rules import Finding

BASELINE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "baselines")

#: ISSUE-14 baseline sections: each audited entry additionally commits a
#: ``<entry>.numerics.json`` (dtype-flow fingerprint) and a
#: ``<entry>.memory.json`` (static HBM plan). Separate FILES on purpose:
#: the pre-existing ``<entry>.json`` graph fingerprints stay
#: byte-identical — the new families extend the gate without re-blessing
#: eleven committed baselines whose graphs did not change.
SECTIONS = ("numerics", "memory")


def artifact_fingerprint(a: Artifact) -> dict[str, Any]:
    """The drift-gated invariants of one lowered entry point."""
    return {
        "kind": a.kind,
        "mesh": a.mesh_shape,
        "batch": a.batch,
        "seq_len": a.seq_len,
        "n_layers": a.n_layers,
        "moe_experts": a.moe_experts,
        "compute_dtype": a.compute_dtype,
        "census": hlo.collective_census(a.hlo_text),
        "alias_count": hlo.input_output_alias_count(a.hlo_text),
        "expected_donated": a.expected_donated,
        "partition_id": hlo.has_partition_id(a.hlo_text),
        "f64_buffers": hlo.count_dtype(a.hlo_text, "f64"),
        "weak_outputs": a.weak_outputs,
        "dots": hlo.dot_dtype_counts(a.stablehlo_text),
        "cold_compiles": a.cold_compiles,
        "steady_compiles": a.steady_compiles,
    }


def numerics_fingerprint(a: Artifact) -> dict[str, Any]:
    """The dtype-flow invariants of one entry (ISSUE 14) — committed as
    ``<entry>.numerics.json``."""
    return numerics.numerics_fingerprint(
        a.stablehlo_text,
        precision=a.precision,
        loss_dtype=a.loss_dtype,
        state_dtypes=a.state_dtypes,
        collective_dtypes=hlo.collective_dtype_census(a.hlo_text),
    )


def memory_fingerprint(a: Artifact) -> dict[str, Any]:
    """The static HBM plan of one entry (ISSUE 14) — committed as
    ``<entry>.memory.json``. None for artifacts without the byte
    evidence (state_bytes unrecorded)."""
    if not a.state_bytes:
        return {}
    return memory.hbm_plan(a)


def build_report(
    artifacts: Iterable[Artifact],
    findings: Iterable[Finding],
    *,
    sections: tuple[str, ...] = SECTIONS,
) -> dict[str, Any]:
    """Assemble the serializable audit report: per-entry fingerprints
    (graph + the ISSUE-14 numerics/memory sections) plus severity-ranked
    findings (per-artifact and source-level alike). ``sections`` narrows
    the extra sections (audit_graph.py's --no-numerics/--no-memory)."""
    import jax

    artifacts = list(artifacts)
    findings = sorted(
        findings, key=lambda f: ("error", "warn", "info").index(f.severity)
    )
    by_sev: dict[str, int] = {}
    for f in findings:
        by_sev[f.severity] = by_sev.get(f.severity, 0) + 1
    report = {
        "jax": jax.__version__,
        "platform": jax.devices()[0].platform,
        "device_count": jax.device_count(),
        "entries": {a.name: artifact_fingerprint(a) for a in artifacts},
        "findings": [f.as_dict() for f in findings],
        "summary": by_sev,
    }
    if "numerics" in sections:
        report["numerics"] = {
            a.name: numerics_fingerprint(a) for a in artifacts
        }
    if "memory" in sections:
        report["memory"] = {
            a.name: fp for a in artifacts
            if (fp := memory_fingerprint(a))
        }
    return report


def _baseline_path(name: str, directory: str, section: str = "") -> str:
    suffix = f".{section}" if section else ""
    return os.path.join(directory, f"{name}{suffix}.json")


def write_baselines(
    report: dict[str, Any], directory: str = BASELINE_DIR
) -> list[str]:
    """Bless the report's fingerprints as the committed baselines (one
    file per entry — plus one per ISSUE-14 section present in the report
    — so a drift diff names the entry AND the family in `git status`)."""
    os.makedirs(directory, exist_ok=True)
    written = []
    for name, fp in report["entries"].items():
        path = _baseline_path(name, directory)
        with open(path, "w") as f:
            json.dump(
                {"jax": report["jax"], "platform": report["platform"],
                 "fingerprint": fp},
                f, indent=1, sort_keys=True,
            )
            f.write("\n")
        written.append(path)
    for section in SECTIONS:
        for name, fp in report.get(section, {}).items():
            path = _baseline_path(name, directory, section)
            with open(path, "w") as f:
                json.dump(
                    {"jax": report["jax"], "platform": report["platform"],
                     "fingerprint": fp},
                    f, indent=1, sort_keys=True,
                )
                f.write("\n")
            written.append(path)
    return written


def _diff(base: Any, cur: Any, prefix: str = "") -> list[str]:
    """Recursive field diff, one human-readable line per changed leaf."""
    if isinstance(base, dict) and isinstance(cur, dict):
        lines = []
        for key in sorted(set(base) | set(cur)):
            sub = f"{prefix}.{key}" if prefix else str(key)
            if key not in base:
                lines.append(f"{sub}: (absent) -> {cur[key]!r}")
            elif key not in cur:
                lines.append(f"{sub}: {base[key]!r} -> (absent)")
            else:
                lines.extend(_diff(base[key], cur[key], sub))
        return lines
    if base != cur:
        return [f"{prefix}: {base!r} -> {cur!r}"]
    return []


def check_baselines(
    report: dict[str, Any],
    directory: str = BASELINE_DIR,
    *,
    require: bool = True,
) -> list[Finding]:
    """Drift gate: diff the report's fingerprints against the committed
    baselines. Missing baseline -> error when ``require`` (the CI
    pre-gate) else warn; drift -> error with the per-field diff, unless
    the baseline was blessed under a different jax version (warn: the
    graph legitimately moves across XLA releases)."""
    out: list[Finding] = []
    checks: list[tuple[str, str, dict]] = [
        ("", name, fp) for name, fp in report["entries"].items()
    ]
    for section in SECTIONS:
        checks.extend(
            (section, name, fp)
            for name, fp in report.get(section, {}).items()
        )
    for section, name, fp in checks:
        label = f"{name}.{section}" if section else name
        rule_kind = f"{section} fingerprint" if section else "graph"
        path = _baseline_path(name, directory, section)
        if not os.path.exists(path):
            out.append(Finding(
                "baseline.missing", "error" if require else "warn", label,
                f"no committed baseline at {path} — bless the current graph "
                "with scripts/audit_graph.py --write-baseline",
            ))
            continue
        with open(path) as f:
            base = json.load(f)
        lines = _diff(base["fingerprint"], fp)
        if not lines:
            continue
        same_env = base.get("jax") == report["jax"] and (
            base.get("platform") == report["platform"]
        )
        sev = "error" if same_env else "warn"
        env_note = "" if same_env else (
            f" [baseline blessed on jax {base.get('jax')}/"
            f"{base.get('platform')}, running {report['jax']}/"
            f"{report['platform']} — drift downgraded to warn]"
        )
        out.append(Finding(
            "baseline.drift", sev, label,
            f"{rule_kind} drifted from committed baseline "
            f"({len(lines)} field(s))"
            f"{env_note}:\n    " + "\n    ".join(lines)
            + "\n  re-bless with scripts/audit_graph.py --write-baseline "
            "if intended",
        ))
    return out
