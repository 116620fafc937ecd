#!/usr/bin/env python
"""Graph auditor CLI: lower the real entry points, run the rule engine,
gate on committed baselines.

    # the CI pre-gate (scripts/verify_tier1.sh): ~2-3 min on CPU
    JAX_PLATFORMS=cpu python scripts/audit_graph.py \
        --modes dp,tp,fsdp,ep --check-baselines

    # after an INTENDED graph change: re-bless, review the diff, commit
    python scripts/audit_graph.py --modes dp,tp,fsdp,ep --decode \
        --write-baseline

The ISSUE-14 numerics (dtype-flow + dtype-literal lint) and memory
(static HBM plan) passes run BY DEFAULT and gate the per-entry
``<entry>.numerics.json`` / ``<entry>.memory.json`` baselines alongside
the graph fingerprints (--no-numerics / --no-memory to disable).

Exit status: 0 iff no error-severity findings. The audit always runs on
the 8-virtual-device CPU mesh (JAX_PLATFORMS honored, defaulting to cpu)
so it needs no accelerator — committed baselines describe the CPU
lowering of the exact programs the trainer runs; see README "Static
analysis / graph audit".
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Mesh env BEFORE jax imports: same 8-virtual-device layout the test
# suite pins in tests/conftest.py, so the audited programs equal the
# tested programs.
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--modes", default="dp,tp,fsdp,ep",
        help="comma-separated train entry points (see analysis.lowering."
        "TRAIN_ENTRIES); default: dp,tp,fsdp,ep",
    )
    p.add_argument(
        "--decode", action="store_true",
        help="also audit the greedy decode entry point",
    )
    p.add_argument(
        "--serve", action="store_true",
        help="also audit the serving (continuous-batching) decode step — "
        "its recompile fingerprint admits a request BETWEEN the two "
        "measured executions, so cold==1/steady==0 proves admission at "
        "fixed slots never recompiles",
    )
    p.add_argument(
        "--numerics", dest="numerics", action="store_true", default=True,
        help="run the dtype-flow numerics pass + dtype-literal lint and "
        "gate the <entry>.numerics.json baselines (DEFAULT ON; "
        "--no-numerics disables)",
    )
    p.add_argument(
        "--no-numerics", dest="numerics", action="store_false",
    )
    p.add_argument(
        "--memory", dest="memory", action="store_true", default=True,
        help="build the static HBM plan per entry and gate the "
        "<entry>.memory.json baselines (DEFAULT ON; --no-memory "
        "disables). Prints the byte table; the obs memory_stats "
        "watermark cross-check runs where the backend reports stats "
        "(TPU) and prints the wired-but-unmeasured note elsewhere",
    )
    p.add_argument(
        "--no-memory", dest="memory", action="store_false",
    )
    p.add_argument(
        "--kernels", action="store_true",
        help="run the ISSUE-20 kernel audit: DMA happens-before race "
        "detection over the recorded ring-kernel schedules, the static "
        "VMEM plans for every Pallas kernel across the model ladder "
        "(gating the kernels_<rung>.json baselines), and the index-map/"
        "SMEM/gate-coverage lint family. Combine with --modes '' "
        "--no-numerics --no-memory for the kernel-only pre-gate",
    )
    p.add_argument(
        "--check-baselines", action="store_true",
        help="fail when a committed baseline is missing (drift always "
        "checks against whatever baselines exist)",
    )
    p.add_argument(
        "--write-baseline", action="store_true",
        help="bless the current fingerprints as the committed baselines "
        "instead of gating on them",
    )
    p.add_argument(
        "--no-execute", action="store_true",
        help="skip the two execution passes (faster; loses the "
        "cold/steady recompile fingerprint)",
    )
    p.add_argument(
        "--report", default="",
        help="write the full JSON report to this path",
    )
    args = p.parse_args()

    import jax

    # The audit is CPU-deterministic by design (a CPU pre-gate, not on
    # the chip path): pin the platform in config as well as in the env.
    if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from dtc_tpu.analysis import memory as memplan
    from dtc_tpu.analysis.lowering import TRAIN_ENTRIES, build_artifacts
    from dtc_tpu.analysis.report import (
        build_report, check_baselines, write_baselines,
    )
    from dtc_tpu.analysis.rules import (
        audit_artifact, audit_dtype_literals, audit_hostsync,
    )

    modes = [m for m in args.modes.split(",") if m]
    unknown = [m for m in modes if m not in TRAIN_ENTRIES]
    if unknown:
        p.error(f"unknown modes {unknown}; known: {sorted(TRAIN_ENTRIES)}")
    sections = tuple(
        s for s, on in (("numerics", args.numerics), ("memory", args.memory))
        if on
    )

    findings = []
    artifacts = []
    for art in build_artifacts(
        modes, decode=args.decode, serve=args.serve,
        execute=not args.no_execute
    ):
        artifacts.append(art)
        found = audit_artifact(
            art, numerics=args.numerics, memory=args.memory
        )
        findings.extend(found)
        errs = sum(1 for f in found if f.severity == "error")
        print(f"[audit] {art.name}: lowered+compiled, "
              f"{len(found)} finding(s) ({errs} error)")
        if args.memory and art.state_bytes:
            plan = memplan.hbm_plan(art)
            row = " ".join(
                f"{k}={plan[k]:,}" for k in (
                    "params", "opt_master", "opt_moments", "activations",
                    "comm_buffers", "total",
                ) if k in plan
            )
            print(f"[audit]   hbm plan ({plan['activations_source']}): {row}")
    findings.extend(audit_hostsync())
    if args.numerics:
        findings.extend(audit_dtype_literals())
    if args.memory:
        watermark = memplan.device_watermark_bytes()
        if watermark is None:
            print(
                "[audit] memory_stats watermark: unavailable on this "
                "backend (CPU keeps no PJRT stats) — wired but unmeasured; "
                "a TPU run cross-checks the plan against the live peak"
            )
        else:
            print(f"[audit] memory_stats watermark: {watermark:,} bytes")

    kreport = None
    if args.kernels:
        from dtc_tpu.analysis import kernels as kern

        kfindings, kreport = kern.run_kernel_audit(
            write_baseline=args.write_baseline,
            require_baselines=args.check_baselines,
        )
        findings.extend(kfindings)
        errs = sum(1 for f in kfindings if f.severity == "error")
        print(f"[audit] kernel audit: {len(kfindings)} finding(s) "
              f"({errs} error) over race detector + lints + "
              f"{len(kreport['rungs'])} ladder rung(s)")
        for rung, fp in kreport["rungs"].items():
            t1 = fp["kernels"]["fused_layers_t1"]
            print(
                f"[audit]   {rung}: megakernel gate {t1['gate_bytes']:,} B "
                f"({'fits' if t1['fits'] else 'NO FIT'} @ "
                f"{t1['budget_bytes']:,}), double-buffered "
                f"{t1['double_buffered_bytes']:,} B, states vmem_limit "
                f"{t1['vmem_limit_bytes']:,} B"
            )
            fitting = [
                s[len("overlap_"):]
                for s in sorted(fp["kernels"]) if s.startswith("overlap_")
                and fp["kernels"][s]["fits"]
            ]
            print(f"[audit]   {rung}: overlap-ring sites fitting: "
                  f"{', '.join(fitting) if fitting else 'none'}")
        if args.write_baseline:
            for path in kreport.get("written", []):
                print(f"[audit] baseline written: {path}")

    report = build_report(artifacts, findings, sections=sections)

    if args.write_baseline:
        for path in write_baselines(report):
            print(f"[audit] baseline written: {path}")
    else:
        drift = check_baselines(report, require=args.check_baselines)
        findings.extend(drift)
        report = build_report(artifacts, findings, sections=sections)

    if kreport is not None:
        report["kernels"] = kreport["rungs"]

    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"[audit] report: {args.report}")

    for f in report["findings"]:
        print(f"[{f['severity'].upper()}] {f['artifact']} {f['rule']}: "
              f"{f['message']}")
    errors = report["summary"].get("error", 0)
    print(f"[audit] {len(report['entries'])} entry point(s), "
          f"{errors} error(s), {report['summary'].get('warn', 0)} warning(s)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
