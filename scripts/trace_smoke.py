#!/usr/bin/env python
"""Tracing end-to-end smoke — the tier-1 pre-gate for ISSUE 7.

Bounded (< ~2 min on the 1-core CI host): a 3-step synthetic CPU
training run and a 2-request serving run, both with tracing on, then the
offline leg — scripts/trace_report.py's loaders must produce a span
attribution table (training), per-request waterfalls (serving), and a
Perfetto export with the required Chrome-trace keys and monotonic
timestamps. Catches a broken span/export pipeline before the long main
run buries it.

    JAX_PLATFORMS=cpu python scripts/trace_smoke.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    import jax

    if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.analysis.lowering import audit_model_cfg
    from dtc_tpu.config.schema import (
        MeshConfig, ModelConfig, OptimConfig, ServeConfig, TrainConfig,
    )
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.obs import Telemetry
    from dtc_tpu.serve import Request, RequestState, ServingEngine
    from dtc_tpu.train.trainer import train
    from scripts.trace_report import (
        load_events, print_span_table, print_waterfalls, request_waterfalls,
        span_table, spans_of,
    )
    from dtc_tpu.obs.trace import to_chrome_trace

    root = tempfile.mkdtemp(prefix="dtc_trace_smoke_")

    # ---- leg 1: 3-step training run, tracing on (the default) ----
    train_dir = os.path.join(root, "train")
    model_cfg = ModelConfig(
        vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=16, dropout=0.0, param_dtype="float32",
        compute_dtype="float32", attention="dense",
    )
    train(
        TrainConfig(
            seed=0, parallel="dp", batch=8, steps=3, log_every=1,
            output_dir=train_dir, dataset="synthetic", warmup_steps=1,
            prefetch=0, mesh=MeshConfig(),
        ),
        model_cfg,
        OptimConfig(lr=1e-3, weight_decay=0.0, grad_clip=1.0),
    )
    tev = load_events(train_dir)
    ttable = span_table(tev)
    names = {r["name"] for r in ttable}
    assert {"step", "dispatch"} <= names, f"missing train spans: {names}"
    steps = [r for r in ttable if r["name"] == "step"]
    assert steps and steps[0]["count"] == 3, ttable
    print("# training span table:")
    print_span_table(ttable, top=8)

    # ---- leg 2: 2-request serving run through the real engine ----
    serve_dir = os.path.join(root, "serve")
    scfg = ServeConfig(slots=2, page_size=4, queue_depth=4,
                       max_new_tokens=4, prefill_bucket=8)
    mcfg = audit_model_cfg()
    model = GPT(mcfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]
    tele = Telemetry.for_serving(serve_dir)
    eng = ServingEngine(model, params, scfg, telemetry=tele)
    rng = np.random.RandomState(0)
    for i in range(2):
        eng.submit(Request(
            rid=f"s{i}", prompt=rng.randint(0, mcfg.vocab_size, 6).tolist(),
            max_new_tokens=4,
        ))
    res = eng.run(max_steps=100)
    tele.flush()
    tele.close()
    assert all(res[f"s{i}"].state is RequestState.DONE for i in range(2)), res

    sev = load_events(serve_dir)
    falls = request_waterfalls(sev)
    assert set(falls) == {"s0", "s1"}, f"waterfall rids: {set(falls)}"
    for rid, entries in falls.items():
        kinds = [x["name"] for x in entries]
        for needed in ("req.queued", "req.prefill", "req.decode", "req.done"):
            assert needed in kinds, f"{rid} missing {needed}: {kinds}"
    print("# serving waterfalls:")
    print_waterfalls(sev)

    # ---- leg 3: Perfetto export schema over BOTH runs ----
    for label, events in (("train", tev), ("serve", sev)):
        trace = to_chrome_trace(events)
        out = os.path.join(root, f"{label}.perfetto.json")
        import json

        with open(out, "w") as f:
            json.dump(trace, f)
        rows = [e for e in trace["traceEvents"] if e["ph"] != "M"]
        assert rows, f"{label}: empty perfetto export"
        for e in rows:
            for k in ("ph", "ts", "dur", "pid", "tid", "name"):
                assert k in e, f"{label}: missing {k} in {e}"
        ts = [e["ts"] for e in rows]
        assert ts == sorted(ts), f"{label}: non-monotonic ts"
        assert any(e["ph"] == "X" for e in rows)
        print(f"# {label}: {len(rows)} perfetto events -> {out}")
    assert spans_of(sev), "serve run emitted no spans"

    print("TRACE SMOKE PASS")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
