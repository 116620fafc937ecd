"""First contact with the chip: the quickest proof the system still starts there.

    python chip_smoke.py              # one TPU chip: train, decode, serve
    python chip_smoke.py --chips 4    # four chips: the parallel-training legs only

One process, which imports JAX once and owns the chip for its whole life.
Every phase goes through an entry point a user already has — the CLI's
``load_config`` -> ``trainer.train``, ``generate.generate``, a
``ServingEngine`` built from ``configs/serve_config.yaml`` — at the full
width of the flagship (``configs/model_config.yaml``), with weights made
from ``--seed``. The first phase that fails ends the run (an assertion or
the program's own exception: non-zero exit, no final line). On success the
LAST line of stdout is one JSON object,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. Without a TPU the script exits non-zero
before any phase: it forces no platform, it only refuses to call a CPU run
a chip run. Everything it writes goes under ``outputs/chip_smoke/`` plus
the compile cache (``dtc_tpu.utils.dist.configure_compile_cache``).

The numbers printed on the earlier lines are single readings from one run,
labelled with the device — a smoke, not a benchmark.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import statistics
import sys
import time
from dataclasses import replace

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "outputs", "chip_smoke")


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[chip_smoke] {phase}: {body}", flush=True)


def config_path(name: str) -> str:
    return os.path.join(REPO, "configs", name)


def fresh_dir(name: str) -> str:
    """The phase's own output directory, emptied: telemetry shards append,
    and a second run in the same checkout must read only its own events."""
    path = os.path.join(OUT, name)
    shutil.rmtree(path, ignore_errors=True)
    return path


def read_events(output_dir: str) -> list[dict]:
    with open(os.path.join(output_dir, "obs", "events.r0.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def compile_seconds(events: list[dict]) -> float:
    """Seconds of the run's ONE startup compile; any other compile event,
    or a recompile, fails the phase."""
    compiles = [e for e in events if e["etype"] == "compile"]
    recompiles = [e for e in events if e["etype"] == "recompile"]
    assert len(compiles) == 1, f"want exactly one compile event, got {compiles}"
    assert not recompiles, f"train step recompiled: {recompiles}"
    return compiles[0]["compile_time_s"]


def assert_losses_fall(losses: list[float]) -> None:
    import numpy as np

    assert losses and np.all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"


# ---------------------------------------------------------------------------
# what is only true on the chip (a CPU rehearsal at toy size swaps these out)


def assert_flash_compiled(model_cfg) -> str:
    """The silent fallback the train phase exists to catch: `attention:
    auto` resolving to dense, or the kernel running in interpret mode."""
    from dtc_tpu.ops import attention, flash_attention

    impl = attention.resolve_impl(
        model_cfg.attention, model_cfg.max_seq_len, model_cfg.head_dim,
        model_cfg.attention_block_q, model_cfg.attention_block_kv,
    )
    assert impl == "flash", f"attention: {model_cfg.attention} resolved to {impl}"
    assert not flash_attention._interpret(), "flash kernel would run interpreted"
    return impl


def assert_kernel_in_program(lowered_text: str, backend: str) -> None:
    """The compiled program agrees with the routing predicate: a Mosaic
    kernel is in it exactly when a kernel backend was chosen."""
    assert ("tpu_custom_call" in lowered_text) == (backend != "xla"), backend


def assert_pallas_ring(model_cfg, batch: int, ring: int) -> None:
    """``collectives: overlapped`` must take the fused Pallas ring, not the
    ppermute-decomposed one, at every dense site of this mesh."""
    from dtc_tpu.ops import overlap_collectives

    d, ff = model_cfg.d_model, model_cfg.d_ff
    rows = batch * model_cfg.max_seq_len // ring
    for site, (k, n, axis) in {
        "qkv_proj": (d, d, 0), "out_proj": (d, d, 1),
        "fc1": (d, ff, 0), "fc2": (ff, d, 1),
    }.items():
        took = overlap_collectives.resolve_backend(rows, k, n, ring, axis, 2)
        assert took == "pallas", f"{site} takes the {took} transport"


def device_bytes(device, key: str) -> int:
    return device.memory_stats()[key]


# ---------------------------------------------------------------------------
# one chip


def phase_train(steps: int) -> None:
    """What ``python main.py --train_config_path configs/train_config_dp.yaml
    --dataset synthetic --steps N`` does, with ``output_dir`` moved off the
    committed ``outputs/dp`` artifact."""
    import jax

    from dtc_tpu.config.loader import load_config
    from dtc_tpu.train.trainer import train
    from dtc_tpu.utils.dist import maybe_initialize_distributed

    train_cfg, model_cfg, opt_cfg = load_config(config_path("train_config_dp.yaml"))
    out = fresh_dir("train")
    train_cfg = replace(train_cfg, steps=steps, dataset="synthetic", output_dir=out)
    maybe_initialize_distributed(train_cfg.multihost, train_cfg.coordinator_timeout_s)

    impl = assert_flash_compiled(model_cfg)
    result = train(train_cfg, model_cfg, opt_cfg)
    assert len(result.losses) == steps, (len(result.losses), steps)
    assert_losses_fall(result.losses)
    events = read_events(out)
    step_s = [e["step_time_s"] for e in events if e["etype"] == "step"]
    median_s = statistics.median(step_s)
    say(
        "train",
        attention=impl, interpret=False, steps=steps,
        batch=train_cfg.batch, seq=model_cfg.max_seq_len,
        compile_s=compile_seconds(events),
        median_step_s=median_s,
        tokens_per_s=round(train_cfg.batch * model_cfg.max_seq_len / median_s, 1),
        loss_first=round(result.losses[0], 4), loss_last=round(result.losses[-1], 4),
        peak_bytes_in_use=device_bytes(jax.devices()[0], "peak_bytes_in_use"),
    )


def flagship_params(model_cfg, seed: int):
    """Random flagship weights from ``seed`` — one tree serves every decode
    backend (the backends differ in execution, not in parameters)."""
    import jax
    import jax.numpy as jnp

    from dtc_tpu.models.gpt import GPT

    return GPT(model_cfg).init(
        {"params": jax.random.PRNGKey(seed)}, jnp.ones((1, 1), jnp.int32),
        train=False,
    )["params"]


#: How far below the oracle's best logit a token may sit and still count as
#: the oracle's choice. The repo's token-exact claim (tests/test_decode_
#: fused.py) is an fp32 CPU fact; on the chip the flagship computes in bf16,
#: a kernel and the einsum oracle round in different orders, and a
#: random-init model's top logits tie within that rounding — so free-running
#: greedy tokens flip at near-ties and then diverge for good (first contact:
#: 229/256 equal). What CAN be held exactly is this: teacher-forced along
#: the candidate's own tokens, the oracle rates every one of them within
#: this band of its own argmax. The band is two bf16 ulps at the logits'
#: magnitude (the head emits bf16; |logit| < 16, ulp 0.0625). Measured on
#: the v5e: worst gap 0.031, largest kernel-vs-oracle logit difference 0.055
#: (PERF.md "First contact") — a wrong mask or a skipped layer is O(1).
NEAR_TIE_BAND = 0.125


@functools.lru_cache(maxsize=None)
def _forced_logits_fn(model):
    """One jitted teacher-forcing program per model (it is asked for the
    oracle's several times at the same shapes)."""
    import jax
    import jax.numpy as jnp

    from dtc_tpu.generate import decode_step, init_cache

    @jax.jit
    def run(params, prompt, tokens):
        cache = init_cache(model, prompt.shape[0])
        cache, logits = decode_step(model, params, cache, prompt)

        def body(cache, tok):
            cache, step_logits = decode_step(model, params, cache, tok[:, None])
            return cache, step_logits[:, -1]

        _, rest = jax.lax.scan(body, cache, tokens[:, :-1].T)
        both = jnp.concatenate([logits[:, -1][None], rest])
        return both.transpose(1, 0, 2).astype(jnp.float32)

    return run


def forced_logits(model, params, prompt, tokens):
    """The logits ``model`` gives at each position of ``tokens`` when fed
    ``prompt`` and then ``tokens`` themselves (teacher forcing), through
    the same ``decode_step`` generate and the engine drive: (B, n, V) fp32."""
    import jax.numpy as jnp
    import numpy as np

    return np.asarray(_forced_logits_fn(model)(
        params, jnp.asarray(prompt, jnp.int32), jnp.asarray(tokens, jnp.int32)
    ))


def near_tie_gap(oracle_logits, tokens) -> tuple[int, float]:
    """(positions where the token IS the oracle's argmax, the worst
    shortfall of a chosen token's oracle logit below the oracle's best)."""
    import numpy as np

    tokens = np.asarray(tokens)
    chosen = np.take_along_axis(oracle_logits, tokens[..., None], axis=-1)[..., 0]
    gap = oracle_logits.max(axis=-1) - chosen
    return int((gap == 0).sum()), float(gap.max())


def phase_decode(seed: int, new_tokens: int) -> None:
    """Greedy ``generate`` at batch 8 once per decode backend. The backend
    the routing predicates chose must be the configured one (the ladder
    fused_layers -> fused -> xla is silent by design), the kernels must
    agree with the oracle up to near-ties (``NEAR_TIE_BAND``), and int8 KV
    is held to the logit bound tests/test_decode_fused.py pins."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.config.loader import load_config
    from dtc_tpu.generate import _generate_jit, generate
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.ops import decode_fused

    _, base_cfg, _ = load_config(config_path("train_config_dp.yaml"))
    params = flagship_params(base_cfg, seed)
    batch, prompt_len = 8, 16
    prompt = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, prompt_len), 0, base_cfg.vocab_size,
        jnp.int32,
    )

    def run(backend: str, kv: str = "auto"):
        cfg = replace(base_cfg, decode_attention=backend, kv_cache_dtype=kv)
        model = GPT(cfg)
        chose = decode_fused.decode_backend(cfg, t_new=1)
        assert chose == backend, (
            f"decode_attention: {backend} (kv {kv}) fell down the ladder to {chose}"
        )
        assert_kernel_in_program(
            _generate_jit.lower(model, params, prompt, new_tokens).as_text(), backend
        )
        t0 = time.perf_counter()
        toks = np.asarray(generate(model, params, prompt, new_tokens))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = np.asarray(generate(model, params, prompt, new_tokens))
        warm_s = time.perf_counter() - t0
        assert toks.shape == (batch, new_tokens) and (toks == again).all()
        assert toks.min() >= 0 and toks.max() < cfg.vocab_size
        say(
            "decode", configured=backend, kv_cache_dtype=kv, ran=chose,
            prefill_ran=decode_fused.decode_backend(cfg, t_new=prompt_len),
            batch=batch, new_tokens=new_tokens,
            first_call_s=round(first_s, 3), warm_call_s=round(warm_s, 4),
            ms_per_token=round(1e3 * warm_s / new_tokens, 3),
        )
        return model, toks

    oracle_model, oracle = run("xla")
    fused_model, fused = run("fused")
    mk_model, fused_layers = run("fused_layers")
    mk8_model, int8 = run("fused_layers", kv="int8")

    # Parity with the oracle, as far as bf16 on the chip allows it (see
    # NEAR_TIE_BAND): along each backend's OWN tokens the oracle must rate
    # every chosen token within the band of its own best, and along the
    # oracle's tokens the backend's logits must sit within the band.
    oracle_logits = forced_logits(oracle_model, params, prompt, oracle)
    for name, model, toks in (
        ("fused", fused_model, fused),
        ("fused_layers", mk_model, fused_layers),
    ):
        exact, gap = near_tie_gap(forced_logits(oracle_model, params, prompt, toks), toks)
        noise = float(np.abs(forced_logits(model, params, prompt, oracle) - oracle_logits).max())
        say(
            "decode", parity=f"{name} vs xla",
            tokens_equal=f"{int((toks == oracle).sum())}/{oracle.size}",
            oracle_argmax_positions=f"{exact}/{toks.size}",
            worst_near_tie_gap=round(gap, 5), max_logit_diff=round(noise, 5),
            band=NEAR_TIE_BAND,
        )
        assert gap <= NEAR_TIE_BAND and noise <= NEAR_TIE_BAND, (
            f"{name} differs from xla by more than a near-tie: gap {gap}, "
            f"logit diff {noise}, band {NEAR_TIE_BAND}"
        )

    # int8 KV is held to the logit bound tests/test_decode_fused.py pins
    # (`gap < 0.5`), here against the bf16-KV megakernel on the same tokens.
    gap8 = float(np.abs(
        forced_logits(mk8_model, params, prompt, fused_layers)
        - forced_logits(mk_model, params, prompt, fused_layers)
    ).max())
    say(
        "decode", parity="int8 vs bf16 KV (fused_layers)", max_logit_diff=round(gap8, 4),
        bound=0.5, tokens_equal=f"{int((int8 == fused_layers).sum())}/{int8.size}",
    )
    assert gap8 < 0.5, f"int8 megakernel logits off by {gap8}"


def phase_serve(seed: int, new_tokens: int) -> None:
    """A ``ServingEngine`` from the shipped serve + flagship configs answers
    the four-request shape of scripts/serve_smoke.py (two share a prefix,
    lengths differ); every request ends DONE, with ``generate``'s tokens up
    to near-ties (``NEAR_TIE_BAND``: the engine decodes four slots from a
    bucket-padded prefill, ``generate`` one row from an exact one, and bf16
    rounds the two differently). Then one speculative request (``spec:``
    on, ``fused_layers``)."""
    import jax.numpy as jnp
    import numpy as np

    from dtc_tpu.config.loader import load_serve_config
    from dtc_tpu.config.schema import SpecConfig
    from dtc_tpu.generate import generate
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.ops import decode_fused
    from dtc_tpu.serve import Request, RequestState, ServingEngine

    serve_cfg, model_cfg = load_serve_config(config_path("serve_config.yaml"))
    params = flagship_params(model_cfg, seed)
    rng = np.random.RandomState(seed)
    draw = lambda n: rng.randint(0, model_cfg.vocab_size, size=n).tolist()  # noqa: E731
    prefix = draw(24)
    prompts = [draw(12), prefix + draw(8), prefix + draw(17), draw(40)]

    def serve(label, model, cfg, prompts, **note):
        eng = ServingEngine(model, params, cfg)
        for i, p in enumerate(prompts):
            shared = len(prefix) if p[: len(prefix)] == prefix else 0
            # deadline_s=0: a process's first requests pay the compiles,
            # which is set-up, not the service time the 30 s default bounds.
            eng.submit(Request(
                rid=f"r{i}", prompt=p, max_new_tokens=new_tokens, deadline_s=0,
                shared_prefix_len=shared,
            ))
        t0 = time.perf_counter()
        results = eng.run(max_steps=20 * new_tokens)
        wall_s = time.perf_counter() - t0
        equal, worst = 0, 0.0
        for i, p in enumerate(prompts):
            r = results[f"r{i}"]
            assert r.state is RequestState.DONE, f"r{i} ended {r.state}: {r.error}"
            assert len(r.tokens) == new_tokens, (i, r.tokens)
            row = jnp.asarray(p, jnp.int32)[None]
            ref = np.asarray(generate(model, params, row, new_tokens))[0].tolist()
            equal += r.tokens == ref
            got = np.asarray(r.tokens)[None]
            _, gap = near_tie_gap(forced_logits(model, params, row, got), got)
            worst = max(worst, gap)
        say(
            "serve", mode=label, backend=decode_fused.decode_backend(model.cfg, t_new=1),
            slots=cfg.slots, requests=len(prompts), done=len(results),
            equal_generate=f"{equal}/{len(prompts)}", worst_near_tie_gap=round(worst, 5),
            band=NEAR_TIE_BAND, wall_s_with_compiles=round(wall_s, 2), **note,
        )
        assert worst <= NEAR_TIE_BAND, (
            f"{label}: an engine token sits {worst} below generate-side argmax "
            f"(band {NEAR_TIE_BAND})"
        )
        return eng, results

    eng, _ = serve("plain", GPT(model_cfg), serve_cfg, prompts)
    hits = eng.reg.snapshot().get("serve_prefix_hits", 0)
    assert hits >= 1, "shared prefix was never reused"
    say("serve", prefix_hits=hits)

    spec_model_cfg = replace(model_cfg, decode_attention="fused_layers")
    draft_layers = model_cfg.n_layers // 3  # the flagship's bottom 4 of 12
    spec_cfg = replace(
        serve_cfg, spec=SpecConfig(spec_k=4, draft_layers=draft_layers)
    )
    assert decode_fused.decode_backend(spec_model_cfg, 4, verify=True) == "fused_layers"
    _, results = serve(
        "speculative", GPT(spec_model_cfg), spec_cfg, prompts[:1],
        spec_k=4, draft_layers=draft_layers,
    )
    say("serve", spec_accept_rate=getattr(results["r0"], "accept_rate", None))


# ---------------------------------------------------------------------------
# four chips


#: Loss parity is judged twice, because one arithmetic cannot carry both
#: claims (PERF.md "First contact"):
#:
#: - **bf16, as shipped** — what users run. On the chip the strategies do
#:   NOT agree to the CPU tests' 2e-4: ten AdamW steps from a random init
#:   amplify where each program rounds to bf16 into ~0.1 in loss (one XLA
#:   flag, xla_allow_excess_precision, moves a single-chip run as far).
#:   Measured: dp and fsdp within 2e-3 of each other and of one chip; tp /
#:   overlapped / PP×TP within 0.15 of dp and within 0.03 of each other.
#:   Held to BF16_BAND, which only a grossly wrong strategy exceeds.
#: - **fp32 at highest matmul precision** — the arithmetic in which
#:   tests/test_train_parity.py makes the claim "every strategy trains the
#:   same model"; held to that file's tolerances (2e-4 GSPMD, 5e-4
#:   pipeline; 2e-4 overlapped-vs-xla as __graft_entry__).
BF16_BAND = 0.25


def multichip_legs() -> list[dict]:
    """name; shipped YAMLs (train[, model]); train / model overrides; the leg
    it is compared with and the fp32 tolerance (None: no fp32 run); whether
    a parameter is sharded (so its shards can be counted)."""
    from dtc_tpu.config.schema import MeshConfig

    def leg(name, configs, train, anchor=None, tol=None, model=None, sharded=True):
        return dict(name=name, configs=configs, train=train, model=model or {},
                    anchor=anchor, tol=tol, sharded=sharded)

    # T=4096 (configs/*_longctx.yaml, batch 4): sequence parallelism over
    # model=4 against plain DP. The shipped config leaves per-step sync off;
    # the smoke turns it on so its step time is a device time. bf16 only
    # (tol None): in fp32 the flash backward's full-T scratches at T=4096
    # exceed the chip's default scoped VMEM and the kernel states no limit
    # of its own (found compiling for a described v5e; ROADMAP defects).
    longctx = ("train_config_longctx.yaml", "model_config_longctx.yaml")
    return [
        leg("dp", ("train_config_dp.yaml",),
            dict(mesh=MeshConfig(pipe=1, data=4, model=1)), tol=0.0, sharded=False),
        leg("tp", ("train_config_tp.yaml",),
            dict(mesh=MeshConfig(pipe=1, data=1, model=4)), "dp", 2e-4),
        leg("fsdp", ("train_config_fsdp.yaml",),
            dict(mesh=MeshConfig(pipe=1, data=4, model=1)), "dp", 2e-4),
        leg("fsdp_overlapped", ("train_config_fsdp.yaml",),
            dict(mesh=MeshConfig(pipe=1, data=4, model=1), collectives="overlapped"),
            "fsdp", 2e-4),
        leg("3d_pp2_tp2", ("train_config_pp.yaml",),
            dict(parallel="3d", mesh=MeshConfig(pipe=2, data=1, model=2),
                 pp_microbatches=2), "dp", 5e-4),
        leg("dp_t4096", longctx,
            dict(mesh=MeshConfig(pipe=1, data=4, model=1), sync_every_step=True),
            sharded=False),
        leg("ulysses_t4096", longctx,
            dict(parallel="tp", mesh=MeshConfig(pipe=1, data=1, model=4),
                 sync_every_step=True),
            "dp_t4096", model=dict(attention="ulysses"), sharded=False),
    ]


def assert_ring_kernels_match_plain_dot(model_cfg, batch: int) -> None:
    """The fused ring kernels against a plain fp32 dot, op by op, on the
    four-device ring at the flagship's dense shapes: forward, dx and the
    streamed dw reduce-scatter agree to bf16 rounding. The training legs
    cannot hold the kernels this tightly (see BF16_BAND), and their
    barrier / remote-DMA schedule has never run anywhere but here."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dtc_tpu.config.schema import MeshConfig
    from dtc_tpu.ops.overlap_collectives import overlap_dense_matmul
    from dtc_tpu.parallel.mesh import mesh_from_config

    mesh = mesh_from_config("fsdp", MeshConfig(pipe=1, data=4, model=1))
    d, ff, t = model_cfg.d_model, model_cfg.d_ff, model_cfg.max_seq_len
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 6))
    worst = {}
    for site, (k, n, axis) in {"fc1": (d, ff, 0), "fc2": (ff, d, 1)}.items():
        rows = NamedSharding(mesh, P("data"))
        w_sharding = NamedSharding(mesh, P("data", None) if axis == 0 else P(None, "data"))
        x = jax.device_put(jax.random.normal(next(keys), (batch, t, k), jnp.bfloat16), rows)
        w = jax.device_put(
            jax.random.normal(next(keys), (k, n), jnp.bfloat16) * k ** -0.5, w_sharding
        )
        g = jax.device_put(jax.random.normal(next(keys), (batch, t, n), jnp.bfloat16), rows)

        def ring(x, w):
            return overlap_dense_matmul(
                x, w, shard_axis=axis, axis_name="data", mesh=mesh, backend="pallas"
            )

        def plain(x, w):
            return jnp.matmul(
                x.astype(jnp.float32), w.astype(jnp.float32), precision="highest"
            )

        def fwd_bwd(dense, g):
            def run(x, w):
                y, vjp = jax.vjp(dense, x, w)
                return (y, *vjp(g.astype(y.dtype)))
            return run

        with mesh:
            got = jax.jit(fwd_bwd(ring, g))(x, w)
            want = jax.jit(fwd_bwd(plain, g))(x, w)
        for name, a, b in zip(("y", "dx", "dw"), got, want):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            worst[f"{site}.{name}"] = float(np.abs(a - b).max() / np.abs(b).max())
    say("fsdp_overlapped", ring_kernels_vs_plain_dot_rel_err={
        k: f"{v:.1e}" for k, v in worst.items()
    }, bound="2^-7")
    assert max(worst.values()) <= 2.0 ** -7, worst


def assert_on_four_devices(leg: str, result, sharded: bool) -> None:
    """Code that has only ever seen one chip, or eight pretend ones, may
    put everything on the first: the mesh must hold four distinct devices,
    every one of them must hold live bytes, and (tp, fsdp) a sharded
    parameter's shards must sit on four devices with a quarter each."""
    import jax

    mesh_ids = {d.id for d in result.mesh.devices.flat}
    assert len(mesh_ids) == 4, f"{leg}: mesh holds devices {sorted(mesh_ids)}"
    in_use = {d.id: device_bytes(d, "bytes_in_use") for d in jax.devices()}
    assert all(v > 0 for v in in_use.values()), f"{leg}: idle device in {in_use}"
    shard_note = "replicated"
    if sharded:
        big = max(
            (a for a in jax.tree.leaves(result.state.params)
             if not a.sharding.is_fully_replicated),
            key=lambda a: a.nbytes,
        )
        shards = big.addressable_shards
        on = {s.device.id for s in shards}
        sizes = {s.data.nbytes for s in shards}
        assert len(on) == 4, f"{leg}: largest sharded param sits on {sorted(on)}"
        # tp and fsdp split it four ways; PP×TP stacks it over 2 stages
        # and splits it over 2 model shards (or replicates it over one).
        want = {big.nbytes // 4} if leg != "3d_pp2_tp2" else {big.nbytes // 4, big.nbytes // 2}
        assert sizes <= want, f"{leg}: shard bytes {sizes} of a {big.nbytes}-byte parameter"
        shard_note = f"{tuple(big.shape)}->{len(shards)}x{max(sizes)}B"
    say(leg, devices=sorted(mesh_ids), bytes_in_use=in_use, largest_sharded_param=shard_note)


def phase_multichip(steps: int) -> None:
    """The parallel strategies this repo exists to compare, one process
    driving all four devices: flagship width, synthetic data, same seed,
    dropout 0, through ``trainer.train`` with the shipped configs and mesh
    overrides (global batch 8 at T=512; the long-context pair at its
    shipped batch 4). Every leg runs in bf16 as shipped (device placement,
    the Pallas ring, BF16_BAND) and again in fp32 at highest matmul
    precision (the parity tests' tolerances)."""
    import jax
    import numpy as np

    from dtc_tpu.config.loader import load_config
    from dtc_tpu.train.trainer import train

    def run_leg(leg, compute_dtype=None):
        """One trainer run; ``compute_dtype`` None keeps the shipped one."""
        name = leg["name"]
        train_cfg, model_cfg, opt_cfg = load_config(*map(config_path, leg["configs"]))
        as_shipped = compute_dtype is None
        compute_dtype = compute_dtype or model_cfg.compute_dtype
        out = fresh_dir(f"{name}_{'shipped' if as_shipped else compute_dtype}")
        train_cfg = replace(
            train_cfg, steps=steps, dataset="synthetic", output_dir=out, **leg["train"]
        )
        assert train_cfg.seed == 0, train_cfg
        model_cfg = replace(
            model_cfg, dropout=0.0, compute_dtype=compute_dtype, **leg["model"]
        )
        if name == "fsdp_overlapped" and as_shipped:
            assert_pallas_ring(model_cfg, train_cfg.batch, ring=4)
            assert_ring_kernels_match_plain_dot(model_cfg, train_cfg.batch)
        t0 = time.perf_counter()
        result = train(train_cfg, model_cfg, opt_cfg)
        wall_s = time.perf_counter() - t0
        assert len(result.losses) == steps
        # Four steps after five of warm-up are too few to insist on a
        # falling curve; they must be finite and below the uniform guess.
        assert np.all(np.isfinite(result.losses)), result.losses
        assert max(result.losses) < np.log(model_cfg.vocab_size), result.losses
        events = read_events(out)
        step_s = [e["step_time_s"] for e in events if e["etype"] == "step"]
        line = dict(
            compute=compute_dtype, mesh=dict(result.mesh.shape),
            batch=train_cfg.batch, seq=model_cfg.max_seq_len,
            compile_s=compile_seconds(events),
            median_step_s=statistics.median(step_s), wall_s=round(wall_s, 1),
            losses=[round(v, 5) for v in result.losses],
        )
        return result, line

    def report(leg, line, got, seen, tol):
        """Print the leg's line; hold it to its anchor where it has one."""
        name, anchor = leg["name"], leg["anchor"]
        if anchor is None:
            say(name, **line)
            return
        diff = float(np.abs(np.subtract(got, seen[anchor])).max())
        say(name, **line, vs=anchor, max_abs_loss_diff=f"{diff:.2e}", tolerance=tol)
        np.testing.assert_allclose(
            got, seen[anchor], rtol=tol, atol=tol,
            err_msg=f"{name} vs {anchor} ({line['compute']})",
        )

    shipped: dict[str, list[float]] = {}
    for leg in multichip_legs():
        result, line = run_leg(leg)
        assert_on_four_devices(leg["name"], result, sharded=leg["sharded"])
        report(leg, line, result.losses, shipped, BF16_BAND)
        shipped[leg["name"]] = result.losses
        del result

    exact: dict[str, list[float]] = {}
    with jax.default_matmul_precision("highest"):
        for leg in multichip_legs():
            if leg["tol"] is None:
                continue
            result, line = run_leg(leg, "float32")
            report(leg, line, result.losses, exact, leg["tol"])
            exact[leg["name"]] = result.losses
            del result
    say("ring_t4096", status="not run (its block kernels sit in a partially manual "
        "region, which the chip's compiler refuses: ROADMAP known defects)")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train, decode, serve on one chip (default); "
                    "4: only the parallel-training legs across four")
    ap.add_argument("--seed", type=int, default=0, help="seed of the random weights")
    ap.add_argument("--steps", type=int, default=0,
                    help="train steps (default 16 on one chip, 4 per leg on four)")
    args = ap.parse_args()

    from dtc_tpu.utils.dist import configure_compile_cache

    configure_compile_cache()
    import jax

    dev = jax.devices()
    if dev[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev[0].platform} ({dev[0].device_kind})")
    if len(dev) != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found {len(dev)} devices")
    say("device", platform=dev[0].platform, kind=dev[0].device_kind, count=len(dev),
        jax=jax.__version__)

    if args.chips == 1:
        phase_train(args.steps or 16)
        phase_decode(args.seed, new_tokens=32)
        phase_serve(args.seed, new_tokens=16)
    else:
        phase_multichip(args.steps or 4)
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev),
    }}))


if __name__ == "__main__":
    main()
