"""Overlapped training collectives (ISSUE 12, ops/overlap_collectives.py).

Three layers of evidence, all on the 8-virtual-device CPU mesh:

- **op parity** — the fused all-gather-matmul and the streamed grad
  reduce-scatter match the single-dot XLA oracle to fp roundoff, forward
  and backward, for BOTH transports: ``decomposed`` (ppermute rings) and
  ``pallas`` (the REAL RDMA kernels, run under Pallas interpret mode —
  the same kernels a TPU executes). Ring edge cases: degenerate 1-shard
  mesh, non-divisible block tails, batch narrower than the ring, bf16
  inputs.
- **training parity** — a full ``parallel: fsdp`` /
  ``collectives: overlapped`` run is loss-parity with the xla path, and
  the DP×FSDP×TP mesh (configs/train_config_3d.yaml's shape) is
  loss-parity with plain DP.
- **HLO structure** — the overlapped train step's compiled module holds
  the ring transport (collective-permute on this CPU) and has LOST the
  serialized per-layer kernel all-gathers; a TPU lowering of the op
  (``jax.export`` — no TPU needed) holds the Pallas custom-calls and no
  all-gather at all.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from dtc_tpu.ops import overlap_collectives as oc
from tests.conftest import make_train_cfg

pytestmark = pytest.mark.kernels


@pytest.fixture
def mesh8():
    return jax.make_mesh((8,), ("data",))


def _rand(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# op-level parity vs the XLA oracle


@pytest.mark.parametrize("backend", ["decomposed", "pallas"])
@pytest.mark.parametrize("shard_axis", [0, 1])
def test_ag_matmul_parity_fwd_bwd(mesh8, backend, shard_axis, monkeypatch):
    """Both transports, both shard modes: fwd product and BOTH grads
    match the single-dot oracle to fp roundoff. The pallas rows drive the
    real RDMA kernels in interpret mode (DTC_OVERLAP=pallas is the
    documented hook)."""
    monkeypatch.setenv("DTC_OVERLAP", backend)
    rng = np.random.default_rng(0)
    x = _rand(rng, 8, 4, 64)
    w = _rand(rng, 64, 128)

    def f(a, b):
        return jnp.sum(jnp.sin(oc.overlap_dense_matmul(
            a, b, shard_axis=shard_axis, axis_name="data", backend=backend
        )))

    with mesh8:
        y = jax.jit(lambda a, b: oc.overlap_dense_matmul(
            a, b, shard_axis=shard_axis, axis_name="data", backend=backend
        ))(x, w)
        dx, dw = jax.jit(jax.grad(f, argnums=(0, 1)))(x, w)
    np.testing.assert_allclose(y, x @ w, rtol=1e-5, atol=1e-5)
    ref_dx, ref_dw = jax.grad(
        lambda a, b: jnp.sum(jnp.sin(a @ b)), argnums=(0, 1)
    )(x, w)
    np.testing.assert_allclose(dx, ref_dx, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dw, ref_dw, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["decomposed", "pallas"])
def test_ag_matmul_bf16_parity(mesh8, backend):
    """bf16 inputs: ring partials accumulate in fp32 (the module
    contract), so the ring matches the oracle within bf16 resolution."""
    rng = np.random.default_rng(1)
    x = _rand(rng, 8, 4, 64, dtype=jnp.bfloat16)
    w = _rand(rng, 64, 128, dtype=jnp.bfloat16)
    with mesh8:
        y = jax.jit(lambda a, b: oc.overlap_dense_matmul(
            a, b, shard_axis=0, axis_name="data", backend=backend
        ))(x, w)
    assert y.dtype == jnp.bfloat16
    ref = (
        x.astype(jnp.float32) @ w.astype(jnp.float32)
    ).astype(jnp.bfloat16).astype(np.float32)
    np.testing.assert_allclose(
        y.astype(np.float32), ref, rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("backend", ["decomposed", "pallas"])
@pytest.mark.parametrize("shard_axis", [0, 1])
def test_reduce_scatter_matmul_vs_psum_scatter(
    mesh8, backend, shard_axis,
):
    """The standalone streamed reduce-scatter against the textbook
    oracle: psum_scatter of the local partial products."""
    rng = np.random.default_rng(2)
    a = _rand(rng, 16, 64)
    b = _rand(rng, 16, 128)
    with mesh8:
        got = jax.jit(lambda p, q: oc.reduce_scatter_matmul(
            p, q, shard_axis=shard_axis, axis_name="data", mesh=mesh8,
            backend=backend,
        ))(a, b)

        from jax import shard_map

        def oracle_local(al, bl):
            part = jnp.einsum(
                "mk,mn->kn", al, bl, preferred_element_type=jnp.float32
            )
            return lax.psum_scatter(
                part, "data", scatter_dimension=shard_axis, tiled=True
            )

        oracle = jax.jit(shard_map(
            oracle_local, mesh=mesh8, in_specs=(P("data"), P("data")),
            out_specs=P("data", None) if shard_axis == 0 else P(None, "data"),
            axis_names={"data"}, check_vma=False,
        ))(a, b)
    np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-5)


def test_degenerate_single_shard_mesh():
    """Ring of 1: the op must collapse to the plain dot (no shard_map, no
    permutes) and stay grad-correct."""
    mesh1 = jax.make_mesh((1,), ("data",))
    rng = np.random.default_rng(3)
    x = _rand(rng, 2, 4, 64)
    w = _rand(rng, 64, 32)
    with mesh1:
        y = jax.jit(lambda a, b: oc.overlap_dense_matmul(
            a, b, shard_axis=0, axis_name="data"
        ))(x, w)
    np.testing.assert_allclose(y, x @ w, rtol=1e-6, atol=1e-6)


def test_non_divisible_tails_fall_back(mesh8):
    """Shard or batch dims the ring cannot split evenly take the
    serialized-dot fallback — parity held, no crash (the 'auto-fallback
    for shapes the kernels don't support' contract)."""
    rng = np.random.default_rng(4)
    cases = [
        ((8, 4, 60), (60, 128), 0),   # K=60 not divisible by ring 8
        ((8, 4, 64), (64, 100), 1),   # N=100 not divisible by ring 8
        ((3, 4, 64), (64, 128), 0),   # batch 3 narrower than the ring
    ]
    for xshape, wshape, sa in cases:
        x = _rand(rng, *xshape)
        w = _rand(rng, *wshape)
        with mesh8:
            y = jax.jit(lambda a, b, sa=sa: oc.overlap_dense_matmul(
                a, b, shard_axis=sa, axis_name="data"
            ))(x, w)
        np.testing.assert_allclose(y, x @ w, rtol=1e-5, atol=1e-5)


def test_eager_and_axisless_calls_are_plain_dots():
    """model.init runs eagerly and generate() runs without FSDP rules —
    both must silently take the plain-dot path."""
    rng = np.random.default_rng(5)
    x = _rand(rng, 2, 4, 16)
    w = _rand(rng, 16, 8)
    y = oc.overlap_dense_matmul(x, w, shard_axis=0, axis_name="data")
    np.testing.assert_allclose(y, x @ w, rtol=1e-6)
    y2 = jax.jit(lambda a, b: oc.overlap_dense_matmul(
        a, b, shard_axis=0, axis_name=None
    ))(x, w)
    np.testing.assert_allclose(y2, x @ w, rtol=1e-6)


def test_fsdp_axis_in_scope_resolution(mesh8):
    """The sharding.py thread: the FSDP axis is visible exactly when the
    active rules shard embed_p onto a live mesh axis — and sequence-
    parallel rule sets defer (overlap+SP composition is future work)."""
    from flax import linen as nn

    from dtc_tpu.parallel.sharding import (
        DEFAULT_RULES, FSDP_RULES, fsdp_axis_in_scope, ring_rules_from,
    )

    with mesh8, nn.logical_axis_rules(FSDP_RULES):
        assert fsdp_axis_in_scope() == "data"
    with mesh8, nn.logical_axis_rules(DEFAULT_RULES):
        assert fsdp_axis_in_scope() is None
    # ring-derived FSDP rules map seq -> model; on a mesh where model is
    # trivial the ring is inert and FSDP overlap still applies…
    with mesh8, nn.logical_axis_rules(ring_rules_from(FSDP_RULES)):
        assert fsdp_axis_in_scope() == "data"
    # …but with a live model axis, SP owns the activations: defer.
    mesh42 = jax.make_mesh((4, 2), ("data", "model"))
    with mesh42, nn.logical_axis_rules(ring_rules_from(FSDP_RULES)):
        assert fsdp_axis_in_scope() is None
    with mesh42, nn.logical_axis_rules(FSDP_RULES):
        assert fsdp_axis_in_scope() == "data"


# ---------------------------------------------------------------------------
# training parity (the trainer-level route: TrainConfig.collectives)


@pytest.mark.quick
def test_fsdp_overlapped_matches_xla_losses(tiny_model_cfg, opt_cfg):
    """The acceptance bar: the overlapped FSDP step is grad-parity with
    the XLA path to fp roundoff — 4 full train steps, loss-for-loss."""
    from dtc_tpu.train.trainer import train

    r_xla = train(make_train_cfg("fsdp"), tiny_model_cfg, opt_cfg)
    r_ovl = train(
        make_train_cfg("fsdp", collectives="overlapped"),
        tiny_model_cfg, opt_cfg,
    )
    np.testing.assert_allclose(
        r_ovl.losses, r_xla.losses, rtol=2e-4, atol=2e-4
    )
    # Param sharding unchanged: the ring consumes the SAME placement.
    qk = r_ovl.state.params["stage"]["blocks"]["Block_0"]["attn"]["q_proj"]["kernel"]
    assert qk.sharding.spec == P(None, "data")


@pytest.mark.quick
def test_3d_overlapped_matches_dp_losses(tiny_model_cfg, opt_cfg):
    """The train_config_3d.yaml mode: DP×FSDP×TP (data=4, model=2) with
    overlapped collectives is loss-parity with plain DP — the ring rides
    the data axis while the explicit Megatron psums carry TP."""
    from dtc_tpu.config.schema import MeshConfig
    from dtc_tpu.train.trainer import train

    r_dp = train(make_train_cfg("dp"), tiny_model_cfg, opt_cfg)
    r_3d = train(
        make_train_cfg(
            "fsdp", collectives="overlapped",
            mesh=MeshConfig(data=4, model=2),
        ),
        tiny_model_cfg, opt_cfg,
    )
    np.testing.assert_allclose(r_3d.losses, r_dp.losses, rtol=5e-4, atol=5e-4)
    qk = r_3d.state.params["stage"]["blocks"]["Block_0"]["attn"]["q_proj"]["kernel"]
    assert qk.sharding.spec == P(None, "data", "model")


@pytest.mark.quick
def test_dropout_parity_under_partitionable_threefry(tiny_model_cfg, opt_cfg):
    """With dropout ACTIVE the two modes stay loss-parity under the
    partitionable threefry (the modern default; sharding-invariant random
    bits). Under this jax's LEGACY threefry, random bits are
    sharding-layout-dependent, so the ring's layouts select different —
    equally valid — dropout masks (the established 1F1B-vs-GPipe dropout
    semantics; create_1f1b_train_step documents the same class). This
    test pins that the divergence is mask SELECTION, not math: flip the
    flag and the trajectories coincide."""
    import dataclasses

    from dtc_tpu.train.trainer import train

    prev = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        drop = dataclasses.replace(tiny_model_cfg, dropout=0.1)
        r_xla = train(make_train_cfg("fsdp", steps=3), drop, opt_cfg)
        r_ovl = train(
            make_train_cfg("fsdp", steps=3, collectives="overlapped"),
            drop, opt_cfg,
        )
        np.testing.assert_allclose(
            r_ovl.losses, r_xla.losses, rtol=5e-4, atol=5e-4
        )
    finally:
        jax.config.update("jax_threefry_partitionable", prev)


def test_overlapped_rejected_under_pipeline(tiny_model_cfg, opt_cfg):
    from dtc_tpu.train.trainer import train

    with pytest.raises(ValueError, match="pipeline"):
        train(
            make_train_cfg("pp", collectives="overlapped", pp_microbatches=2),
            tiny_model_cfg, opt_cfg,
        )


def test_resolve_collectives_routes_both_configs(tiny_model_cfg):
    """The knob may arrive via EITHER config: a model-level 'overlapped'
    must survive the train-level 'xla' default (not be silently
    reverted), and the pipeline rejection must fire on every route in —
    including when both configs already agree on 'overlapped'."""
    import dataclasses

    from dtc_tpu.train.train_step import resolve_collectives

    t_xla = make_train_cfg("fsdp")
    model_ovl = dataclasses.replace(tiny_model_cfg, collectives="overlapped")
    assert resolve_collectives(t_xla, model_ovl).collectives == "overlapped"
    assert resolve_collectives(
        dataclasses.replace(t_xla, collectives="overlapped"), tiny_model_cfg
    ).collectives == "overlapped"
    # xla + xla: untouched (and no gratuitous replace).
    assert resolve_collectives(t_xla, tiny_model_cfg) is tiny_model_cfg
    t_pp = make_train_cfg(
        "pp", collectives="overlapped", pp_microbatches=2
    )
    with pytest.raises(ValueError, match="pipeline"):
        resolve_collectives(t_pp, model_ovl)


# ---------------------------------------------------------------------------
# HLO structure: the ring replaces the serialized gathers


@pytest.mark.slow
def test_overlapped_step_hlo_structure():
    """The compiled overlapped FSDP step (CPU lowering): the ring
    transport is present and the serialized layer-scan all-gathers are
    gone — the only "/blocks/"-scope gathers left are the rank-1 bias/LN
    assemblies (XLA-managed by design). The xla-mode module of the SAME
    config shows the serialized rank>=2 block gathers, proving the
    assertion bites."""
    from dtc_tpu.analysis import hlo
    from dtc_tpu.analysis.lowering import (
        audit_model_cfg, audit_opt_cfg, compiled_train_hlo,
    )
    from dtc_tpu.config.schema import MeshConfig
    from dtc_tpu.parallel.sharding import FSDP_RULES

    def block_gathers(txt):
        return [
            (d, dims) for d, dims, scope in hlo.all_gather_entries(txt)
            if "/blocks/" in scope and len(dims) >= 2
        ]

    ovl = compiled_train_hlo(
        "fsdp", MeshConfig(), audit_model_cfg(collectives="overlapped"),
        audit_opt_cfg(), FSDP_RULES,
    )
    census = hlo.collective_census(ovl)
    assert census.get("collective-permute", {}).get("count", 0) > 0, census
    assert block_gathers(ovl) == [], block_gathers(ovl)

    xla = compiled_train_hlo(
        "fsdp", MeshConfig(), audit_model_cfg(), audit_opt_cfg(), FSDP_RULES,
    )
    assert block_gathers(xla), (
        "the serialized baseline no longer shows layer-scan gathers — "
        "the structural assertion above is vacuous"
    )


def test_tpu_lowering_contains_pallas_custom_calls(mesh8, monkeypatch):
    """``jax.export`` for platform "tpu" (no TPU needed): the fused op's
    forward AND backward lower to Pallas custom-calls — and contain NO
    all-gather instruction at all (the gather IS the kernels' RDMA)."""
    from jax import export

    # Export must lower the REAL kernels, not interpret-mode emulation.
    monkeypatch.setattr(oc, "_interpret", lambda: False)
    rng = np.random.default_rng(6)
    x = _rand(rng, 8, 4, 1024)
    w = _rand(rng, 1024, 1024)  # ring blocks of 128: hardware-aligned

    def f(a, b):
        # sin keeps the primal output live in the grad program — without
        # it the forward kernel would be dead code under jax.grad (the
        # cotangent of a plain sum is independent of the primal).
        return jnp.sum(jnp.sin(oc.overlap_dense_matmul(
            a, b, shard_axis=0, axis_name="data", mesh=mesh8,
            backend="pallas",
        )))

    with mesh8:
        exp = export.export(
            jax.jit(jax.grad(f, argnums=(0, 1))), platforms=("tpu",)
        )(x, w)
    txt = exp.mlir_module()
    assert txt.count("tpu_custom_call") >= 3, (
        "expected the ag fwd + ag re-gather (dx) + streamed-rs (dw) "
        "kernels as tpu_custom_calls"
    )
    assert "all_gather" not in txt and "all-gather" not in txt
    # The lowering stamps kernel_name onto the custom-call lines — the
    # exact fingerprint the census rules key the ring transport on
    # (name-matched, so foreign Pallas kernels can never satisfy it).
    from dtc_tpu.analysis.hlo import (
        OVERLAP_KERNEL_TOKENS, PALLAS_CUSTOM_CALL_TARGET,
    )

    assert PALLAS_CUSTOM_CALL_TARGET in txt
    assert all(tok in txt for tok in OVERLAP_KERNEL_TOKENS)


# ---------------------------------------------------------------------------
# audit integration: the new entries' rule wiring (fabricated census)


def test_census_rules_for_overlapped_entries():
    """The graph-audit satellite, unit-level: an overlapped entry with
    neither permutes nor Pallas custom-calls trips the required-
    collective rule; either fingerprint alone satisfies it; a surviving
    per-layer kernel gather trips the serialized-layer-gather rule."""
    from dtc_tpu.analysis.lowering import Artifact
    from dtc_tpu.analysis.rules import audit_census

    def art(hlo_text):
        return Artifact(
            name="train_fsdp_overlapped", kind="train", parallel="fsdp",
            mesh_shape={"data": 8}, batch=8, seq_len=32,
            hlo_text=hlo_text, stablehlo_text="", expected_donated=0,
            param_shapes=[("f32", (4, 64, 128))], weak_outputs=0,
            n_layers=4, moe_experts=0, compute_dtype="float32",
        )

    bare = art("ENTRY %main {\n  %r = f32[8] add(x, y)\n}")
    rules_hit = [f.rule for f in audit_census(bare)]
    assert "census.required_collective" in rules_hit

    permute = art(
        "ENTRY %main {\n"
        "  %p = f32[8,128] collective-permute(%a)\n}"
    )
    assert "census.required_collective" not in [
        f.rule for f in audit_census(permute)
    ]

    # The overlap KERNELS' custom-calls satisfy the transport check —
    # matched by kernel_name, so a foreign Pallas kernel (flash, decode)
    # does NOT (the check would otherwise be vacuous on TPU).
    pallas = art(
        "ENTRY %main {\n"
        '  %c = f32[8,128] custom-call(%a), custom_call_target='
        '"tpu_custom_call", kernel_name = "_overlap_ag_matmul_kernel"\n}'
    )
    assert "census.required_collective" not in [
        f.rule for f in audit_census(pallas)
    ]
    foreign = art(
        "ENTRY %main {\n"
        '  %c = f32[8,128] custom-call(%a), custom_call_target='
        '"tpu_custom_call", kernel_name = "_flash_fwd_kernel"\n}'
    )
    assert "census.required_collective" in [
        f.rule for f in audit_census(foreign)
    ]

    # A rank-2 gather scoped INSIDE the layer scan trips the rule…
    leaked = art(
        "ENTRY %main {\n"
        "  %p = f32[8,128] collective-permute(%a)\n"
        "  %g = f32[64,128] all-gather(%b), metadata={op_name="
        '"jit(s)/fwd/GPT/stage/while/body/blocks/Block_0/mlp/fc1/dot"}\n}'
    )
    assert "census.serialized_layer_gather" in [
        f.rule for f in audit_census(leaked)
    ]
    # …while the SAME shape at the head (lm_head on the tiny model) and
    # rank-1 bias/LN assemblies inside blocks are legitimate.
    legit = art(
        "ENTRY %main {\n"
        "  %p = f32[8,128] collective-permute(%a)\n"
        "  %g = f32[64,128] all-gather(%b), metadata={op_name="
        '"jit(s)/fwd/GPT/head/dot_general"}\n'
        "  %h = f32[64] all-gather(%c), metadata={op_name="
        '"jit(s)/fwd/GPT/stage/while/body/blocks/Block_0/ln_1/mul"}\n}'
    )
    assert "census.serialized_layer_gather" not in [
        f.rule for f in audit_census(legit)
    ]


def test_stacked_gather_rule_catches_compute_dtype_cast():
    """The hoisted-stacked-gather rule accepts the COMPUTE dtype too: XLA
    sinks the fp32->bf16 convert below the gather, so the hoisted form of
    an fp32 stacked param can land as bf16[L, ...] — while incidental
    integer buffers sharing the leading dim stay excluded."""
    from dtc_tpu.analysis.lowering import Artifact
    from dtc_tpu.analysis.rules import audit_census

    def art(body):
        return Artifact(
            name="train_fsdp", kind="train", parallel="fsdp",
            mesh_shape={"data": 8}, batch=8, seq_len=32,
            hlo_text=(
                "ENTRY %m {\n  %ar = f32[1] all-reduce(%g)\n"
                "  %pid = u32[] partition-id()\n" + body + "}"
            ),
            stablehlo_text="", expected_donated=0,
            param_shapes=[("f32", (4, 64, 128))], weak_outputs=0,
            n_layers=4, moe_experts=0, compute_dtype="bfloat16",
        )

    cast = art("  %ag = bf16[4,64,128]{2,1,0} all-gather(%w)\n")
    assert "census.stacked_param_gather" in [
        f.rule for f in audit_census(cast)
    ]
    idx = art("  %ag = s32[4,32,1]{2,1,0} all-gather(%i)\n")
    assert "census.stacked_param_gather" not in [
        f.rule for f in audit_census(idx)
    ]


def test_pallas_custom_call_census_parser():
    from dtc_tpu.analysis import hlo

    txt = (
        "ENTRY %main {\n"
        '  %c1 = f32[8,128] custom-call(%a), custom_call_target='
        '"tpu_custom_call"\n'
        '  %c2 = (f32[4,4], f32[2,2]) custom-call(%b), custom_call_target='
        '"tpu_custom_call"\n'
        '  %other = f32[8] custom-call(%d), custom_call_target="cholesky"\n}'
    )
    cc = hlo.pallas_custom_calls(txt)
    assert cc["count"] == 2
    assert cc["bytes"] == 8 * 128 * 4 + (16 + 4) * 4
    census = hlo.collective_census(txt)
    assert census["pallas_custom_call"] == cc
    # kernel-free module: no row at all (pre-ISSUE-12 baselines stay
    # byte-identical).
    assert "pallas_custom_call" not in hlo.collective_census("%r = add()")
    # The NAME-matched overlap-kernel parser: only kernel_name lines with
    # an overlap token count (foreign Pallas kernels are excluded).
    named = (
        "ENTRY %m {\n"
        '  %c1 = f32[8,128] custom-call(%a), custom_call_target='
        '"tpu_custom_call", kernel_name = "_overlap_rs_matmul_kernel"\n'
        '  %c2 = f32[8,128] custom-call(%b), custom_call_target='
        '"tpu_custom_call", kernel_name = "_flash_fwd_kernel"\n}'
    )
    ok = hlo.overlap_kernel_custom_calls(named)
    assert ok == {"count": 1, "bytes": 8 * 128 * 4}


# ---------------------------------------------------------------------------
# metrics: the 3d comm terms + devprof recognition


def test_tp_sharded_param_count_matches_rule_table(tiny_model_cfg):
    """The estimator's TP-sharded split must equal what the rule table
    actually shards over "model" — computed from param_specs, so the two
    can never silently diverge."""
    from dtc_tpu.models.gpt import GPT
    from dtc_tpu.parallel.sharding import param_specs
    from dtc_tpu.utils.metrics import tp_sharded_param_count

    model = GPT(tiny_model_cfg)
    params = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
            jnp.ones((1, tiny_model_cfg.max_seq_len), jnp.int32),
            train=False,
        )
    )["params"]
    specs = param_specs(params)
    total = 0
    for leaf, spec in zip(
        jax.tree.leaves(params),
        jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)),
    ):
        if "model" in tuple(spec):
            total += int(np.prod(leaf.shape))
    assert tp_sharded_param_count(tiny_model_cfg) == total


def test_comm_bytes_3d_terms(tiny_model_cfg):
    """Hand-computed DP×FSDP×TP estimate: FSDP factor 3 over the honest
    per-device share (TP-sharded params / model + TP-replicated rest),
    plus the unchanged Megatron activation term."""
    from dtc_tpu.models.gpt import param_count
    from dtc_tpu.utils.metrics import (
        comm_bytes_per_step, tp_sharded_param_count,
    )

    cfg = tiny_model_cfg
    mesh = {"data": 4, "model": 2, "pipe": 1}
    got = comm_bytes_per_step(cfg, 8, 32, mesh, "fsdp")
    n, n_tp = param_count(cfg), tp_sharded_param_count(cfg)
    local = n_tp / 2 + (n - n_tp)
    assert got["dp_allreduce"] == pytest.approx(3.0 * 3 / 4 * local * 4)
    act = 8 * 32 * cfg.d_model * 4 / 4          # per-device batch shard
    assert got["tp_allreduce"] == pytest.approx(
        4.0 * cfg.n_layers * 2.0 * 1 / 2 * act
    )
    # Pure FSDP (model=1) keeps the historical formula bit-for-bit — the
    # committed train_fsdp baseline pins it.
    old = comm_bytes_per_step(cfg, 8, 32, {"data": 8}, "fsdp")
    assert old["dp_allreduce"] == pytest.approx(3.0 * 7 / 8 * n * 4)


def test_devprof_fused_collective_recognition():
    """Device rows named after the overlap kernels count as fused
    collectives (compute + structural overlap), and the breakdown view
    reports exposed vs hidden per collective."""
    from dtc_tpu.obs.devprof import (
        OpRow, attribute, overlap_breakdown,
    )

    def row(name, hlo_op, t0, dur, kind):
        return OpRow(
            name=name, hlo_op=hlo_op, hlo_module="m", scope="",
            t0_s=t0, dur_s=dur, pid=1, tid=1, kind=kind,
        )

    rows = [
        row("fusion.1", "fusion.1", 0.0, 1.0, "compute"),
        # a collective half-hidden under the fusion
        row("all-gather.2", "all-gather.2", 0.5, 1.0, "collective"),
        # the fused ring kernel
        row(
            "overlap_ag_matmul_kernel", "custom-call.3", 2.0, 0.5,
            "compute",
        ),
    ]
    att = attribute(rows)
    assert att.fused_collective_s == pytest.approx(0.5)
    assert att.collective_s == pytest.approx(1.0)
    assert att.overlap_ratio == pytest.approx(0.5)

    bd = overlap_breakdown(rows)
    coll = [d for d in bd if not d["fused"]]
    assert len(coll) == 1
    assert coll[0]["overlapped_s"] == pytest.approx(0.5)
    assert coll[0]["exposed_s"] == pytest.approx(0.5)
    assert coll[0]["under"][0][0] == "fusion.1"
    fused = [d for d in bd if d["fused"]]
    assert len(fused) == 1 and fused[0]["exposed_s"] == 0.0
