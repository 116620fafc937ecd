"""Models described by a layer pattern.

``ModelConfig.layer_pattern`` states ONE period of the stack: per position
a mixer kind and an FFN kind (``"gdn+moe_shared"``);
``ModelConfig.leading_pattern`` the layers that come once before the
periods (a model's leading dense layers). The schema lists the kinds' names
and this file maps them to modules (:data:`MIXERS`, :data:`FFNS`);
:class:`PatternLM` runs the leading layers, then scans over periods, each
layer under its own ``nn.remat`` as ``GPTStage`` does, and
:func:`build_model` gives ``trainer.train`` this model or ``GPT`` from the
configuration alone. An empty pattern is the GPT-2 block of
``models/gpt.py``, untouched.

Every pattern layer is residual, pre-normed or (``norm_placement:
sandwich``: ``norm_1_post`` / ``norm_2_post``) normed on both sides::

    x += mixer(rms(x; w1));  x += ffn(rms(x; w2))                          (pre)
    x += rms(mixer(rms(x; w1)); w1');  x += rms(ffn(rms(x; w2)); w2')      (sandwich)
    rms(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)       (norm_gain zero_centred)
              = x * rsqrt(mean(x^2) + eps) * w             (norm_gain plain)

with a token embedding only (positions are the mixers' business), a final
``rms`` and a head without bias — its own leaf, or with ``tie_embeddings``
the embedding transposed — through the fused head+CE op that ``GPTHead``
uses. No biases, no dropout.

Passes (``stack_passes`` T > 1: a looped stack). The scanned
periods run T times on the SAME leaves: ONE outer scan whose body is the
stack and the head's readout and into which the parameters are broadcast
(``nn.scan`` over a function of this module, so the tree keeps its paths and
the step holds one copy of the stack; every leaf's gradient is the sum over
its T uses), the periods' scan inside it, each layer under its own remat.
After EVERY pass the final norm, whose output the next pass starts from, the
head (one ``head/lm_head`` leaf, per-token cross-entropy ``CE_t`` through
``ops/fused_ce.fused_head_ce_tokens``) and the exit gate ``z_t = h_t .
w + b`` (``head/exit_gate``, float32). After the last pass, per token::

    log p_t = log_sigmoid(z_t) + sum_{j<t} log_sigmoid(-z_j)    (t < T)
    log p_T =                    sum_{j<T} log_sigmoid(-z_j)    (what is left; z_T is unused)
    loss    = mean_n [ sum_t p_t CE_t  +  EXIT_BETA * sum_t p_t log p_t ]

Nothing is detached. Without ``targets`` the loop runs without readouts and
the last pass's logits come back; the passes' logits never exist together.
No pass's logits are kept for the backward: the op makes them again
there (T passes' do not fit beside the state; PERF.md section 4).

Counters (collection ``counters``, by the name sowed; ``train_step`` hands
them on as ``{name: rows}`` beside the loss): ``"moe"`` — one row of
:data:`COUNTERS` an expert layer, and a pass where the stack is looped;
``"passes"`` — one row of :data:`PASS_COUNTERS` a pass, sowed once by the
model after the last pass. The trainer emits a ``moe_counters`` and a
``pass_counters`` event a step from them.

Mixers:

- ``gated_attn`` / ``attn`` — softmax attention, ``n_heads`` query heads on
  ``n_kv_heads`` KV heads of ``head_dim``; q and k RMS-normed over the
  head (``qk_norm``, else neither); rotary positions on the first ``rope_fraction`` of the head,
  half-split pairing. ``gated_attn``'s query projection also yields a
  per-head output gate; ``attn`` has none. Through
  ``ops/attention.causal_attention`` (flash on the chip, KV groups picked by
  the kernels' index maps). Where the packed flash family will run and q
  and k come straight from their matmuls (:func:`packed_rotary_plan`), they are
  rotated as the projections wrote them, ``(B, T, H * head_dim)``, by one
  Mosaic pass (``ops/rotary.py``) and reach the kernel without a 4-D view.
- ``shortconv`` — a gated short convolution: one projection to (B, C, u),
  ``out_proj(C * conv(B * u))`` with a depthwise causal convolution of
  ``shortconv_width`` taps, no bias and no activation.
- ``gdn`` — Gated DeltaNet (``ops/gated_delta.py``): one fused projection
  to (q, k, v, z), a second to (b, a); a depthwise causal convolution and
  SiLU over (q, k, v); ``beta = sigmoid(b)``, ``g = -exp(A_log) *
  softplus(a + dt_bias)``; q, k L2-normalised; the chunked delta-rule scan;
  a gain-only RMS norm of the output, gated by ``silu(z)``.

FFN:

- ``moe_shared`` / ``moe`` — the router scores ``moe_experts``, keeps
  ``moe_top_k`` (gates renormalised), and this process computes the part of
  the sum that its held experts give (``ops/moe_dispatch.held_experts``:
  nothing dropped); ``moe_shared`` adds a shared SwiGLU expert behind a
  sigmoid gate. The router's form is the model's (``moe_score``,
  ``moe_selection_bias``, ``moe_routed_scale``): softmax probabilities, or
  sigmoid scores chosen with a per-expert bias that never enters the gate.
- ``swiglu`` — a dense SwiGLU of width ``d_ff``.

The float32 islands are the norms, the router and its scores, the short
convolutions' taps, the decay and the scan's carried state, the exit gate,
``log p``, the entropy and the loss over them; the matmuls run in
``compute_dtype``.

Scopes on the device path (``benchmark/spans.py`` reads the op-name path):
``gdn`` with ``proj`` / ``conv`` / ``scan`` / ``out``; ``shortconv`` with
``proj`` / ``conv`` / ``out``; ``attn_full`` with ``attn_qkv`` (the
projections, q / k norms and rotary), ``attn_kernel`` around the kernel call
and ``attn_proj``; ``moe`` with ``router`` / ``dispatch`` / ``experts`` /
``combine`` / ``shared``; ``mlp``; ``post_norm`` (a sandwich layer's two
outer norms); ``head`` (every pass's norm, head and CE); ``exit`` (the gate
under ``head``, and the exit distribution and the loss after the last pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from dtc_tpu.config.schema import ModelConfig, pattern_kinds
from dtc_tpu.models.gpt import _dtype
from dtc_tpu.ops import moe_dispatch as md
from dtc_tpu.ops.attention import causal_attention
from dtc_tpu.ops.gated_delta import gated_delta_chunked, supports_chunk_kernel
from dtc_tpu.ops.rotary import packed_rotary, supports_packed_rotary

#: The per-step counters a pattern model sows (collection ``counters``),
#: one row an expert layer; the train step returns them beside the loss.
#: The last only where the router chooses with a selection bias: the
#: choices plain top-k of the scores would not have made.
COUNTERS = (*md.HELD_COUNTERS, "moe_bias_swapped")

#: A looped stack's readings, one row a pass (``"passes"`` of the counters):
#: the tokens' mean exit probability and mean cross-entropy at that pass, and
#: the mean entropy of the exit distribution (one number, in every row).
PASS_COUNTERS = ("exit_p", "pass_ce", "exit_entropy")

#: ``moe_score: sigmoid``: the chosen scores are normalised over their sum
#: plus this.
SIGMOID_GATE_EPS = 1e-6

#: A looped stack's loss takes this times the exit distribution's entropy off
#: the expected cross-entropy (a uniform prior over the passes).
EXIT_BETA = 0.1

NOT_SERVED = (
    "a layer-pattern model trains only: there is no cache for recurrent "
    "state yet, nor a convolution's, so generate / ServingEngine cannot run it"
)


def _dense(features: int, name: str, cfg: ModelConfig) -> nn.Dense:
    return nn.Dense(features, name=name, use_bias=False,
                    dtype=_dtype(cfg.compute_dtype), param_dtype=_dtype(cfg.param_dtype))


class RMSNorm(nn.Module):
    """Float32 RMS norm over the last axis. ``zero_centred``: the gain is
    ``1 + w`` with ``w`` starting at 0; else a plain gain starting at 1."""

    eps: float
    zero_centred: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        init = nn.initializers.zeros_init() if self.zero_centred else nn.initializers.ones_init()
        w = self.param("scale", init, (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps)
        return x * (1.0 + w if self.zero_centred else w)


def _norm(cfg: ModelConfig, name: str) -> RMSNorm:
    return RMSNorm(cfg.norm_eps, zero_centred=cfg.norm_gain == "zero_centred", name=name)


def rotary(x: jax.Array, theta: float, fraction: float) -> jax.Array:
    """Rotary positions on the first ``fraction`` of the last axis of
    ``x`` (B, T, H, D), half-split pairing; the rest passes."""
    t, d = x.shape[1], x.shape[-1]
    rot = int(d * fraction)
    if rot == 0:
        return x
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]          # (T, rot/2)
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1), jnp.float32)[None, :, None]
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    xr = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([xr, rest], -1)


def packed_rotary_plan(cfg: ModelConfig, t: int, gated: bool) -> dict | None:
    """The kernel's plan where an attention layer's q and k go from their
    projections to the flash kernel as ``(B, T, H * head_dim)``, rotated there
    by ``ops/rotary.packed_rotary``; None where :func:`rotary` runs on the
    ``(B, T, H, head_dim)`` view. Packed takes all of: the attention resolves
    to flash without KV groups (a head of one lane tile, which the kernel
    asks for, is a lane group of the packed family), q and k come straight
    from their own matmuls in the compute dtype (no q / k norm, no output
    gate cut from the query projection), and the kernel holds the shape
    (:func:`~dtc_tpu.ops.rotary.supports_packed_rotary`)."""
    from dtc_tpu.config.schema import DTYPE_BYTES
    from dtc_tpu.ops.attention import resolve_impl

    hd, h = cfg.head_dim, cfg.n_heads
    if (gated or cfg.qk_norm or cfg.kv_heads != h
            or resolve_impl(cfg.attention, t, hd, cfg.attention_block_q,
                            cfg.attention_block_kv) != "flash"):
        return None
    return supports_packed_rotary(hd, cfg.rope_fraction, h, t, DTYPE_BYTES[cfg.compute_dtype])


class Attention(nn.Module):
    """``gated``: the query projection is twice as wide and its second half
    gates the heads' output through a sigmoid."""

    cfg: ModelConfig
    gated: bool

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, t, _ = x.shape
        h, hk, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        cdtype = _dtype(cfg.compute_dtype)
        packed = packed_rotary_plan(cfg, t, self.gated) is not None
        with jax.named_scope("attn_qkv"):
            def heads(a):
                return a.reshape(b, t, -1, hd)

            # packed: q and k stay as the projections wrote them until they are
            # rotated; the views taken then and the flash entry's own reshape cancel
            view = (lambda a: a) if packed else heads
            if self.gated:
                qg = _dense(h * 2 * hd, "q_proj", cfg)(x).reshape(b, t, h, 2 * hd)
                q, gate = qg[..., :hd], qg[..., hd:]
            else:
                q = view(_dense(h * hd, "q_proj", cfg)(x))
            k = view(_dense(hk * hd, "k_proj", cfg)(x))
            v = heads(_dense(hk * hd, "v_proj", cfg)(x))
            if packed:
                q, k = _rows_per_data_shard(
                    functools.partial(packed_rotary, theta=cfg.rope_theta, head_dim=hd), q, k)
                q, k = heads(q), heads(k)
            else:
                q_norm, k_norm = ((_norm(cfg, "q_norm"), _norm(cfg, "k_norm")) if cfg.qk_norm
                                  else (lambda a: a,) * 2)
                q = rotary(q_norm(q), cfg.rope_theta, cfg.rope_fraction)
                k = rotary(k_norm(k), cfg.rope_theta, cfg.rope_fraction)
                q, k = q.astype(cdtype), k.astype(cdtype)
        with jax.named_scope("attn_kernel"):
            out = causal_attention(
                q, k, v, impl=cfg.attention,
                block_q=cfg.attention_block_q, block_kv=cfg.attention_block_kv,
            )
        with jax.named_scope("attn_proj"):
            if self.gated:
                out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(cdtype)
            return _dense(cfg.d_model, "out_proj", cfg)(out.reshape(b, t, h * hd))


def causal_depthwise_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """``y_t = sum_j w[:, j] * x_{t - (W-1) + j}`` over (B, T, C) with
    ``w`` (C, W): W shifted multiply-adds, zeros before the sequence."""
    width = w.shape[1]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j: j + t] * w[:, j] for j in range(width))


class GatedDeltaNet(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, t, _ = x.shape
        hk, hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
        cdtype, f32 = _dtype(cfg.compute_dtype), jnp.float32
        nk, nv = hk * dk, hv * dv
        with jax.named_scope("proj"):
            qkvz = _dense(2 * nk + 2 * nv, "in_proj_qkvz", cfg)(x)
            ba = _dense(2 * hv, "in_proj_ba", cfg)(x).astype(f32)
        with jax.named_scope("conv"):
            w = self.param("conv", nn.initializers.lecun_normal(), (2 * nk + nv, cfg.gdn_conv_width),
                           _dtype(cfg.param_dtype))
            # float32 inside the fusion, compute dtype in HBM
            qkv = jax.nn.silu(causal_depthwise_conv(
                qkvz[..., : 2 * nk + nv].astype(f32), w.astype(f32))).astype(cdtype)
            z = qkvz[..., 2 * nk + nv:].reshape(b, t, hv, dv)
        with jax.named_scope("scan"):
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)),
                (hv,))
            dt_bias = self.param("dt_bias", nn.initializers.ones_init(), (hv,), f32)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)

            def unit(v, scale=1.0):  # L2 over the head, in float32
                v = v.astype(f32)
                v = v * (scale * jax.lax.rsqrt(jnp.sum(jnp.square(v), axis=-1, keepdims=True) + 1e-6))
                return v.astype(cdtype)

            q = unit(qkv[..., :nk].reshape(b, t, hk, dk), dk ** -0.5)
            k = unit(qkv[..., nk: 2 * nk].reshape(b, t, hk, dk))
            # each key head serves hv / hk value heads: the scan picks it
            v = qkv[..., 2 * nk:].reshape(b, t, hv, dv)
            o = _rows_per_data_shard(
                functools.partial(gated_delta_chunked, chunk=cfg.gdn_chunk, dtype=cdtype),
                q, k, v, g, beta)
        with jax.named_scope("out"):
            o = RMSNorm(cfg.norm_eps, zero_centred=False, name="norm")(o)
            o = (o * jax.nn.silu(z.astype(f32))).astype(cdtype)
            return _dense(cfg.d_model, "out_proj", cfg)(o.reshape(b, t, nv))


class ShortConv(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        d = cfg.d_model
        cdtype, f32 = _dtype(cfg.compute_dtype), jnp.float32
        with jax.named_scope("proj"):
            bcu = _dense(3 * d, "in_proj", cfg)(x)
        with jax.named_scope("conv"):
            w = self.param("conv", nn.initializers.lecun_normal(), (d, cfg.shortconv_width),
                           _dtype(cfg.param_dtype))
            # float32 inside the fusion, compute dtype in HBM
            gate_in, gate_out, u = (bcu[..., i * d: (i + 1) * d].astype(f32) for i in range(3))
            y = (gate_out * causal_depthwise_conv(gate_in * u, w.astype(f32))).astype(cdtype)
        with jax.named_scope("out"):
            return _dense(d, "out_proj", cfg)(y)


class SwiGLU(nn.Module):
    cfg: ModelConfig
    width: int

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = jax.nn.silu(_dense(self.width, "gate_proj", cfg)(x)) * _dense(self.width, "up_proj", cfg)(x)
        return _dense(cfg.d_model, "down_proj", cfg)(h)


def _free_axes():
    """(mesh, its axes that are not manual yet) under a mesh of more than
    one device with such an axis; else None (one device, no mesh, an already
    manual region)."""
    from jax._src.core import trace_state_clean

    from dtc_tpu.parallel.sharding import ambient_mesh

    mesh = None if trace_state_clean() else ambient_mesh(allow_empty=True)
    if mesh is None or mesh.size == 1:
        return None
    free = set(mesh.axis_names) - set(mesh.manual_axes)
    return (mesh, free) if free else None


def _data_axis(batch: int):
    """(mesh, axis, free axes) where the batch axis of the activations is
    laid over a free mesh axis under the active rules and divides ``batch``;
    else None (:func:`_free_axes` is, or the batch-1 ``model.init`` trace)."""
    where = _free_axes()
    if where is None:
        return None
    mesh, free = where
    axis = dict(nn.get_logical_axis_rules()).get("batch")
    if axis not in free or batch % dict(mesh.shape)[axis]:
        return None
    return mesh, axis, free


def _rows_per_data_shard(fn, *args):
    """``fn`` over batch-leading arrays, each device on its own rows: XLA
    cannot partition a Mosaic kernel (the scan's fused kernels), and the
    scan is independent across rows, so on a mesh it runs in a region that
    is manual over every free axis, as the flash kernel does — whole on
    every device where the rows do not divide (the batch-1 ``model.init``
    trace)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    where = _free_axes()
    if where is None:
        return fn(*args)
    mesh, free = where
    data = _data_axis(args[0].shape[0])
    rows = P(data[1]) if data else P()
    return shard_map(fn, mesh=mesh, in_specs=(rows,) * len(args), out_specs=rows,
                     axis_names=free, check_vma=False)(*args)


def _per_data_shard(fn, where, x, *rest):
    """``fn`` on each device's own tokens. The loop over tiles has a
    device's own trip count, so on a mesh (``where``: :func:`_data_axis`)
    the sort, the experts' matmuls and the scatter run inside a region that
    is manual over every free mesh axis (as the flash kernel does,
    ``ops/attention._flash_per_shard``): tokens stay where their batch rows
    are, the weights arrive whole (under FSDP: gathered at the region's
    edge), and the counters are summed / maxed over the devices."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if where is None:
        return fn(x, *rest)
    mesh, axis, free = where

    def local(x, *rest):
        y, c = fn(x, *rest)
        total, peak = jax.lax.psum(c, axis), jax.lax.pmax(c, axis)
        # assigned and dropped add up; the fullest expert anywhere; the
        # mean load of a device's held experts, averaged; the most flushes
        # a device ran
        return y, jnp.stack([total[0], peak[1], total[2] / jax.lax.psum(1, axis), total[3], peak[4]])

    tok = P(axis)
    return shard_map(
        local, mesh=mesh, in_specs=(tok, tok, tok, *(P(),) * (len(rest) - 2)),
        out_specs=(tok, P()), axis_names=free, check_vma=False,
    )(x, *rest)


class ExpertLayer(nn.Module):
    """``shared``: one SwiGLU expert of ``moe_shared_d_ff`` behind a sigmoid
    gate is added to the held experts' part of the routed sum."""

    cfg: ModelConfig
    shared: bool

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, t, d = x.shape
        e, k, held, f = cfg.moe_experts, cfg.moe_top_k, cfg.experts_held, cfg.moe_d_ff
        cdtype, pdtype = _dtype(cfg.compute_dtype), _dtype(cfg.param_dtype)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1, batch_axis=(0,))
        w_gate = self.param("w_gate", init, (held, d, f), pdtype)
        w_up = self.param("w_up", init, (held, d, f), pdtype)
        w_down = self.param("w_down", init, (held, f, d), pdtype)
        with jax.named_scope("router"):
            logits = nn.Dense(e, name="router", use_bias=False, dtype=jnp.float32,
                              param_dtype=jnp.float32)(x.astype(jnp.float32)).reshape(b * t, e)
            sigmoid = cfg.moe_score == "sigmoid"
            scores = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)
            bias = (self.param("expert_bias", nn.initializers.zeros_init(), (e,), jnp.float32)
                    if cfg.moe_selection_bias else None)
            gates, idx = md.top_k_gates(
                scores, k, bias=bias, eps=SIGMOID_GATE_EPS if sigmoid else 0.0,
                scale=cfg.moe_routed_scale)
        y, counters = _per_data_shard(
            functools.partial(md.held_experts, first=cfg.moe_expert_rank * held, published=e),
            _data_axis(b), x.reshape(b * t, d), gates, idx,
            w_gate.astype(cdtype), w_up.astype(cdtype), w_down.astype(cdtype),
        )
        if bias is not None:
            with jax.named_scope("router"):
                counters = jnp.append(counters, md.bias_swapped(scores, idx))
        self.sow("counters", "moe", counters)
        y = y.reshape(b, t, d)
        if self.shared:
            with jax.named_scope("shared"):
                gate = nn.Dense(1, name="shared_gate", use_bias=False, dtype=jnp.float32,
                                param_dtype=pdtype)(x.astype(jnp.float32))
                shared = SwiGLU(cfg, cfg.moe_shared_d_ff, name="shared")(x)
                y = y + jax.nn.sigmoid(gate) * shared.astype(jnp.float32)
        return y.astype(cdtype)


#: mixer kind -> (module of a configuration, its scope on the device path)
MIXERS = {
    "gdn": (GatedDeltaNet, "gdn"),
    "gated_attn": (functools.partial(Attention, gated=True), "attn_full"),
    "attn": (functools.partial(Attention, gated=False), "attn_full"),
    "shortconv": (ShortConv, "shortconv"),
}
#: ffn kind -> (module, scope)
FFNS = {
    "moe_shared": (functools.partial(ExpertLayer, shared=True), "moe"),
    "moe": (functools.partial(ExpertLayer, shared=False), "moe"),
    "swiglu": (lambda cfg, name: SwiGLU(cfg, cfg.d_ff, name=name), "mlp"),
}


class PatternBlock(nn.Module):
    cfg: ModelConfig
    kinds: tuple[str, str]

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        cdtype = _dtype(cfg.compute_dtype)
        (mixer_cls, mixer_name), (ffn_cls, ffn_name) = MIXERS[self.kinds[0]], FFNS[self.kinds[1]]
        def post(y, name):
            if cfg.norm_placement != "sandwich":
                return y
            with jax.named_scope("post_norm"):
                return _norm(cfg, name)(y).astype(cdtype)

        h = _norm(cfg, "norm_1")(x).astype(cdtype)
        x = x + post(mixer_cls(cfg, name=mixer_name)(h), "norm_1_post")
        h = _norm(cfg, "norm_2")(x).astype(cdtype)
        x = x + post(ffn_cls(cfg, name=ffn_name)(h), "norm_2_post")
        return nn.with_logical_constraint(x, ("batch", "seq", "embed"))


class _Layers(nn.Module):
    """The layers of ``entries`` in order, each under its own remat: one
    period of the pattern under ``nn.scan``, or the leading layers once."""

    cfg: ModelConfig
    train: bool
    entries: tuple

    @nn.compact
    def __call__(self, h, _=None):
        cls = PatternBlock
        mode = self.cfg.remat_mode
        if mode != "none" and self.train:
            # prevent_cse stays on: a period's layers are unrolled in ONE
            # scan iteration, where XLA would merge a layer's recomputation
            # with its forward and keep every activation after all
            # (GPTStage's one-block scan body cannot be merged that way).
            kwargs: dict = {}
            if mode == "block_save_flash":
                kwargs["policy"] = jax.checkpoint_policies.save_only_these_names(
                    "flash_out", "flash_lse", "flash_q", "flash_k", "flash_v")
            cls = nn.remat(cls, **kwargs)
        for position, entry in enumerate(self.entries):
            h = cls(self.cfg, pattern_kinds(entry), name=f"layer_{position}")(h)
        return h, None


class PatternEmbed(nn.Module):
    cfg: ModelConfig

    def setup(self):
        self.wte = nn.Embed(self.cfg.padded_vocab_size, self.cfg.d_model,
                            param_dtype=_dtype(self.cfg.param_dtype))

    def __call__(self, x: jax.Array) -> jax.Array:
        return nn.with_logical_constraint(
            self.wte(x).astype(_dtype(self.cfg.compute_dtype)), ("batch", "seq", "embed"))

    def table(self) -> jax.Array:
        """The embedding (vocab, d): a tied head's weight, transposed."""
        return self.wte.embedding


class PatternStage(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, h: jax.Array, *, train: bool) -> jax.Array:
        cfg = self.cfg
        if cfg.leading_pattern:
            h, _ = _Layers(cfg, train, cfg.leading_pattern, name="leading")(h)
        scanned = nn.scan(
            _Layers,
            variable_axes={"params": 0, "counters": 0},
            split_rngs={"params": True},
            length=cfg.pattern_periods,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(cfg, train, cfg.layer_pattern, name="periods")
        h, _ = scanned(h, None)
        return h


def _exit_gate(dense: type, pdtype) -> nn.Module:
    """A looped stack's exit gate, of the ``dense`` class handed in: a
    ``Linear(d -> 1)`` with bias that computes in float32 whatever the
    compute dtype (``log p``, the entropy and the loss over them take their
    dtype from its score)."""
    return dense(1, dtype=jnp.float32, param_dtype=pdtype)


class PatternHead(nn.Module):
    """Final RMS norm and the head without bias, through the fused head +
    cross-entropy op (``ops/fused_ce.py``) when ``targets`` are given. The
    head's weight is this module's ``lm_head`` leaf, or with
    ``tie_embeddings`` the embedding ``tied`` (vocab, d) that the caller
    hands in, transposed: that leaf then gets both gradients. The op folds a
    bias gradient into its dW matmul; the zero bias passed here is a
    constant, its gradient discarded. A looped stack calls the parts, once
    a pass: :meth:`norm`, then :meth:`token_losses` and :meth:`gate`."""

    cfg: ModelConfig

    def setup(self):
        cfg = self.cfg
        pdtype = _dtype(cfg.param_dtype)
        # A looped stack keeps every pass's readout for the backward: the
        # norm and the gate then save their compute-dtype inputs only and
        # make their float32 intermediates again (else four (T, B, S, d)
        # float32 arrays a step: 1 GiB in the Ouro cell).
        norm, dense = ((nn.remat(RMSNorm), nn.remat(nn.Dense)) if cfg.stack_passes > 1
                       else (RMSNorm, nn.Dense))
        self.norm_f = norm(cfg.norm_eps, zero_centred=cfg.norm_gain == "zero_centred")
        if not cfg.tie_embeddings:
            self.lm_head = self.param("lm_head", nn.initializers.lecun_normal(),
                                      (cfg.d_model, cfg.padded_vocab_size), pdtype)
        if cfg.exit_gate:
            self.exit_gate = _exit_gate(dense, pdtype)

    def norm(self, h: jax.Array) -> jax.Array:
        return self.norm_f(h).astype(_dtype(self.cfg.compute_dtype))

    def _bias(self) -> jax.Array:
        return jnp.zeros((self.cfg.padded_vocab_size,), _dtype(self.cfg.param_dtype))

    def logits(self, h: jax.Array, kernel: jax.Array | None = None) -> jax.Array:
        """Logits of normed ``h`` (``kernel``: a tied head's)."""
        from dtc_tpu.ops.fused_ce import head_logits

        return head_logits(h, self.lm_head if kernel is None else kernel, self._bias(),
                           self.cfg.vocab_size)

    def token_losses(self, h: jax.Array, targets: jax.Array) -> jax.Array:
        """Every token's cross-entropy (float32) of normed ``h``."""
        from dtc_tpu.ops.fused_ce import fused_head_ce_tokens

        return fused_head_ce_tokens(h, self.lm_head, self._bias(), targets, self.cfg.vocab_size)

    def gate(self, h: jax.Array) -> jax.Array:
        """The exit gate's score of normed ``h``: (B, T) float32."""
        with jax.named_scope("exit"):
            return self.exit_gate(h)[..., 0]  # promoted to float32 inside, under its remat

    def __call__(self, h: jax.Array, targets: jax.Array | None = None,
                 tied: jax.Array | None = None) -> jax.Array:
        from dtc_tpu.ops.fused_ce import fused_head_ce

        h = self.norm(h)
        kernel = self.lm_head if tied is None else tied.T
        if targets is not None:
            return fused_head_ce(h, kernel, self._bias(), targets, self.cfg.vocab_size)
        return self.logits(h, kernel)


def exit_distribution(z: jax.Array) -> jax.Array:
    """``log p`` (T, ...) of the pass a token exits at, from the gates'
    scores ``z`` (T, ...) with the passes in front: ``lambda_t =
    sigmoid(z_t)`` of what earlier passes left, the last pass taking the
    remainder whatever ``z_T`` says (module docstring, "Passes")."""
    stay = jax.nn.log_sigmoid(-z[:-1])
    first = jnp.zeros_like(z[:1])
    left = jnp.concatenate([first, jnp.cumsum(stay, axis=0)])       # sum_{j<t} log(1 - lambda_j)
    return left + jnp.concatenate([jax.nn.log_sigmoid(z[:-1]), first])


def exit_loss(ce: jax.Array, z: jax.Array, beta: float = EXIT_BETA) -> tuple[jax.Array, jax.Array]:
    """(loss, one row of :data:`PASS_COUNTERS` a pass) from the passes'
    per-token cross-entropies and gate scores, both (T, ...) float32: the
    tokens' mean of the expected cross-entropy under the exit distribution
    less ``beta`` times that distribution's entropy."""
    logp = exit_distribution(z)
    p = jnp.exp(logp)
    entropy = -jnp.sum(p * logp, axis=0)
    loss = jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy)
    tokens = tuple(range(1, ce.ndim))
    rows = jnp.stack([p.mean(tokens), ce.mean(tokens),
                      jnp.broadcast_to(entropy.mean(), ce.shape[:1])], axis=-1)
    return loss, jax.lax.stop_gradient(rows)


class PatternLM(nn.Module):
    """Decoder-only model of a layer pattern. Param tree ``{"embed",
    "stage": {"leading": {"layer_<i>": ...}, "periods": {"layer_<i>":
    ...}}, "head"}``: a period's leaves stacked over periods, the leading
    layers' (where the configuration has any) plain; no ``head/lm_head``
    with ``tie_embeddings``. A looped stack (``stack_passes`` > 1) has the
    tree of one stack, its passes sharing every leaf, and ``head/exit_gate``.
    Training and evaluation only."""

    cfg: ModelConfig

    def setup(self):
        self.embed = PatternEmbed(self.cfg)
        self.stage = PatternStage(self.cfg)
        self.head = PatternHead(self.cfg)

    def __call__(self, x: jax.Array, *, train: bool = True, decode: bool = False,
                 targets: jax.Array | None = None) -> jax.Array:
        if decode:
            raise NotImplementedError(NOT_SERVED)
        h = self.embed(x)
        if self.cfg.stack_passes > 1:
            return self._looped(h, train, targets)
        h = self.stage(h, train=train)
        tied = self.embed.table() if self.cfg.tie_embeddings else None
        return self.head(h, targets=targets, tied=tied)

    def _looped(self, h: jax.Array, train: bool, targets: jax.Array | None) -> jax.Array:
        """The passes as one scanned body (module docstring, "Passes")."""
        cfg = self.cfg

        def one_pass(mdl, h, _):
            h = mdl.stage(h, train=train)
            # the head's parts run under their own method names: the scope
            # the traces are read by is set here
            with jax.named_scope("head"):
                h = mdl.head.norm(h)
                if targets is not None:
                    return h, (mdl.head.token_losses(h, targets), mdl.head.gate(h))
                if mdl.is_initializing():
                    mdl.head.gate(h)  # model.init passes no targets: the gate's leaves are made here
                return h, None

        h, read = nn.scan(
            one_pass, variable_broadcast="params", variable_axes={"counters": 0},
            split_rngs={"params": False}, length=cfg.stack_passes,
        )(self, h, None)
        if targets is None:
            return self.head.logits(h)
        with jax.named_scope("exit"):
            loss, rows = exit_loss(*read)
        self.sow("counters", "passes", rows)
        return loss


def build_model(cfg: ModelConfig) -> nn.Module:
    """The model a configuration describes: :class:`PatternLM` where it
    states a layer pattern, else ``GPT``."""
    if cfg.layer_pattern:
        return PatternLM(cfg)
    from dtc_tpu.models.gpt import GPT

    return GPT(cfg)


def pattern_param_count(cfg: ModelConfig) -> int:
    """Exact parameter count of :class:`PatternLM` from the configuration
    (``gpt.param_count`` hands pattern models here)."""
    d = cfg.d_model
    nk, nv = cfg.gdn_key_heads * cfg.gdn_key_dim, cfg.gdn_value_heads * cfg.gdn_value_dim
    hd = cfg.head_dim
    attn = (d * cfg.n_heads * hd + 2 * d * cfg.kv_heads * hd + 2 * hd * cfg.qk_norm
            + cfg.n_heads * hd * d)
    routed = (d * cfg.moe_experts + cfg.experts_held * 3 * d * cfg.moe_d_ff
              + cfg.moe_selection_bias * cfg.moe_experts)
    per = {
        "gdn": d * (2 * nk + 2 * nv) + d * 2 * cfg.gdn_value_heads
        + (2 * nk + nv) * cfg.gdn_conv_width + 2 * cfg.gdn_value_heads + cfg.gdn_value_dim + nv * d,
        "gated_attn": attn + d * cfg.n_heads * hd,
        "attn": attn,
        "shortconv": d * 3 * d + d * cfg.shortconv_width + d * d,
        "moe_shared": routed + 3 * d * cfg.moe_shared_d_ff + d,
        "moe": routed,
        "swiglu": 3 * d * cfg.d_ff,
    }
    norms = (4 if cfg.norm_placement == "sandwich" else 2) * d
    layers = sum(n * (per[m] + per[f] + norms) for m, f, n in cfg.layer_census())
    head = (1 if cfg.tie_embeddings else 2) * cfg.padded_vocab_size * d + d
    return layers + head + cfg.exit_gate * (d + 1)


def _attention_plan(cfg: ModelConfig, gated: bool) -> dict:
    from dtc_tpu.ops.attention import resolve_impl

    tile = packed_rotary_plan(cfg, cfg.max_seq_len, gated)
    plan = {
        "kernel": resolve_impl(cfg.attention, cfg.max_seq_len, cfg.head_dim,
                               cfg.attention_block_q, cfg.attention_block_kv),
        "heads": cfg.n_heads, "kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "block_q": min(cfg.attention_block_q, cfg.max_seq_len),
        "block_kv": min(cfg.attention_block_kv, cfg.max_seq_len),
        "rotary_dims": int(cfg.head_dim * cfg.rope_fraction), "qk_norm": cfg.qk_norm,
        # where q and k are rotated: in the projections' own (B, T, H d) layout by
        # the Mosaic kernel, or as (B, T, H, d) by XLA
        "rotary": "packed" if tile else "xla",
    }
    if tile:
        plan["rotary_tile"] = {"rows": tile["rows"], "vmem_limit_bytes": tile["vmem_limit_bytes"]}
    return plan


def layer_plan(cfg: ModelConfig) -> dict:
    """Fields of the trainer's one ``layer_plan`` start-up event: the
    pattern, and per mixer kind the kernel and tiles it will run."""
    mixers = {m for m, _, _ in cfg.layer_census()}
    plan: dict = {
        "pattern": list(cfg.layer_pattern),
        "periods": cfg.pattern_periods,
        "remat": cfg.remat_mode,
        # the passes over the stack, each with its own head pass, and where
        # a layer's norms sit
        "passes": cfg.stack_passes,
        "norm_placement": cfg.norm_placement,
    }
    if cfg.exit_gate:
        plan["exit"] = {"beta": EXIT_BETA, "pass_logits": "recomputed"}
    if cfg.leading_pattern:
        plan["leading"] = list(cfg.leading_pattern)
    for kind in ("gated_attn", "attn"):
        if kind in mixers:
            plan[kind] = _attention_plan(cfg, gated=kind == "gated_attn")
    if "shortconv" in mixers:
        # the taps as shifted multiply-adds that XLA fuses with the two gates
        plan["shortconv"] = {"width": cfg.shortconv_width, "channels": cfg.d_model,
                             "implementation": "xla"}
    if "gdn" in mixers:
        from dtc_tpu.config.schema import DTYPE_BYTES

        local = supports_chunk_kernel(
            cfg.gdn_chunk, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.gdn_value_heads,
            cfg.gdn_key_heads, DTYPE_BYTES[cfg.compute_dtype])
        plan["gdn"] = {
            # one Mosaic kernel a pass with the state in VMEM across a row's
            # chunks, or the jax.numpy form with XLA's scan carrying it
            "kernel": "mosaic" if local else "xla", "carry": "vmem" if local else "scan",
            "chunk": cfg.gdn_chunk,
            "chunks": cfg.max_seq_len // cfg.gdn_chunk,
            "key_heads": cfg.gdn_key_heads, "value_heads": cfg.gdn_value_heads,
            "key_dim": cfg.gdn_key_dim, "value_dim": cfg.gdn_value_dim,
        }
        if local:
            # the kernels' grid step: value heads x chunk positions
            plan["gdn"]["tile"] = [local["tiles"], local["chunk"]]
            plan["gdn"]["vmem_limit_bytes"] = {
                leg: local[leg]["vmem_limit_bytes"] for leg in ("fwd", "bwd")}
    return plan


def moe_plan(cfg: ModelConfig, tokens_per_device: int) -> dict | None:
    """Fields of the ``moe_plan`` start-up event, or None without an
    expert layer."""
    ffns = {f for _, f, _ in cfg.layer_census()}
    if not ffns & {"moe_shared", "moe"}:
        return None
    held, k = cfg.experts_held, cfg.moe_top_k
    return {
        "experts_published": cfg.moe_experts, "experts_held": held,
        "rank": cfg.moe_expert_rank, "first_expert": cfg.moe_expert_rank * held,
        "top_k": k, "expert_width": cfg.moe_d_ff,
        "shared_width": cfg.moe_shared_d_ff if "moe_shared" in ffns else 0,
        "score": cfg.moe_score, "selection_bias": cfg.moe_selection_bias,
        "tokens_per_device": tokens_per_device, "tile_rows": md.HELD_TILE_ROWS,
        "staged_rows": md.held_staging_rows(
            tokens_per_device * k, held, cfg.moe_experts, md.HELD_TILE_ROWS),
        "expected_held": tokens_per_device * k * held / cfg.moe_experts,
    }
