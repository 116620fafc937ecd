"""Autoregressive generation with a per-layer KV cache.

A capability beyond the reference (which trains and plots, but cannot
sample — SURVEY.md §1 lists no serve/inference path). Decode reuses the
training model unchanged: ``decode=True`` threads a "cache" collection
through the modules — each attention layer keeps packed
``(B, max_seq_len, H·D)`` key/value buffers (the model-native lane
layout the fused decode kernel reads directly, ops/decode_attention.py),
and ONE model-level write-frontier/position counter lives at the GPT
root — so one prefill call consumes the whole prompt and each subsequent
call appends one token at O(T) cost instead of re-running the full O(T²)
forward per token. ``cfg.decode_attention`` selects the per-layer
attention backend: ``fused`` (single Pallas launch per layer — the
serving fast path) or ``xla`` (the einsum/softmax parity oracle).

The token loop is a ``lax.scan`` under one ``jax.jit``: no per-token
Python dispatch, TPU-friendly static shapes throughout. Greedy decoding
(``temperature == 0``) takes a fast path that skips the sampling
machinery entirely — no per-token RNG splits ride the scan carry and the
argmax never sees the top-k/top-p filters.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any


def init_cache(model, batch_size: int) -> PyTree:
    """Fresh decode cache for ``batch_size`` sequences.

    Shapes come from ``jax.eval_shape`` over the decode init — no params
    are materialized and no forward runs (``model.init`` would both
    allocate a full random parameter set AND advance the cache by one
    position). Every leaf starts at zero: index/pos 0, empty K/V."""
    dummy = jnp.ones((batch_size, 1), dtype=jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)}, dummy, train=False, decode=True
        )
    )
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])


def decode_step(model, params: PyTree, cache: PyTree, tok: jax.Array,
                lora: PyTree | None = None, spec_verify: bool = False):
    """ONE decode iteration: apply the model to ``tok`` (B, T_new) with the
    KV cache threaded through, returning ``(new_cache, logits)`` with
    logits ``(B, T_new, V)``.

    This is THE single-step function both decode drivers share: the greedy
    scan below calls it with ``T_new == 1`` inside ``lax.scan``, and the
    serving runtime's continuous-batching scheduler
    (:mod:`dtc_tpu.serve.engine`) drives it directly — once per iteration
    over its fixed slot batch (per-slot frontiers via a ``(B,)`` cache
    index), and once per admission as the prefill over a padded prompt.
    One definition means the serving path cannot drift numerically from
    the generate path the parity tests pin.

    ``lora`` is the model's "lora" collection for an adapter-enabled model
    (``cfg.adapter.rank > 0``): one shared adapter as-initialized
    (per-site ``(L, in, r)`` factors), or the serving engine's per-slot
    gathered stack (``(L, B, in, r)`` — each batch row decodes under its
    own tenant's adapter). Required iff the model has adapters.

    With ``cfg.decode_attention == "fused_layers"`` the single-token call
    routes through the layer-fused megakernel
    (:func:`dtc_tpu.ops.decode_fused.fused_decode_step` — ONE Pallas
    launch scans every layer; O(1) launches per token instead of
    O(layers)·O(ops)); prefill and unsupported shapes fall back to the
    per-layer model apply below. Because BOTH drivers route here, the
    megakernel serves generate's scalar frontier and the engine's (B,)
    slot frontiers from the same code path.

    ``spec_verify=True`` marks a speculative k-token VERIFY call (ISSUE
    19): ``tok`` is (B, k) draft proposals at the frontier, and the
    megakernel — not the prefill fallback — takes all k query positions
    in ONE launch (causal among the k in-register, cache writes at
    ``frontier..frontier+k-1``). The flag only widens the fused_layers
    gate; the per-layer model apply below already handles multi-token
    frontier appends (the same path prefill uses), so the xla/fused
    fallback ladder IS the verify parity oracle."""
    from dtc_tpu.ops import decode_fused

    if decode_fused.use_fused_layers(model.cfg, tok.shape[1], verify=spec_verify):
        return decode_fused.fused_decode_step(model, params, cache, tok, lora)
    variables = {"params": params, "cache": cache}
    if lora is not None:
        variables["lora"] = lora
    logits, mutated = model.apply(
        variables, tok, train=False, decode=True, mutable=["cache"],
    )
    return mutated["cache"], logits


def _top_k_mask(logits: jax.Array, k: int) -> jax.Array:
    """-inf everywhere below the k-th largest logit per row."""
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _top_p_mask(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filter: keep the smallest prefix of descending-probability
    tokens whose cumulative mass reaches ``p`` (the boundary token that
    crosses p stays in — the standard convention)."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Token j survives iff the mass BEFORE it is < p.
    keep = (cum - probs) < p
    # Smallest kept logit per row = the cutoff value.
    cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


def _generate_impl(
    model,
    params: PyTree,
    prompt: jax.Array,
    max_new_tokens: int,
    rng: jax.Array | None = None,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    lora: PyTree | None = None,
) -> jax.Array:
    """Sample ``max_new_tokens`` continuations of ``prompt`` (B, T_prompt).

    ``temperature == 0`` is greedy argmax; otherwise softmax sampling at the
    given temperature (requires ``rng``), optionally filtered by ``top_k``
    (keep the k most likely tokens) and/or ``top_p`` (nucleus: smallest set
    whose probability mass reaches p) — filters compose, k first. Returns
    ``(B, max_new_tokens)`` int32 tokens. Total length must fit
    ``cfg.max_seq_len``.

    Runs under a TP mesh unchanged: call inside ``with mesh,
    nn.logical_axis_rules(rules)`` with TP-sharded params and the decode
    path shards the KV cache over heads (asserted token-exact against
    single-device decode in tests/test_generate.py).
    """
    b, t_prompt = prompt.shape
    cfg = model.cfg
    if t_prompt + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({t_prompt}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({cfg.max_seq_len}) — the KV cache cannot grow past it"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if top_k is not None and not 1 <= top_k <= cfg.padded_vocab_size:
        raise ValueError(
            f"top_k must be in [1, {cfg.padded_vocab_size}], got {top_k}"
        )
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if rng is None:
        rng = jax.random.PRNGKey(0)  # unused by greedy

    # ``greedy`` is a STATIC fact (temperature is a static argname), so
    # the two loop bodies below compile to different programs: the greedy
    # scan carries no RNG key and runs argmax only — none of the top-k /
    # top-p / categorical machinery appears in its HLO.
    greedy = temperature == 0.0

    def sample(logits_last: jax.Array, key: jax.Array) -> jax.Array:
        # Padded vocab columns carry -1e9 from the head mask, so neither
        # argmax nor categorical can pick them.
        if greedy:
            return jnp.argmax(logits_last, axis=-1).astype(jnp.int32)
        logits_last = logits_last.astype(jnp.float32) / temperature
        if top_k is not None:
            logits_last = _top_k_mask(logits_last, top_k)
        if top_p is not None:
            logits_last = _top_p_mask(logits_last, top_p)
        return jax.random.categorical(key, logits_last, axis=-1).astype(jnp.int32)

    cache = init_cache(model, b)

    # Prefill: one forward over the whole prompt fills every layer's cache.
    # named_scope (ISSUE 8): the device-time attribution separates the
    # prompt pass from the token scan by these scopes — the decode leg of
    # the same provenance the train step's fwd/optimizer scopes provide.
    # ``lora`` (one shared adapter for the whole batch) is loop-invariant:
    # closed over by the scan body, read every step, never carried.
    with jax.named_scope("prefill"):
        cache, logits = decode_step(model, params, cache, prompt, lora)
    rng, sub = jax.random.split(rng)
    first = sample(logits[:, -1], sub)

    if greedy:
        def body(carry, _):
            cache, tok = carry
            cache, logits = decode_step(model, params, cache, tok[:, None], lora)
            nxt = sample(logits[:, -1], None)
            return (cache, nxt), nxt
        init = (cache, first)
    else:
        def body(carry, _):
            cache, tok, key = carry
            cache, logits = decode_step(model, params, cache, tok[:, None], lora)
            key, sub = jax.random.split(key)
            nxt = sample(logits[:, -1], sub)
            return (cache, nxt, key), nxt
        init = (cache, first, rng)

    if max_new_tokens == 1:
        return first[:, None]
    with jax.named_scope("decode"):
        _, rest = jax.lax.scan(body, init, None, length=max_new_tokens - 1)
    return jnp.concatenate([first[:, None], rest.T], axis=1)


_generate_jit = functools.partial(
    jax.jit,
    static_argnums=(0, 3),
    static_argnames=("temperature", "top_k", "top_p"),
)(_generate_impl)


def generate(
    model,
    params: PyTree,
    prompt: jax.Array,
    max_new_tokens: int,
    rng: jax.Array | None = None,
    *,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    lora: PyTree | None = None,
    tracer=None,
) -> jax.Array:
    """See :func:`_generate_impl` for semantics; this wrapper picks the
    compiled path. With ``cfg.debug_checks`` the model emits
    ``checkify.check`` guards (decode-cache overflow), which must be
    functionalized before jit — this path discharges them and throws,
    trading per-call recompiles for dev-mode assertions. The static
    length validation above makes the check unreachable from THIS API;
    it protects direct ``model.apply(..., decode=True)`` callers.

    ``tracer`` (an :class:`dtc_tpu.obs.trace.Tracer`) wraps the whole
    compiled call in one ``generate`` span — the prefill+scan is a
    single jit, so finer host-side splits would be fiction; per-token
    attribution lives in the serving engine's iteration spans."""
    if getattr(model.cfg, "layer_pattern", ()):
        from dtc_tpu.models.pattern import NOT_SERVED

        raise NotImplementedError(NOT_SERVED)
    if tracer is not None and tracer.enabled:
        with tracer.span(
            "generate", cat="generate", batch=int(prompt.shape[0]),
            prompt_len=int(prompt.shape[1]), new_tokens=int(max_new_tokens),
        ):
            out = generate(
                model, params, prompt, max_new_tokens, rng,
                temperature=temperature, top_k=top_k, top_p=top_p, lora=lora,
            )
            # Sync INSIDE the span so it measures device work, not the
            # async dispatch returning (the bracketed call is host-side).
            jax.block_until_ready(out)
            return out
    if getattr(model.cfg, "debug_checks", False):
        from jax.experimental import checkify

        def f(params, prompt, rng, lora):
            return _generate_impl(
                model, params, prompt, max_new_tokens, rng,
                temperature=temperature, top_k=top_k, top_p=top_p, lora=lora,
            )

        err, out = jax.jit(checkify.checkify(f))(params, prompt, rng, lora)
        err.throw()
        return out
    return _generate_jit(
        model, params, prompt, max_new_tokens, rng,
        temperature=temperature, top_k=top_k, top_p=top_p, lora=lora,
    )
