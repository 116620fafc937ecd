"""AST lint: hard-coded dtype literals in model/op hot paths (ISSUE 14).

The mixed-precision policy flows from config (``param_dtype`` /
``compute_dtype`` / ``OptimConfig.precision``) through
``models/gpt._dtype`` and flax's ``promote_dtype``; a hard-coded
``jnp.float32`` or ``.astype(jnp.bfloat16)`` in a hot path BYPASSES the
policy — the layer silently runs one dtype while the config (and the
auditor reading the config) claims another. The ``hostsync.py`` pattern
applies: the lint is not "no dtype literals" but "no dtype literals
outside a sanctioned scope", because the mandated-fp32 islands are
SUPPOSED to hard-code fp32 — softmax and LayerNorm variance, the CE
loss, MoE routing numerics, quantization scale math, Pallas kernel
accumulators.

The allowlist below names (file, enclosing-scope) pairs, matched on any
enclosing function or class name — the same contract as hostsync's
SANCTIONED_CONDITIONS table: renaming a scope without updating the table
fails loudly in tests/test_numerics.py, and a NEW literal in an
unsanctioned scope trips the lint on the pristine-tree assertion. Pure
``ast`` on source text — no JAX import, lints any file.
"""

from __future__ import annotations

import ast
import dataclasses
import os

#: dtype attribute names whose literal use the lint tracks.
DTYPE_NAMES = frozenset({
    "float32", "float64", "float16", "bfloat16", "int8",
})

#: Sanctioned scopes per hot-path file (relative to ``dtc_tpu/``). A
#: site is sanctioned when ANY enclosing function/class name appears in
#: its file's set; ``"*"`` sanctions the whole file (the pure Pallas
#: kernel files, whose fp32 online-softmax stats and accumulators are
#: the kernels' DESIGN — their numerics are pinned by the kernel parity
#: tests, not by dtype-policy plumbing); ``"<module>"`` sanctions
#: module-level dtype tables. Every entry is a mandated-precision
#: region: fp32-mandatory numerics (softmax/LN variance/loss/routing),
#: kernel accumulators, dtype plumbing helpers whose JOB is naming
#: dtypes, or int8 quantization scale math.
ALLOWLIST: dict[str, frozenset[str]] = {
    "models/gpt.py": frozenset({
        "_dtype",            # THE policy resolver (name -> jnp dtype)
        "ln",                # pre-LN blocks: fp32-mandated LayerNorm
        "MoEMLP",            # router softmax numerics: fp32-mandated
        "GPTHead",           # ln_f: fp32-mandated LayerNorm
        "GPT",               # decode cache index bookkeeping (int32)
        "CausalSelfAttention",  # int8 KV scale cache (fp32 scales)
        "OverlapDense",      # param_dtype field default, = nn.Dense's
    }),
    "models/pattern.py": frozenset({
        "RMSNorm",           # zero-centred / plain RMS norm: fp32-mandated
        "rotary",            # cos / sin tables built in fp32
        "Attention",         # the output gate's sigmoid in fp32
        "GatedDeltaNet",     # decay, beta, L2 norms: fp32-mandated
        "ShortConv",         # the taps and both gates in fp32 inside one fusion
        "ExpertLayer",       # router, its scores, the selection bias, the shared gate: fp32
        "_exit_gate",        # the exit gate of a looped stack: fp32 (log p, the entropy and
                             # the loss over them take their dtype from it, no literal)
    }),
    "ops/gated_delta.py": frozenset({
        # The delta rule's decays, cumulative sums, carried state and every
        # matmul's accumulator are fp32 by design; operands take ``dtype``.
        "*",
    }),
    "ops/attention.py": frozenset({
        "decode_attention",  # fp32 scores/softmax — the mandated island
    }),
    "ops/fused_ce.py": frozenset({
        # fp32 logsumexp/loss statistics, fwd + bwd.
        "_stats_tokens", "_stats_loss", "head_logits", "fused_head_ce", "_fhc_fwd",
        "_fhc_bwd", "_head_grads", "fused_head_ce_tokens", "_fhct_fwd", "_fhct_bwd",
    }),
    # Pure Pallas kernel files: fp32 stats/accumulators throughout, by
    # design (flash online softmax, zigzag-ring merge stats).
    "ops/flash_attention.py": frozenset({"*"}),
    "ops/ring_attention.py": frozenset({"*"}),
    # The packed rotary kernel: the rotation in fp32 inside the pass, the
    # tables built in fp64 and rounded once; q and k keep their dtype in HBM.
    "ops/rotary.py": frozenset({"*"}),
    "ops/ulysses_attention.py": frozenset({
        "ulysses_causal_attention",
    }),
    "ops/decode_attention.py": frozenset({
        # fp32 one-pass softmax + int8 quantization scale arithmetic.
        "fused_decode_attention", "_head_kv", "_decode_kernel_single",
        "_decode_kernel_blocked", "quantize_kv", "dequantize_kv",
    }),
    "ops/decode_fused.py": frozenset({
        # The megakernel's in-register fp32 LN/softmax + int8 dequant;
        # the module-level table is the kernel's dtype-name map.
        "<module>", "_fused_layers_kernel", "_fused_layers_call",
        "supports_fused_layers",
    }),
    "ops/moe_dispatch.py": frozenset({
        # Routing probs/aux loss fp32; slot-map scatter arithmetic.
        "top_k_routing", "load_balance_loss", "dispatch_combine_tensors",
        "sort_dispatch", "sort_combine", "einsum_dispatch",
        "slot_to_token",
        # Held experts: fp32 gates, counters, matmul accumulators, the
        # combine's staging and scatter-add target, the weights' gradient sums.
        "held_experts", "_held_tiles_fwd", "_held_tiles_bwd", "_mm", "_staging",
        "bias_swapped",  # a float32 counter, as held_experts' are
    }),
    "ops/overlap_collectives.py": frozenset({
        # fp32 MXU accumulation (preferred_element_type) in both ring
        # kernels and the decomposed twin.
        "_contract", "_grad_partial", "_pallas_ag_matmul",
        "_pallas_rs_matmul", "_decomposed_ag_matmul",
        "_decomposed_rs_matmul",
    }),
}

#: Default lint roots: the model + ops hot paths.
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ROOTS = ("models", "ops")


@dataclasses.dataclass
class DtypeSite:
    """One hard-coded dtype literal."""

    path: str            # file path as given
    rel: str             # allowlist key (path relative to dtc_tpu/)
    lineno: int
    dtype: str           # the DTYPE_NAMES member
    code: str            # unparsed expression context
    scope: tuple[str, ...]  # enclosing class/function names, outermost first
    sanctioned: bool


def _literal_dtypes(node: ast.AST) -> list[tuple[ast.AST, str]]:
    """(node, dtype) for dtype-literal uses inside ``node`` WITHOUT
    recursing (the caller walks). Two forms:

    - an Attribute ``jnp.float32`` / ``np.bfloat16``;
    - a Constant STRING naming a dtype in a dtype position — the
      ``.astype("float32")`` argument or any ``dtype="bfloat16"``
      keyword. (Position-restricted on purpose: bare string comparisons
      like ``cfg.param_dtype == "float32"`` are config PLUMBING, not a
      policy bypass.)
    """
    out: list[tuple[ast.AST, str]] = []
    if isinstance(node, ast.Attribute) and node.attr in DTYPE_NAMES:
        base = node.value
        if isinstance(base, ast.Name) and base.id in ("jnp", "np", "jax"):
            out.append((node, node.attr))
    if isinstance(node, ast.Call):
        f = node.func
        if (
            isinstance(f, ast.Attribute) and f.attr == "astype"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and node.args[0].value in DTYPE_NAMES
        ):
            out.append((node.args[0], node.args[0].value))
        for kw in node.keywords:
            if (
                kw.arg == "dtype"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value in DTYPE_NAMES
            ):
                out.append((kw.value, kw.value.value))
    return out


def lint_source(
    source: str, path: str = "<string>", rel: str = ""
) -> list[DtypeSite]:
    """All dtype-literal sites in ``source`` with their enclosing scope
    chain and sanction status (``rel`` selects the allowlist row)."""
    tree = ast.parse(source, filename=path)
    allowed = ALLOWLIST.get(rel, frozenset())
    sites: list[DtypeSite] = []

    def visit(node: ast.AST, scope: tuple[str, ...]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            scope = scope + (node.name,)
        for lit, dtype in _literal_dtypes(node):
            ok = (
                "*" in allowed
                or (not scope and "<module>" in allowed)
                or any(s in allowed for s in scope)
            )
            sites.append(DtypeSite(
                path=path,
                rel=rel,
                lineno=getattr(lit, "lineno", getattr(node, "lineno", 0)),
                dtype=dtype,
                code=ast.unparse(lit),
                scope=scope,
                sanctioned=ok,
            ))
        for child in ast.iter_child_nodes(node):
            visit(child, scope)

    visit(tree, ())
    return sites


def lint_tree(pkg_dir: str = _PKG_DIR) -> list[DtypeSite]:
    """Lint every hot-path file under ``pkg_dir`` (``dtc_tpu/``)."""
    sites: list[DtypeSite] = []
    for root in DEFAULT_ROOTS:
        base = os.path.join(pkg_dir, root)
        if not os.path.isdir(base):
            continue
        for name in sorted(os.listdir(base)):
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            rel = f"{root}/{name}"
            with open(path) as f:
                sites.extend(lint_source(f.read(), path, rel))
    return sites


def unsanctioned(sites: list[DtypeSite]) -> list[DtypeSite]:
    return [s for s in sites if not s.sanctioned]
