"""Block-size sweep for the packed flash kernels at long context (on-chip).

Times forward and forward+backward of flash_causal_attention at the
long-context bench shapes (B=4, H=16, D=32 — the flagship head layout)
across (block_q, block_kv) tilings, best-of-3 windows. Also reports the fused-vs-split backward delta at T=4096 by
forcing the split path. Feeds the PERF.md long-context ceiling analysis.

Usage: python scripts/sweep_flash.py [--seq 4096] [--iters 20]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

COMBOS = [
    (256, 512), (512, 512), (1024, 512), (2048, 512),
    (256, 1024), (512, 1024), (1024, 1024),
    (512, 2048), (256, 2048),
]


def best_of_3(fn, iters):
    import numpy as np

    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        np.asarray(jax_leaf(out))  # sync by value fetch
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3  # ms


def jax_leaf(tree):
    import jax

    return jax.tree.leaves(tree)[0].ravel()[0]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=32)
    ap.add_argument("--force-split", action="store_true",
                    help="route the backward through the split dq/dkv kernels")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import dtc_tpu.ops.flash_attention as fa

    if args.force_split:
        fa._PACKED_MAX_T = 0

    b, t, h, d = args.batch, args.seq, args.heads, args.head_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (
        jax.random.normal(kk, (b, t, h, d), jnp.bfloat16) for kk in keys
    )

    # Counted FLOPs for context: fwd 4BT^2·H·D/2, bwd 8BT^2·H·D/2 (causal).
    fwd_tf = 2.0 * b * t * t * h * d / 1e12
    print(f"# shape b={b} t={t} h={h} d={d}; counted fwd {fwd_tf:.3f} TF, "
          f"fwd+bwd {3 * fwd_tf:.3f} TF; peak 197 TF/s, hd32 lane bound ~25%")
    for bq, bkv in COMBOS:
        if t % bq or t % bkv:
            continue
        try:
            fwd = jax.jit(lambda q, k, v: fa.flash_causal_attention(
                q, k, v, block_q=bq, block_kv=bkv))
            g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                fa.flash_causal_attention(
                    q, k, v, block_q=bq, block_kv=bkv
                ).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))
            fwd(q, k, v)  # compile
            g(q, k, v)
            t_fwd = best_of_3(lambda: fwd(q, k, v), args.iters)
            t_all = best_of_3(lambda: g(q, k, v), args.iters)
            eff_f = fwd_tf / (t_fwd / 1e3) / 197.0
            eff_a = 3 * fwd_tf / (t_all / 1e3) / 197.0
            print(f"bq={bq:5d} bkv={bkv:5d}  fwd {t_fwd:8.3f} ms ({eff_f:5.1%} peak)"
                  f"  fwd+bwd {t_all:8.3f} ms ({eff_a:5.1%} peak)", flush=True)
        except Exception as e:  # noqa: BLE001 — sweep survives bad tilings
            first = (str(e).splitlines() or [""])[0]
            print(f"bq={bq:5d} bkv={bkv:5d}  FAILED: {type(e).__name__}: "
                  f"{first[:90]}", flush=True)


if __name__ == "__main__":
    main()
