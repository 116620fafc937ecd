"""Plain float32 reference of the GPT-2 block family and its AdamW step.

Imports nothing of the program and takes nothing the program made: the
weights come from :func:`make_weights` (the benchmark's own generator, the
same call the runner uses to seed the program), the batches from
``traffic.py``. Everything is ``jax.numpy`` in float32 at
``jax.default_matmul_precision("highest")``; no kernels, no cache.

It follows Radford et al. 2019 (pre-LN blocks, learned positions, GELU in
the tanh form, MHA) with the departures of the program's block that the
configuration files list under ``assumed``: an untied output head with a
bias, LayerNorm eps 1e-6, a vocabulary padded to a multiple of 128 whose
padded columns never enter the loss.

Memory: layers run under ``lax.scan`` with ``jax.checkpoint`` (only each
layer's input is kept), the head and its cross-entropy run over chunks of
the sequence. With more than one device the rows and every large leaf are
laid out over them by sharding annotations only; the arithmetic is the
same.

``matmul="int8"`` is the control: every dense matmul (q, k, v, out, fc1,
fc2, head; forward and both backward products) takes operands rounded to
int8 with one absmax scale per row of the contraction, the usual
vector-wise int8 matmul. It is the nearest precision below the bfloat16
that the configurations state.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

LN_EPS = 1e-6
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8
HEAD_CHUNK = 128  # sequence positions per head+loss chunk

def padded_vocab(model: dict) -> int:
    m = max(int(model.get("vocab_pad_multiple", 128)), 1)
    return -(-int(model["vocab_size"]) // m) * m


def leaf_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Reference leaf name -> shape. Block leaves are stacked over layers."""
    d, f, L = model["d_model"], model["d_ff"], model["n_layers"]
    v, t = padded_vocab(model), model["max_seq_len"]
    block = {
        "ln_1.g": (d,), "ln_1.b": (d,), "ln_2.g": (d,), "ln_2.b": (d,),
        "q.w": (d, d), "k.w": (d, d), "v.w": (d, d), "out.w": (d, d),
        "q.b": (d,), "k.b": (d,), "v.b": (d,), "out.b": (d,),
        "fc1.w": (d, f), "fc1.b": (f,), "fc2.w": (f, d), "fc2.b": (d,),
    }
    shapes = {"wte": (v, d), "wpe": (t, d), "ln_f.g": (d,), "ln_f.b": (d,),
              "head.w": (d, v), "head.b": (v,)}
    shapes.update({f"blocks.{k}": (L, *s) for k, s in block.items()})
    return shapes


def seed_words(seed: int) -> np.ndarray:
    """A seed of any size as two uint32 words (traced, so one program
    serves every seed)."""
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def make_weights(model: dict, words: jax.Array) -> dict[str, jax.Array]:
    """Every weight from the seed, on the device, float32: normal(0, 0.02)
    for matrices, embeddings and biases, 1 + normal(0, 0.02) for LayerNorm
    gains. (GPT-2 starts biases at 0 and gains at 1; a trained model has
    neither, and a zero bias would hide a dropped bias add.)"""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), words[0]), words[1])
    out = {}
    for name, shape in leaf_shapes(model).items():
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        out[name] = 1.0 + w if name.endswith(".g") else w
    return out


# ---------------------------------------------------------------------------
# matmul flavours


def _quant(x: jax.Array, axis: int) -> jax.Array:
    """Round to int8 with one absmax scale per vector along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


@jax.custom_vjp
def _int8_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return _quant(a, -1) @ _quant(b, 0)


def _int8_fwd(a, b):
    return _int8_matmul(a, b), (a, b)


def _int8_bwd(res, g):
    a, b = res
    a2, g2 = a.reshape(-1, a.shape[-1]), g.reshape(-1, g.shape[-1])
    da = _quant(g, -1) @ _quant(b.T, 0)
    db = _quant(a2.T, -1) @ _quant(g2, 0)
    return da, db


_int8_matmul.defvjp(_int8_fwd, _int8_bwd)


def _mm(matmul: str):
    if matmul == "float32":
        return jnp.matmul
    if matmul == "int8":
        return _int8_matmul
    raise ValueError(f"unknown matmul flavour {matmul!r}")


# ---------------------------------------------------------------------------
# the model


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _block(h, p, n_heads: int, mm):
    b, t, d = h.shape
    hd = d // n_heads
    x = _layer_norm(h, p["ln_1.g"], p["ln_1.b"])
    q = (mm(x, p["q.w"]) + p["q.b"]).reshape(b, t, n_heads, hd)
    k = (mm(x, p["k.w"]) + p["k.b"]).reshape(b, t, n_heads, hd)
    v = (mm(x, p["v.w"]) + p["v.b"]).reshape(b, t, n_heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    h = h + mm(a.reshape(b, t, d), p["out.w"]) + p["out.b"]
    x = _layer_norm(h, p["ln_2.g"], p["ln_2.b"])
    x = _gelu(mm(x, p["fc1.w"]) + p["fc1.b"])
    return h + mm(x, p["fc2.w"]) + p["fc2.b"]


def _head_loss_sum(h, y, w, bias, vocab: int, mm):
    """Sum of next-token cross-entropies over (rows, chunk) positions."""
    logits = (mm(h, w) + bias)[..., :vocab]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def loss_fn(params: dict, x: jax.Array, y: jax.Array, model: dict,
            matmul: str = "float32") -> jax.Array:
    """Mean next-token cross-entropy of rows ``x`` against targets ``y``."""
    mm = _mm(matmul)
    b, t = x.shape
    h = params["wte"][x] + params["wpe"][:t][None]
    blocks = {k[len("blocks."):]: v for k, v in params.items() if k.startswith("blocks.")}
    body = jax.checkpoint(
        lambda carry, p: (_block(carry, p, model["n_heads"], mm), None)
    )
    h, _ = jax.lax.scan(body, h, blocks)
    h = _layer_norm(h, params["ln_f.g"], params["ln_f.b"])
    c = HEAD_CHUNK if t % HEAD_CHUNK == 0 else t
    hs = jnp.moveaxis(h.reshape(b, t // c, c, -1), 1, 0)
    ys = jnp.moveaxis(y.reshape(b, t // c, c), 1, 0)
    chunk = jax.checkpoint(
        lambda hy: _head_loss_sum(hy[0], hy[1], params["head.w"], params["head.b"],
                                  model["vocab_size"], mm)
    )
    return jnp.sum(jax.lax.map(chunk, (hs, ys))) / (b * t)


# ---------------------------------------------------------------------------
# the step: clip by global norm, then AdamW


def leaf_norms(tree: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """L2 norm of every leaf; of every layer's slice for a stacked leaf."""
    out = {}
    for name, a in tree.items():
        a = a.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if name.startswith("blocks.") else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(a), axis=axes))
    return out


def train_step(params, mu, nu, count, x, y, *, model: dict, optim: dict,
               matmul: str = "float32"):
    """One step. Returns the new state, the loss, and the per-leaf norms of
    the gradient as AdamW gets it (after the clip)."""
    loss, g = jax.value_and_grad(loss_fn)(params, x, y, model, matmul)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in g.values()))
    clip = optim["grad_clip"]
    if clip > 0:
        scale = jnp.where(gnorm < clip, 1.0, clip / gnorm)
        g = {k: v * scale for k, v in g.items()}
    count = count + 1
    lr, wd = optim["lr"], optim["weight_decay"]
    c1 = 1.0 - B1 ** count.astype(jnp.float32)
    c2 = 1.0 - B2 ** count.astype(jnp.float32)
    new_p, new_mu, new_nu = {}, {}, {}
    for k, p in params.items():
        m = B1 * mu[k] + (1.0 - B1) * g[k]
        v = B2 * nu[k] + (1.0 - B2) * jnp.square(g[k])
        upd = (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS) + wd * p
        new_p[k], new_mu[k], new_nu[k] = p - lr * upd, m, v
    return new_p, new_mu, new_nu, count, loss, leaf_norms(g)


def _layout(devices: list, model: dict):
    """Shardings for more than one device: rows over the devices, every
    leaf along its last axis where that divides. None on one device."""
    n = len(devices)
    if n == 1:
        return None, None
    mesh = Mesh(np.array(devices), ("r",))

    def leaf(shape):
        spec = [None] * len(shape)
        if shape[-1] % n == 0 and int(np.prod(shape)) >= 1 << 16:
            spec[-1] = "r"
        return NamedSharding(mesh, P(*spec))

    return (
        {k: leaf(s) for k, s in leaf_shapes(model).items()},
        NamedSharding(mesh, P("r", None)),
    )


def run_steps(model: dict, optim: dict, seed: int, batches: list[np.ndarray],
              *, matmul: str = "float32", devices: list | None = None,
              rows: slice | None = None, frozen: bool = False) -> dict[str, Any]:
    """Follow ``len(batches)`` steps from the seed's weights.

    ``batches`` are the (rows, T+1) token arrays the program was fed.
    ``rows`` plants the fault "part of the batch left out": the loss and
    gradient are taken over that slice of every batch only. ``frozen``
    plants "a step that returns its state unchanged": the parameters stay
    the seed's (the losses and the first gradient are then those of the
    unchanged parameters).

    Returns ``losses``, ``grad1`` (per-leaf norms of step 1's clipped
    gradient), ``dparam`` (per-leaf norms of the parameters' change over
    all the steps), each as host floats / numpy arrays.
    """
    devices = devices or jax.devices()[:1]
    p_sh, b_sh = _layout(devices, model)
    words = jax.device_put(seed_words(seed), devices[0] if p_sh is None else
                           NamedSharding(p_sh["wte"].mesh, P()))
    with jax.default_matmul_precision("highest"):
        make = jax.jit(functools.partial(make_weights, model), out_shardings=p_sh)
        step = jax.jit(
            functools.partial(train_step, model=model, optim=optim, matmul=matmul),
            donate_argnums=(0, 1, 2),
        )
        delta = jax.jit(lambda p, w: leaf_norms(
            {k: p[k] - v for k, v in make_weights(model, w).items()}))
        params = make(words)
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.int32)
        losses, grad1 = [], None
        for i, batch in enumerate(batches):
            if rows is not None:
                batch = batch[rows]
            x = np.ascontiguousarray(batch[:, :-1])
            y = np.ascontiguousarray(batch[:, 1:])
            if b_sh is not None:
                x, y = jax.device_put(x, b_sh), jax.device_put(y, b_sh)
            if frozen:
                keep = jax.tree.map(jnp.copy, params)
            params, mu, nu, count, loss, gn = step(params, mu, nu, count, x, y)
            if frozen:
                params = keep
            losses.append(float(loss))
            if i == 0:
                grad1 = jax.device_get(gn)
        dparam = jax.device_get(delta(params, words))
    del params, mu, nu
    return {"losses": losses, "grad1": grad1, "dparam": dparam}
