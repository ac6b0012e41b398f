"""Plain float32 reference of the Hyena language model, and the benchmark's
own weights.

Nothing here imports the program under test.  The model follows
arXiv:2302.10866 (Def. 3.1, Algorithms 1-3, App. D.3) with the filter
details the benchmarked configuration states: a positional encoding of
``[t, cos 2πkt, sin 2πkt]`` on ``t = linspace(0, 1, L)``, a sine FFN
(ω = 14), an exponential window ``exp(-8·e^a·s) + 0.1·sigmoid(b)`` on
``s = arange(L)/L``, taps normalised to unit l1 norm per channel, a
per-channel skip gain, and a depthwise short FIR of width 3 on the
projected inputs.  Blocks are pre-norm (RMSNorm with ``1 + g`` gains):
Hyena mixer, then a GELU (tanh) MLP; an untied LM head; the loss is the
token mean of cross-entropy plus ``z_loss_weight`` times the mean squared
log-partition.  AdamW with bias correction, linear warm-up then cosine
decay, global-norm clipping, and decoupled decay on every stored array of
two or more dimensions.

``Precision`` says where values are rounded.  ``FP32`` rounds nothing: the
reference proper, run under ``jax.default_matmul_precision("highest")``.
``FP8`` rounds parameters and every activation (and, in the backward pass,
every cotangent) at the points where a bf16 policy rounds, to float8 e4m3
with a per-tensor scale; ``HIGH`` rounds at the same points to a pair of
bfloat16 (16 significant bits).
``CONTROLS`` names the control of each stated precision: the reference
one step below it, which the comparison has to fail.

The parameter tree has the layout that the program's train state and
serve engine take (``groups`` holds one scan-stacked block group), so the
benchmark makes one set of weights from the seed and gives it to both.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one Hyena LM, read from a configuration file."""

    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    order: int
    filter_width: int
    filter_depth: int
    pos_dim: int
    sine_freq: float
    decay: tuple
    short_len: int = 3
    z_loss_weight: float = 1e-4

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dims":
        return cls(
            n_layers=c["n_layers"], d_model=c["d_model"], d_ff=c["d_ff"],
            vocab_size=c["vocab_size"], order=c["hyena_order"],
            filter_width=c["hyena_filter_width"],
            filter_depth=c["hyena_filter_depth"],
            pos_dim=c["hyena_pos_dim"], sine_freq=c["hyena_sine_freq"],
            decay=tuple(c["hyena_decay"]),
        )


# ------------------------------------------------------------- precision

def _e4m3(y):
    """Round to the nearest float8 e4m3 value (4 significant bits, spacing
    2^-9 below 2^-6, largest 448), in float32 arithmetic: the same values
    as a cast to ``float8_e4m3fn`` and back, on any backend."""
    m, e = jnp.frexp(y)
    q = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    q = jnp.where(jnp.abs(y) < 2.0 ** -6, jnp.round(y * 512.0) / 512.0, q)
    return jnp.clip(q, -448.0, 448.0)


def _fp8_round(x):
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return _e4m3(x32 / scale) * scale


def _high_round(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi + (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _rounding(fn):
    """``fn`` on the values and, in the backward pass, on the cotangents."""

    @jax.custom_vjp
    def r(x):
        return fn(x)

    r.defvjp(lambda x: (fn(x), None), lambda _, g: (fn(g),))
    return r


@dataclasses.dataclass(frozen=True)
class Precision:
    name: str
    round: Callable


FP32 = Precision("fp32", lambda x: x)
HIGH = Precision("high", _rounding(_high_round))
FP8 = Precision("fp8", _rounding(_fp8_round))

# control name -> (rounding, matmul precision): the reference one step
# below what a cell's configuration states
CONTROLS = {
    "fp8": (FP8, "highest"),  # below bf16 compute
    # below float32 at "highest": three bf16 passes in every matmul, and
    # operands kept to 16 significant bits (a bf16 pair) where the policy
    # rounds, which is what three passes keep also where XLA's CPU
    # backend ignores the matmul precision
    "high": (HIGH, "high"),
}


# --------------------------------------------------------------- weights

def _param_shapes(d: Dims) -> Params:
    D, N, nl, V = d.d_model, d.order, d.n_layers, d.vocab_size
    inner = (N + 1) * D
    dims = [d.pos_dim] + [d.filter_width] * (d.filter_depth - 1) + [N * D]
    ffn = [
        {"w": (nl, dims[i], dims[i + 1]), "b": (nl, dims[i + 1])}
        for i in range(len(dims) - 1)
    ]
    return {
        "embed": {"table": (V, D)},
        "final_norm": {"g": (D,)},
        "groups": [{
            "norm1": {"g": (nl, D)},
            "mixer": {
                "in_proj": {"w": (nl, D, inner), "b": (nl, inner)},
                "out_proj": {"w": (nl, D, D), "b": (nl, D)},
                "short_filter": (nl, inner, d.short_len),
                "filters": {
                    "ffn": ffn,
                    "decay_log_rate": (nl, N * D),
                    "window_bias": (nl, N * D),
                    "skip": (nl, N * D),
                },
            },
            "norm2": {"g": (nl, D)},
            "mlp": {"up": {"w": (nl, D, d.d_ff)},
                    "down": {"w": (nl, d.d_ff, D)}},
        }],
        "head": {"w": (D, V)},
    }


def _leaf_init(path: str, shape, key, d: Dims):
    """One leaf's initial value, by its role in the model."""
    last = path.rsplit("/", 1)[-1]
    if path == "embed/table":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if last == "g" or last == "b" or last == "window_bias":
        return jnp.zeros(shape, jnp.float32)
    if last == "skip":
        return jnp.ones(shape, jnp.float32)
    if last == "decay_log_rate":
        row = jnp.linspace(math.log(d.decay[0]), math.log(d.decay[1]),
                           shape[-1], dtype=jnp.float32)
        return jnp.broadcast_to(row, shape)
    if last == "short_filter":
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(
            shape[-1])
    # matrices (..., fan_in, fan_out)
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}{k}/")
    elif isinstance(tree, list):
        for i, v in enumerate(tree):
            yield from _paths(v, f"{prefix}{i}/")
    else:
        yield prefix[:-1], tree


def _build(tree, fn, prefix=""):
    if isinstance(tree, dict):
        return {k: _build(v, fn, f"{prefix}{k}/") for k, v in tree.items()}
    if isinstance(tree, list):
        return [_build(v, fn, f"{prefix}{i}/") for i, v in enumerate(tree)]
    return fn(prefix[:-1], tree)


def leaf_paths(d: Dims):
    return [p for p, _ in _paths(_param_shapes(d))]


def init_params(seed, d: Dims) -> Params:
    """All weights from the seed, float32; jit this (one call on device).
    ``seed`` is a uint32 pair ``(hi, lo)`` so that any 64-bit seed works."""
    base = jax.random.wrap_key_data(jnp.asarray(seed, jnp.uint32),
                                    impl="threefry2x32")
    index = {p: i for i, p in enumerate(leaf_paths(d))}
    return _build(
        _param_shapes(d),
        lambda p, s: _leaf_init(p, s, jax.random.fold_in(base, index[p]), d),
    )


def seed_words(seed: int) -> np.ndarray:
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


# ----------------------------------------------------------------- model

def rmsnorm(x, g):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + g)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def filters(fp, d: Dims, L: int):
    """(order, D, L) taps on a grid of L points (App. D.3, Algorithm 2)."""
    K = (d.pos_dim - 1) // 2
    t = jnp.linspace(0.0, 1.0, L, dtype=jnp.float32)[:, None]
    ang = 2.0 * math.pi * jnp.arange(K, dtype=jnp.float32)[None, :] * t
    h = jnp.concatenate([t, jnp.cos(ang), jnp.sin(ang)], axis=-1)
    n = len(fp["ffn"])
    for i, layer in enumerate(fp["ffn"]):
        h = h @ layer["w"] + layer["b"]
        if i < n - 1:
            h = jnp.sin(d.sine_freq * h)
    s = jnp.arange(L, dtype=jnp.float32)[:, None] / L
    window = (jnp.exp(-jnp.exp(fp["decay_log_rate"])[None, :] * s * 8.0)
              + 0.1 * jax.nn.sigmoid(fp["window_bias"])[None, :])
    h = (h * window).reshape(L, d.order, d.d_model).transpose(1, 2, 0)
    return h / (jnp.sum(jnp.abs(h), axis=-1, keepdims=True) + 1e-8)


def causal_conv(u, h):
    """y[b, t, c] = Σ_{s<=t} h[c, t-s] u[b, s, c], by FFT on 2L points."""
    L = u.shape[1]
    n = 2 * L
    U = jnp.fft.rfft(u, n=n, axis=1)
    H = jnp.fft.rfft(h, n=n, axis=1).T[None]
    return jnp.fft.irfft(U * H, n=n, axis=1)[:, :L]


def hyena_mixer(p, d: Dims, x, R, grid: int):
    """Def. 3.1 of order N, with taps evaluated on ``grid`` points."""
    B, L, D = x.shape
    z = R(x @ p["in_proj"]["w"] + p["in_proj"]["b"])
    w = p["short_filter"]  # (inner, K)
    zs = sum(
        jnp.pad(z, ((0, 0), (k, 0), (0, 0)))[:, :L] * w[:, k]
        for k in range(w.shape[1])
    )
    zs = R(zs)
    parts = jnp.split(zs, d.order + 1, axis=-1)
    v, gates = parts[0], parts[1:]
    h = filters(p["filters"], d, grid)[:, :, :L]
    skip = p["filters"]["skip"].reshape(d.order, D)
    for n in range(d.order):
        y = R(causal_conv(v, h[n]) + v * skip[n])
        v = R(y * gates[n])
    return R(v @ p["out_proj"]["w"] + p["out_proj"]["b"])


def block(p, d: Dims, x, R, grid: int):
    x = R(x + hyena_mixer(p["mixer"], d, R(rmsnorm(x, p["norm1"]["g"])), R,
                          grid))
    h = R(rmsnorm(x, p["norm2"]["g"]))
    h = R(gelu_tanh(R(h @ p["mlp"]["up"]["w"])))
    return R(x + R(h @ p["mlp"]["down"]["w"]))


def hidden(params: Params, d: Dims, tokens, prec: Precision = FP32,
           grid: Optional[int] = None):
    """Final-normed hidden states (B, L, D); layers scanned with remat."""
    R = prec.round
    p = jax.tree_util.tree_map(R, params)
    grid = grid or tokens.shape[1]
    x = R(p["embed"]["table"][tokens])

    @jax.checkpoint
    def body(x, lp):
        return block(lp, d, x, R, grid), None

    x, _ = jax.lax.scan(body, x, p["groups"][0])
    return R(rmsnorm(x, p["final_norm"]["g"])), p["head"]["w"]


def logits(params: Params, d: Dims, tokens, prec: Precision = FP32,
           grid: Optional[int] = None):
    x, head = hidden(params, d, tokens, prec, grid)
    return prec.round(x @ head)


def loss(params: Params, d: Dims, tokens, labels, prec: Precision = FP32,
         block_rows: int = 2048):
    """(total loss, cross-entropy): the head and softmax run over blocks
    of ``block_rows`` tokens, recomputed in the backward pass."""
    x, head = hidden(params, d, tokens, prec)
    rows = min(block_rows, labels.size)
    xs = x.reshape(-1, rows, x.shape[-1])
    ys = labels.reshape(-1, rows)

    @jax.checkpoint
    def part(carry, xy):
        xb, yb = xy
        lg = prec.round(xb @ head)
        logz = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, yb[:, None], axis=-1)[:, 0]
        ce, zl = carry
        return (ce + jnp.sum(logz - ll), zl + jnp.sum(logz * logz)), None

    (ce, zl), _ = jax.lax.scan(part, (0.0, 0.0), (xs, ys))
    n = labels.size
    return ce / n + d.z_loss_weight * zl / n, ce / n


# ------------------------------------------------------------- optimizer

@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 6e-4
    b1: float = 0.9
    b2: float = 0.98
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1

    def lr_at(self, step: int) -> float:
        warm = min(step / max(self.warmup_steps, 1), 1.0)
        frac = min(max((step - self.warmup_steps)
                       / max(self.total_steps - self.warmup_steps, 1), 0.0),
                   1.0)
        decay = self.min_lr_ratio + (1 - self.min_lr_ratio) * 0.5 * (
            1 + math.cos(math.pi * frac))
        return self.lr * warm * decay


def leaf_norms(tree) -> jax.Array:
    """Per-leaf l2 norms, in ``leaf_paths`` order."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for _, v in _paths(tree)
    ])


def diff_norms(a, b) -> jax.Array:
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for (_, x), (_, y) in zip(_paths(a), _paths(b))
    ])


def make_train_step(d: Dims, opt: AdamW, prec: Precision = FP32):
    """(params, m, v, tokens, labels, step, lr) -> (params, m, v, ce,
    raw-gradient leaf norms).  Jit it under the matmul precision wanted."""

    def step(params, m, v, tokens, labels, t, lr):
        (_, ce), g = jax.value_and_grad(
            lambda p: loss(p, d, tokens, labels, prec), has_aux=True)(params)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt.clip_norm / (gn + 1e-9))
        bc1 = 1.0 - opt.b1 ** t
        bc2 = 1.0 - opt.b2 ** t

        def upd(p, gi, mi, vi):
            gi = gi * scale
            mi = opt.b1 * mi + (1 - opt.b1) * gi
            vi = opt.b2 * vi + (1 - opt.b2) * gi * gi
            delta = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt.eps)
            if p.ndim >= 2:
                delta = delta + opt.weight_decay * p
            return p - lr * delta, mi, vi

        out = jax.tree_util.tree_map(upd, params, g, m, v)
        is3 = lambda x: isinstance(x, tuple)
        pick = lambda i: jax.tree_util.tree_map(lambda o: o[i], out,
                                                is_leaf=is3)
        return pick(0), pick(1), pick(2), ce, leaf_norms(g)

    return step
