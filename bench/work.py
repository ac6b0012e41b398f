"""Operations and bytes the Hyena LM's algorithm needs, from its shapes,
and the table of device peaks.

Model FLOPs follow the paper's App. A.2 accounting (Table 4.4), two
operations per multiply-add: per token and layer, the input projections
``(N+1)·D²``, the short conv ``(N+1)·D·3``, the FFT conv ``5·N·D·log2 L``,
the output projection ``D²`` and the MLP ``2·D·d_ff``; per layer and
forward pass, the implicit filter FFN on the L-point grid (once for the
batch); the LM head ``D·V`` per token.  A train step is three forward
passes' worth (backward = 2 x forward); recomputation is not counted.

The long conv's work is that of a causal FFT convolution on ``n = 2L``
points, whatever implements it: real FFTs of input and filter
(``2.5·n·log2 n`` each), the complex product, the inverse FFT, and the
skip-and-gate epilogue; its bytes are the input, gate and output
activations and the float32 filter.  Training counts the forward conv and
the two backward convs (input and filter gradients).
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Iterable, Tuple

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    """The peaks of ``device_kind``; a device not in the table raises."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {path.name} "
            f"(have {sorted(table)})")
    return {k: float(v) for k, v in table[device_kind].items()}


def least_time_s(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    return max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


# ------------------------------------------------------------ model FLOPs

def layer_token_flops(d, L: int) -> float:
    D, N, K = d.d_model, d.order, d.short_len
    proj = (N + 1) * D * D
    short = (N + 1) * D * K
    fft = 5 * N * D * math.log2(max(L, 2))
    out = D * D
    mlp = 2 * D * d.d_ff
    return 2.0 * (proj + short + fft + out + mlp)


def filter_flops(d, L: int) -> float:
    w = d.filter_width
    per_point = (d.pos_dim * w + (d.filter_depth - 2) * w * w
                 + w * d.order * d.d_model)
    return 2.0 * L * per_point


def forward_flops(d, B: int, L: int) -> float:
    layers = d.n_layers * (B * L * layer_token_flops(d, L) + filter_flops(d, L))
    return layers + 2.0 * B * L * d.d_model * d.vocab_size


def train_step_flops(d, B: int, L: int) -> float:
    return 3.0 * forward_flops(d, B, L)


# -------------------------------------------------------------- long conv

def conv_work(B: int, L: int, D: int, act_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one gated causal long conv on (B, L, D)."""
    n = 2 * L
    fft = 2.5 * n * math.log2(n)
    flops = B * D * (2 * fft + 6 * (n // 2 + 1)) + D * fft + 3 * B * L * D
    nbytes = 3 * B * L * D * act_bytes + D * L * 4 + D * 4
    return flops, nbytes


def train_conv_work(d, B: int, L: int) -> Tuple[float, float]:
    f, b = conv_work(B, L, d.d_model)
    k = 3 * d.n_layers * d.order
    return k * f, k * b


def prefill_conv_work(d, L: int) -> Tuple[float, float]:
    f, b = conv_work(1, L, d.d_model)
    k = d.n_layers * d.order
    return k * f, k * b


# ----------------------------------------------------------------- decode

def matmul_params(d) -> int:
    D, N = d.d_model, d.order
    per_layer = (N + 1) * D * D + D * D + 2 * D * d.d_ff
    return d.n_layers * per_layer + D * d.vocab_size


def decode_work(d, cursors: Iterable[int], weight_bytes: int = 2,
                cache_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one decode step over the active slots, each at
    its own cursor (tokens already absorbed): the weights once, each
    slot's conv history up to its cursor, and the float32 filter taps up
    to the furthest cursor."""
    cursors = list(cursors)
    if not cursors:
        return 0.0, 0.0
    D, N, nl = d.d_model, d.order, d.n_layers
    P = matmul_params(d)
    flops = sum(2.0 * P + nl * N * 2.0 * t * D for t in cursors)
    nbytes = (P * weight_bytes + nl * N * D * max(cursors) * 4
              + sum(nl * N * t * D * cache_bytes for t in cursors))
    return flops, nbytes
