"""Operations and bytes from shapes, pinned for hyena-153m against hand
arithmetic, and the peaks table."""
import json
from pathlib import Path

import pytest

from bench import reference as ref
from bench import work

CONFIG = Path(__file__).resolve().parents[2] / "bench/configs/hyena-153m.json"


@pytest.fixture(scope="module")
def d():
    return ref.Dims.from_config(json.loads(CONFIG.read_text()))


def test_train_step_flops_hyena_153m(d):
    # per token and layer, x2 for multiply-add: in_proj 3·864², short conv
    # 3·864·3, FFT conv 5·2·864·log2(2048), out_proj 864², MLP 2·864·1728
    per_token = 2 * (3 * 864 * 864 + 3 * 864 * 3 + 5 * 2 * 864 * 11
                     + 864 * 864 + 2 * 864 * 1728)
    assert per_token == 12_149_568
    # filter FFN on the 2048-point grid, once per layer and pass:
    # 65->64, 64->64, 64->64, 64->2·864
    filt = 2 * 2048 * (65 * 64 + 2 * 64 * 64 + 64 * 2 * 864)
    head = 2 * 864 * 50257
    fwd = 18 * (8 * 2048 * per_token + filt) + 8 * 2048 * head
    assert work.forward_flops(d, 8, 2048) == pytest.approx(fwd, rel=1e-12)
    assert work.train_step_flops(d, 8, 2048) == pytest.approx(3 * fwd,
                                                              rel=1e-12)
    # about 918 MFLOP a trained token
    assert 3 * fwd / (8 * 2048) == pytest.approx(918.26e6, rel=1e-4)


def test_long_conv_work_hyena_153m(d):
    B, L, D = 8, 2048, 864
    n = 4096
    fft = 2.5 * n * 12  # one real FFT of 4096 points
    flops = B * D * (2 * fft + 6 * 2049) + D * fft + 3 * B * L * D
    nbytes = 3 * B * L * D * 2 + D * L * 4 + D * 4
    assert work.conv_work(B, L, D) == pytest.approx((flops, nbytes))
    f, b = work.train_conv_work(d, B, L)
    assert f == pytest.approx(3 * 18 * 2 * flops)
    assert b == pytest.approx(3 * 18 * 2 * nbytes)
    f, b = work.prefill_conv_work(d, 512)
    one = work.conv_work(1, 512, D)
    assert (f, b) == pytest.approx((36 * one[0], 36 * one[1]))


def test_decode_work_hyena_153m(d):
    P = 18 * (3 * 864 * 864 + 864 * 864 + 2 * 864 * 1728) + 864 * 50257
    assert work.matmul_params(d) == P
    cursors = [100, 700]
    flops = sum(2 * P + 18 * 2 * 2 * t * 864 for t in cursors)
    nbytes = (2 * P + 18 * 2 * 864 * 700 * 4
              + sum(18 * 2 * t * 864 * 2 for t in cursors))
    assert work.decode_work(d, cursors) == pytest.approx((flops, nbytes))
    assert work.decode_work(d, []) == (0.0, 0.0)


def test_peaks_by_device_kind():
    pk = work.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    # a least time is bounded by the slower of compute and bandwidth
    assert work.least_time_s(197e12, 0, pk) == pytest.approx(1.0)
    assert work.least_time_s(0, 819e9, pk) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in peaks.json"):
        work.peaks("TPU v9 imaginary")


def test_fp8_control_rounds_as_float8_e4m3():
    import jax
    import jax.numpy as jnp

    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * jnp.exp(
        3 * jax.random.normal(jax.random.PRNGKey(1), (4096,)))
    y = jnp.clip(x, -448.0, 448.0)
    want = y.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert bool(jnp.all(ref._e4m3(y) == want))
    # per-tensor scale: the largest magnitude maps to 448 and back
    q = ref.FP8.round(x)
    assert float(jnp.max(jnp.abs(q))) == float(jnp.max(jnp.abs(x)))
    assert bool(jnp.all(jnp.isfinite(q)))
