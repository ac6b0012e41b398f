"""Block-FFT conv (beyond-paper MXU path) and Hyena-ViT tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blockfft import _factor, _split, blockfft_causal_conv
from repro.core.fftconv import fft_causal_conv


@pytest.mark.parametrize("L", [8, 32, 128, 512])
@pytest.mark.parametrize("D", [1, 6])
def test_blockfft_matches_fft(L, D):
    u = jax.random.normal(jax.random.PRNGKey(0), (2, L, D))
    h = jax.random.normal(jax.random.PRNGKey(1), (D, L)) / L
    skip = jax.random.normal(jax.random.PRNGKey(2), (D,))
    got = blockfft_causal_conv(u, h, skip)
    want = fft_causal_conv(u, h, skip)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-4)


def test_blockfft_in_hyena_mixer():
    from repro.common.param import split_params
    from repro.core import HyenaConfig, FilterConfig
    from repro.core.operator import init_hyena
    from repro.models.hyena import apply_hyena_mixer
    from repro.models.mixer_api import ApplyContext

    cfg = HyenaConfig(
        d_model=16, order=2,
        filter=FilterConfig(d_model=16, order=2, ffn_width=16, pos_dim=9),
    )
    params, _ = split_params(init_hyena(jax.random.PRNGKey(0), cfg))
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16))
    y_fft = apply_hyena_mixer(params, cfg, u, ApplyContext(conv_backend="fft"))
    y_bl = apply_hyena_mixer(
        params, cfg, u, ApplyContext(conv_backend="blockfft")
    )
    np.testing.assert_allclose(y_fft, y_bl, rtol=2e-3, atol=2e-3)


def _conv_and_grads(conv, u, h, skip, gate, dy):
    """The conv's output and its gradients in u, h, skip (and gate), as
    float32 numpy, from one jitted ``value_and_grad``."""
    argnums = (0, 1, 2) if gate is None else (0, 1, 2, 3)

    def loss(u, h, skip, gate):
        y = conv(u, h, skip, gate)
        return jnp.vdot(y.astype(jnp.float32), dy), y

    (_, y), grads = jax.jit(
        jax.value_and_grad(loss, argnums=argnums, has_aux=True)
    )(u, h, skip, gate)
    return [np.asarray(x, np.float32) for x in (y, *grads)]


# power of two, 5-smooth (padded N = 90 = 2·3²·5), prime (N = 75); the
# last case runs under an ambient bf16 matmul precision
_MXU_CASES = [
    (L, dtype, gated, None)
    for L in (64, 45, 37)
    for dtype in ("float32", "bfloat16")
    for gated in (False, True)
] + [(37, "float32", True, "bfloat16")]


@pytest.mark.parametrize("L,dtype,gated,precision", _MXU_CASES)
def test_blockfft_matches_direct_values_and_grads(L, dtype, gated,
                                                  precision):
    """The MXU four-step conv (the ``fft`` backend's transform on a TPU)
    against the O(L²) oracle, in values and in ``jax.grad`` w.r.t. u, h,
    skip and gate.  Its matmuls are pinned to HIGHEST, so an ambient
    ``default_matmul_precision("bfloat16")`` must not move it off the
    float32 tolerance.  XLA:CPU computes float32 dots in full at any
    precision, so that case cannot fail here; the lowering test in
    ``test_chip_compile.py`` checks what the chip is given."""
    from repro.core.fftconv import direct_causal_conv

    dt = getattr(jnp, dtype)
    rng = np.random.default_rng(L)
    B, D = 2, 5
    u = jnp.asarray(rng.standard_normal((B, L, D)), dt)
    h = jnp.asarray(rng.standard_normal((D, L)) / np.sqrt(L), dt)
    skip = jnp.asarray(rng.standard_normal((D,)), dt)
    gate = jnp.asarray(rng.standard_normal((B, L, D)), dt) if gated else None
    dy = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
    with jax.default_matmul_precision(precision or "highest"):
        got = _conv_and_grads(blockfft_causal_conv, u, h, skip, gate, dy)
    # the oracle runs on the same values in float32: in bf16 its own
    # gather-transpose would sum dh's contributions in bf16
    f32 = [None if x is None else x.astype(jnp.float32)
           for x in (u, h, skip, gate)]
    with jax.default_matmul_precision("highest"):
        want = _conv_and_grads(direct_causal_conv, *f32, dy)
    # float32: normwise relative error near fp32 rounding; bf16: the
    # program rounds to bf16 up to three times on the way (the cotangent,
    # the gated product, the result), 2^-8 relative each
    tol = 1e-5 if dtype == "float32" else 2.0 ** -6
    for name, g, w in zip(("y", "du", "dh", "dskip", "dgate"), got, want):
        err = np.max(np.abs(g - w)) / np.max(np.abs(w))
        assert err < tol, (name, err)


def test_factorization():
    for split in (_factor, _split):
        for N in [16, 64, 1024, 65536]:
            R, S = split(N)
            assert R * S == N and R >= S
    # the MXU path's rule: the least matmul work, measured fastest on a
    # v5e among the splits of 4096 (the hyena-153m train step's N)
    assert _split(4096) == (128, 32)


def test_vit_forward_and_grad():
    from repro.common.param import split_params
    from repro.models.vit import ViTConfig, apply_vit, init_vit, vit_loss

    cfg = ViTConfig(image_size=16, patch_size=4, d_model=32, n_layers=2,
                    d_ff=64, n_classes=10)
    params, _ = split_params(init_vit(jax.random.PRNGKey(0), cfg))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3))
    logits = apply_vit(params, cfg, imgs)
    assert logits.shape == (4, 10)
    labels = jnp.asarray([0, 1, 2, 3])
    (loss, m), g = jax.value_and_grad(vit_loss, has_aux=True)(
        params, cfg, imgs, labels
    )
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(g))


def test_vit_learns():
    """Tiny Hyena-ViT separates two synthetic classes in a few steps."""
    from repro.common.param import split_params
    from repro.models.vit import ViTConfig, init_vit, vit_loss
    from repro.train import optim as O

    cfg = ViTConfig(image_size=8, patch_size=4, d_model=16, n_layers=1,
                    d_ff=32, n_classes=2)
    params, _ = split_params(init_vit(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    imgs = rng.normal(size=(32, 8, 8, 3)).astype(np.float32)
    labels = (imgs.mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    imgs[labels == 1] += 0.8
    imgs_j, labels_j = jnp.asarray(imgs), jnp.asarray(labels)
    ocfg = O.AdamWConfig(lr=3e-3, warmup_steps=0, schedule="constant",
                         weight_decay=0.0)
    opt = O.init_adamw(params)
    losses = []
    step = jax.jit(lambda p, o: _step(p, o, cfg, imgs_j, labels_j, ocfg))
    for _ in range(25):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.6, losses[::6]


def _step(params, opt, cfg, imgs, labels, ocfg):
    from repro.models.vit import vit_loss
    from repro.train import optim as O

    (loss, _), g = jax.value_and_grad(vit_loss, has_aux=True)(
        params, cfg, imgs, labels
    )
    params, opt, _ = O.adamw_update(ocfg, g, opt, params)
    return params, opt, loss
