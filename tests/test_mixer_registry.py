"""Registry conformance suite: every registered TokenMixer honors the shared
contract — teacher-forced forward vs. decode parity, cache shape/dtype
specs, metadata (state_bytes / flops) against measured shapes — and a new
mixer can be registered without touching blocks.py / lm.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.param import Ax, split_params
from repro.configs.base import ModelConfig
from repro.core.conv_api import (
    get_conv_backend,
    registered_conv_backends,
    resolve_conv_backend,
)
from repro.launch.mesh import make_mesh
from repro.models import blocks, lm
from repro.models.mixer_api import (
    ApplyContext,
    TokenMixer,
    get_mixer,
    register_mixer,
    registered_mixers,
)

BUILTIN_MIXERS = (
    "attention", "local_attention", "hyena", "ssd", "rglru",
    "hyena_se", "hyena_mr", "hyena_li",
)


def small_cfg(mixer: str) -> ModelConfig:
    """A tiny ModelConfig exercising the named mixer."""
    return ModelConfig(
        name=f"conformance-{mixer}", family="test",
        n_layers=1, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=64, vocab_size=64, pattern=(mixer,), local_window=8,
        ssm_state=16, ssd_head_dim=16, rnn_width=32,
        hyena_filter_width=16, hyena_pos_dim=9,
        hyena_se_len=4, hyena_mr_support=8,
    )


def test_all_builtins_registered():
    names = set(registered_mixers())
    assert names >= set(BUILTIN_MIXERS), names


def test_unknown_mixer_raises_with_registered_list():
    with pytest.raises(ValueError, match="registered"):
        get_mixer("mamba3")


# ------------------------------------------------------------- conformance

@pytest.mark.parametrize("mixer", BUILTIN_MIXERS)
def test_forward_decode_parity(mixer):
    """apply == prefill teacher-forced outputs; decode_step continues a
    prefilled cache exactly; decode-from-empty-cache matches apply."""
    cfg = small_cfg(mixer)
    m = get_mixer(mixer)
    mc = m.make_config(cfg)
    B, L, L0 = 2, 12, 8
    params, _ = split_params(m.init(jax.random.PRNGKey(0), mc))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, L, cfg.d_model))
    ctx = ApplyContext()

    y_apply = m.apply(params, mc, x, ctx)
    assert y_apply.shape == (B, L, cfg.d_model)
    assert np.isfinite(np.asarray(y_apply, np.float32)).all()

    # prefill over the full sequence is the teacher-forced forward
    y_pf, _ = m.prefill(params, mc, x, L, jnp.float32, ctx)
    np.testing.assert_allclose(
        np.asarray(y_pf, np.float32), np.asarray(y_apply, np.float32),
        rtol=2e-3, atol=2e-3,
    )

    # prefill a prefix, then decode the rest token-by-token
    assert m.supports_decode
    _, cache = m.prefill(params, mc, x[:, :L0], L, jnp.float32, ctx)
    for t in range(L0, L):
        y_t, cache = m.decode_step(params, mc, x[:, t], cache)
        np.testing.assert_allclose(
            np.asarray(y_t, np.float32), np.asarray(y_apply[:, t], np.float32),
            rtol=2e-3, atol=2e-3, err_msg=f"{mixer} decode step {t}",
        )

    # decode from an *empty* init_cache reproduces the whole sequence
    cache = m.init_cache(mc, B, L, jnp.float32)
    for t in range(L):
        y_t, cache = m.decode_step(params, mc, x[:, t], cache)
        np.testing.assert_allclose(
            np.asarray(y_t, np.float32), np.asarray(y_apply[:, t], np.float32),
            rtol=2e-3, atol=2e-3, err_msg=f"{mixer} cold decode step {t}",
        )


@pytest.mark.parametrize("mixer", BUILTIN_MIXERS)
def test_cache_spec_stable_under_decode(mixer):
    """decode_step preserves the cache treedef and every leaf's shape/dtype
    (required for lax.scan over decode steps)."""
    cfg = small_cfg(mixer)
    m = get_mixer(mixer)
    mc = m.make_config(cfg)
    B, L = 2, 8
    params, _ = split_params(m.init(jax.random.PRNGKey(0), mc))
    cache = m.init_cache(mc, B, L, jnp.bfloat16)
    x_t = jax.random.normal(jax.random.PRNGKey(1), (B, cfg.d_model),
                            jnp.bfloat16)
    _, cache2 = m.decode_step(params, mc, x_t, cache)
    spec = lambda c: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), c)
    assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(cache2)
    assert spec(cache) == spec(cache2), mixer


@pytest.mark.parametrize("mixer", BUILTIN_MIXERS)
def test_cache_slot_ops_conformance(mixer):
    """The serving slot contract: cache_slot_axes covers every cache key,
    cache_slice/cache_insert roundtrip one request's state between a pooled
    cache and a batch-1 cache, and cache_reset zeroes exactly one slot."""
    cfg = small_cfg(mixer)
    m = get_mixer(mixer)
    mc = m.make_config(cfg)
    B, L = 3, 8
    params, _ = split_params(m.init(jax.random.PRNGKey(0), mc))
    xa = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, L, cfg.d_model))
    xb = 0.5 * jax.random.normal(jax.random.PRNGKey(2), (1, L, cfg.d_model))
    _, pool = m.prefill(params, mc, xa, L, jnp.float32, ApplyContext())
    _, one = m.prefill(params, mc, xb, L, jnp.float32, ApplyContext())
    axes = m.cache_slot_axes(mc)
    assert set(axes) <= set(pool), (set(axes), set(pool))
    assert set(m.init_cache(mc, B, L, jnp.float32)) <= set(pool)

    # slice(insert(pool, s, one), s) == one, for every per-slot leaf
    slot = 1
    pool2 = m.cache_insert(mc, pool, slot, one)
    back = m.cache_slice(mc, pool2, slot)
    for k in pool:
        np.testing.assert_allclose(
            np.asarray(back[k], np.float32), np.asarray(one[k], np.float32),
            err_msg=f"{mixer}.{k}",
        )
        # the other slots are untouched by the insert
        ax = axes.get(k, 0)
        if ax >= 0:
            np.testing.assert_allclose(
                np.asarray(jnp.take(pool2[k], 0, axis=ax), np.float32),
                np.asarray(jnp.take(pool[k], 0, axis=ax), np.float32),
                err_msg=f"{mixer}.{k} slot 0 disturbed",
            )

    # reset zeroes exactly the target slot; shared leaves survive
    pool3 = m.cache_reset(mc, pool2, slot)
    for k in pool3:
        ax = axes.get(k, 0)
        if ax < 0:
            np.testing.assert_array_equal(
                np.asarray(pool3[k]), np.asarray(pool2[k]), err_msg=k
            )
        else:
            assert float(jnp.sum(jnp.abs(
                jnp.take(pool3[k], slot, axis=ax).astype(jnp.float32)
            ))) == 0.0, f"{mixer}.{k} not reset"
            np.testing.assert_array_equal(
                np.asarray(jnp.take(pool3[k], 2, axis=ax)),
                np.asarray(jnp.take(pool2[k], 2, axis=ax)),
                err_msg=f"{mixer}.{k} slot 2 disturbed by reset",
            )

    # an inserted slot decodes exactly like the standalone batch-1 cache
    x_t = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (B, cfg.d_model))
    y_pool, _ = m.decode_step(params, mc, x_t, pool2)
    y_one, _ = m.decode_step(params, mc, x_t[slot : slot + 1], one)
    np.testing.assert_allclose(
        np.asarray(y_pool[slot], np.float32),
        np.asarray(y_one[0], np.float32), rtol=1e-4, atol=1e-4,
        err_msg=f"{mixer}: pooled decode != standalone decode",
    )


@pytest.mark.parametrize("mixer", BUILTIN_MIXERS)
def test_cache_shard_axes_conformance(mixer):
    """The rule-driven cache-sharding spec (DESIGN.md §9): every named key
    exists in the serving cache with a rank-matching tuple of known logical
    names, and the rule engine resolves the spec on a production-shaped
    mesh without touching the slot dim or cursors."""
    from repro.distributed.sharding import TP_RULES, resolve_spec
    from jax.sharding import PartitionSpec as P

    cfg = small_cfg(mixer)
    m = get_mixer(mixer)
    mc = m.make_config(cfg)
    spec = m.cache_shard_axes(mc)
    cache = jax.eval_shape(lambda: m.init_cache(mc, 2, 16, jnp.bfloat16))
    params, _ = split_params(m.init(jax.random.PRNGKey(0), mc))
    full = jax.eval_shape(
        lambda: m.prefill(params, mc, jnp.zeros((2, 8, cfg.d_model)), 16,
                          jnp.bfloat16, ApplyContext())[1]
    )
    assert set(spec) <= set(full), (mixer, set(spec) - set(full))
    known = set(TP_RULES) | {None}

    class FakeMesh:  # debug-mesh shape: reduced configs have few heads
        shape = {"data": 2, "model": 2}

    # per-slot cursors carry no spec at all: they must replicate (every
    # chip owns every slot's RoPE position / validity mask)
    assert "t" not in spec, (mixer, spec)
    slot_axes = m.cache_slot_axes(mc)
    for k, ax in spec.items():
        leaf = full[k]
        assert len(ax) == leaf.ndim, (mixer, k, ax, leaf.shape)
        assert set(ax) <= known, (mixer, k, set(ax) - known)
        p = resolve_spec(ax, leaf.shape, FakeMesh())
        # the slot dim may shard over the data axes (data-parallel
        # request ownership) but never over 'model' — the tensor-parallel
        # axis belongs to heads/channels
        slot_dim = slot_axes.get(k, 0)
        if slot_dim >= 0 and len(p) > slot_dim:
            entry = p[slot_dim]
            names = entry if isinstance(entry, tuple) else (
                (entry,) if entry else ())
            assert "model" not in names, (mixer, k, p)
    # every decode-capable builtin shards at least one cache leaf over the
    # model axis — serving caches scale with TP, not per-chip replication
    resolved = [
        resolve_spec(ax, full[k].shape, FakeMesh()) for k, ax in spec.items()
    ]
    assert any("model" in jax.tree_util.tree_leaves(list(p)) or
               any(e == "model" for e in p) for p in resolved), (
        mixer, resolved
    )


def _tree_bytes(tree) -> int:
    return sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
    )


@pytest.mark.parametrize("mixer", BUILTIN_MIXERS)
def test_state_bytes_matches_measured_cache(mixer):
    """state_bytes metadata == the byte count of the *serving* cache — the
    prefill-populated one, which for hyena also carries the fp32 decode
    filter taps — at batch 1 with the bf16 cache dtype.  No drift between
    the capability tables and the real cache layout."""
    cfg = small_cfg(mixer)
    m = get_mixer(mixer)
    mc = m.make_config(cfg)
    params, _ = split_params(m.init(jax.random.PRNGKey(0), mc))
    for max_len in (16, 64):
        x = jnp.zeros((1, 8, cfg.d_model))
        struct = jax.eval_shape(
            lambda x: m.prefill(params, mc, x, max_len, jnp.bfloat16,
                                ApplyContext())[1], x
        )
        assert m.state_bytes(cfg, max_len) == _tree_bytes(struct), (
            mixer, max_len
        )
        # the empty init_cache never exceeds the populated serving cache
        empty = jax.eval_shape(
            lambda: m.init_cache(mc, 1, max_len, jnp.bfloat16)
        )
        assert _tree_bytes(empty) <= m.state_bytes(cfg, max_len)


@pytest.mark.parametrize("mixer", BUILTIN_MIXERS)
def test_flops_metadata_sane(mixer):
    """flops metadata scales with L and covers at least one mul+add per
    mixer parameter per token (every dense weight touches every token)."""
    cfg = small_cfg(mixer)
    m = get_mixer(mixer)
    mc = m.make_config(cfg)
    L = 64
    f1, f2 = m.flops(cfg, L), m.flops(cfg, 2 * L)
    assert f1 > 0 and np.isfinite(f1)
    assert f2 >= 2 * f1  # at least linear in L
    n_params = sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), mc))
        )
    )
    assert f1 >= L * n_params, (mixer, f1, L * n_params)


def test_local_attention_state_is_windowed():
    """Capability metadata reflects the ring buffer: local attention state
    stops growing at the window size."""
    cfg = small_cfg("local_attention")
    m = get_mixer("local_attention")
    assert m.state_bytes(cfg, 1 << 20) == m.state_bytes(cfg, cfg.local_window)
    assert get_mixer("attention").state_bytes(cfg, 128) > \
        get_mixer("attention").state_bytes(cfg, 64)


# ------------------------------------------------- extension without edits

@register_mixer
class _ToyMixer(TokenMixer):
    """A per-channel gain — registered by the *test* to prove that adding a
    mixer touches zero dispatch sites in blocks.py / lm.py."""

    name = "toy_gain"

    def make_config(self, cfg):
        return cfg.d_model

    def init(self, key, d):
        return {"gain": Ax(jnp.ones((d,), jnp.float32), ("embed",))}

    def apply(self, params, d, h, ctx):
        return h * params["gain"].astype(h.dtype)

    def init_cache(self, d, batch, max_len, dtype):
        return {"t": jnp.zeros((), jnp.int32)}

    def prefill(self, params, d, h, max_len, dtype, ctx):
        return self.apply(params, d, h, ctx), {"t": jnp.asarray(h.shape[1], jnp.int32)}

    def decode_step(self, params, d, h_t, cache):
        return h_t * params["gain"].astype(h_t.dtype), {"t": cache["t"] + 1}

    def state_bytes(self, cfg, max_len):
        return 4

    def flops(self, cfg, L):
        return 2.0 * L * cfg.d_model


def test_new_mixer_runs_through_lm_without_dispatch_edits():
    cfg = dataclasses.replace(
        small_cfg("attention"), name="toy-arch", pattern=("toy_gain",),
        n_layers=2,
    )
    params, _ = split_params(lm.init_lm(jax.random.PRNGKey(0), cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    logits, _ = lm.forward(params, cfg, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    caches = lm.init_caches(cfg, 2, 8, dtype=jnp.float32)
    lg, caches = lm.decode_step(params, cfg, tokens[:, 0], caches)
    assert lg.shape == (2, cfg.vocab_size)
    assert np.isfinite(np.asarray(lg)).all()
    # blocks-level helpers resolve it too
    assert blocks.mixer_config(cfg, "toy_gain") == cfg.d_model


def test_hyena_prefill_honors_ctx_conv_backend():
    """The serving path's backend override reaches the prompt long convs:
    prefill under the O(L²) oracle matches prefill under the default FFT."""
    cfg = small_cfg("hyena")
    m = get_mixer("hyena")
    mc = m.make_config(cfg)
    params, _ = split_params(m.init(jax.random.PRNGKey(0), mc))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.d_model))
    y_fft, c_fft = m.prefill(params, mc, x, 12, jnp.float32, ApplyContext())
    y_dir, c_dir = m.prefill(
        params, mc, x, 12, jnp.float32, ApplyContext(conv_backend="direct")
    )
    np.testing.assert_allclose(np.asarray(y_fft), np.asarray(y_dir),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(c_fft["long"]),
                               np.asarray(c_dir["long"]), rtol=2e-3, atol=2e-3)


def test_ctx_mesh_override_matches_ambient():
    """ApplyContext.mesh is honored by the lm entry points: running under an
    explicit 1x1 mesh override matches the meshless run."""
    cfg = small_cfg("hyena")
    params, _ = split_params(lm.init_lm(jax.random.PRNGKey(0), cfg))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    want, _ = lm.forward(params, cfg, tokens)
    mesh = make_mesh((1, 1), ("data", "model"))
    got, _ = lm.forward(params, cfg, tokens, ctx=ApplyContext(mesh=mesh))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- conv backend API

def test_conv_backends_agree_on_small_input():
    B, L, D = 2, 32, 4
    u = jax.random.normal(jax.random.PRNGKey(0), (B, L, D))
    h = jax.random.normal(jax.random.PRNGKey(1), (D, L)) / L
    skip = jax.random.normal(jax.random.PRNGKey(2), (D,))
    want = get_conv_backend("fft_local")(u, h, skip)
    for name, backend in registered_conv_backends().items():
        got = backend(u, h, skip)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=5e-3, atol=5e-3,
            err_msg=name,
        )


def test_resolve_conv_backend_env_and_override(monkeypatch):
    monkeypatch.delenv("REPRO_CONV_BACKEND", raising=False)
    assert resolve_conv_backend() == "fft"
    monkeypatch.setenv("REPRO_CONV_BACKEND", "blockfft")
    assert resolve_conv_backend() == "blockfft"
    assert resolve_conv_backend("direct") == "direct"  # override beats env
    monkeypatch.setenv("REPRO_CONV_BACKEND", "cufft")
    # the error names the bad backend, where it came from, and the sorted
    # registered list — a typo'd env var is diagnosable from the message
    with pytest.raises(
        ValueError,
        match=r"unknown conv backend 'cufft' \(from \$REPRO_CONV_BACKEND\)",
    ):
        resolve_conv_backend()
    with pytest.raises(ValueError, match="blockfft_overlap"):
        resolve_conv_backend("no-such-backend")


def test_backend_length_constraint():
    direct = get_conv_backend("direct")
    with pytest.raises(ValueError, match="supports L"):
        direct.validate_len(1 << 20)


def test_pattern_validated_at_config_registration():
    from repro.configs.base import register

    with pytest.raises(ValueError, match="registered"):
        register(dataclasses.replace(
            small_cfg("attention"), name="bad-arch", pattern=("atention",)
        ))


@pytest.mark.parametrize("mixer", BUILTIN_MIXERS)
def test_cache_page_axes_conformance(mixer):
    """The paging contract (mixer_api.cache_page_axes): every named key
    exists in the cache on the max_len grid with its time axis exactly one
    past the slot axis; those leaves really are append-only (positions
    below the cursor never move once written); and decode tolerates
    arbitrary garbage at positions >= t — the property that lets the paged
    allocator map unwritten table entries to a recycled trash block."""
    cfg = small_cfg(mixer)
    m = get_mixer(mixer)
    mc = m.make_config(cfg)
    spec = m.cache_page_axes(mc)
    slots = m.cache_slot_axes(mc)
    B, L, L0 = 2, 12, 6
    params, _ = split_params(m.init(jax.random.PRNGKey(0), mc))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (B, L, cfg.d_model))
    _, cache = m.prefill(params, mc, x[:, :L0], L, jnp.float32,
                         ApplyContext())
    for k, ax in spec.items():
        assert k in cache, (mixer, k)
        assert slots.get(k, 0) >= 0, (mixer, k, "paged leaf must be per-slot")
        assert ax == slots.get(k, 0) + 1, (mixer, k, ax)
        assert cache[k].shape[ax] == L, (mixer, k, cache[k].shape)
    if not spec:
        return  # windowed / recurrent mixers: all state pinned

    def time_slice(leaf, ax, lo, hi):
        idx = [slice(None)] * leaf.ndim
        idx[ax] = slice(lo, hi)
        return np.asarray(leaf[tuple(idx)], np.float32)

    def corrupt(leaf, ax, start):
        pos = jnp.arange(leaf.shape[ax]).reshape(
            [leaf.shape[ax] if d == ax else 1 for d in range(leaf.ndim)]
        )
        return jnp.where(pos >= start, jnp.asarray(37.5, leaf.dtype), leaf)

    # garbage past the cursor must be invisible to decode (it is either
    # masked or overwritten at the write position before any read)
    dirty = {
        k: corrupt(v, spec[k], L0) if k in spec else v
        for k, v in cache.items()
    }
    clean, c, d = cache, cache, dirty
    for t in range(L0, L):
        y_c, c = m.decode_step(params, mc, x[:, t], c)
        y_d, d = m.decode_step(params, mc, x[:, t], d)
        np.testing.assert_allclose(
            np.asarray(y_c, np.float32), np.asarray(y_d, np.float32),
            rtol=1e-6, atol=1e-6,
            err_msg=f"{mixer} step {t}: garbage past the cursor leaked",
        )
        # append-only: everything before this step's write position is
        # byte-stable across the step
        for k, ax in spec.items():
            np.testing.assert_array_equal(
                time_slice(c[k], ax, 0, t), time_slice(clean[k], ax, 0, t),
                err_msg=f"{mixer}.{k} rewrote history at step {t}",
            )
        clean = {k: v for k, v in c.items()}


def test_cache_page_axes_lm_collector_validates_adjacency():
    """lm.cache_page_axes mirrors the cache tree with the paged time axis
    (shifted for scan-stacked groups) or -1, and rejects specs whose time
    axis is not slot + 1."""
    cfg = small_cfg("attention")
    m = get_mixer("attention")
    mc = m.make_config(cfg)
    params, _ = split_params(m.init(jax.random.PRNGKey(0), mc))
    cache = m.prefill(params, mc, jnp.zeros((1, 4, cfg.d_model)), 8,
                      jnp.float32, ApplyContext())[1]
    caches = {"groups": [cache]}
    axes = lm.cache_page_axes(cfg, caches)
    page_spec = m.cache_page_axes(mc)
    for k in cache:
        want = page_spec[k] + 1 if k in page_spec else -1  # stacked shift
        assert axes["groups"][0][k] == want, (k, axes["groups"][0][k])

    class BadMixer(type(m)):
        name = "bad-paging"

        def cache_page_axes(self, mc):
            return {"k": 3}  # k's slot axis is 0 -> time axis must be 1

    import unittest.mock as mock

    import repro.models.mixer_api as mixer_api

    with mock.patch.object(mixer_api, "get_mixer",
                           lambda name: BadMixer()):
        with pytest.raises(ValueError, match="slot axis"):
            lm.cache_page_axes(cfg, caches)
