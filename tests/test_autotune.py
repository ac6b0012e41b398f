"""Autotune plan-cache round trip (DESIGN.md §8): search → persist → load →
same plan, and the planned kernel output equals the default-tile output
(plans are semantics-preserving by construction).
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.conv_api import get_conv_backend
from repro.kernels import ops


@pytest.fixture
def plan_env(tmp_path, monkeypatch):
    path = tmp_path / "plans.json"
    monkeypatch.setenv(autotune.ENV_FILE, str(path))
    monkeypatch.setenv(autotune.ENV_MODE, "search")
    autotune.reset_cache()
    yield path
    autotune.reset_cache()


def _set_mode(monkeypatch, mode):
    monkeypatch.setenv(autotune.ENV_MODE, mode)
    autotune.reset_cache()  # simulate a fresh process reading the file


def test_plan_roundtrip_short_conv(plan_env, monkeypatch):
    B, L, D, K = 2, 64, 16, 3
    u = jnp.asarray(np.random.default_rng(0).standard_normal((B, L, D)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((D, K)),
                    jnp.float32)
    y_search = ops.short_conv_gate(u, w, use_kernel=True)

    plans = json.loads(plan_env.read_text())
    key = autotune.plan_key(f"short_conv_k{K}", (B, L, D), jnp.float32)
    assert key in plans
    plan = plans[key]
    assert set(plan) == {"block_l", "block_d"}

    # load mode (fresh in-memory cache) returns the persisted plan — and
    # never times candidates
    _set_mode(monkeypatch, "load")
    loaded = autotune.plan_for(
        f"short_conv_k{K}", (B, L, D), jnp.float32,
        candidates=[{"block_l": 1, "block_d": 1}],
        run=lambda **kw: (_ for _ in ()).throw(AssertionError("searched")),
    )
    assert loaded == plan

    # plan output == default-tile output
    y_load = ops.short_conv_gate(u, w, use_kernel=True)
    _set_mode(monkeypatch, "off")
    y_off = ops.short_conv_gate(u, w, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(y_search), np.asarray(y_load))
    np.testing.assert_allclose(
        np.asarray(y_load), np.asarray(y_off), rtol=1e-6, atol=1e-6
    )


def test_plan_roundtrip_toeplitz_and_blockfft(plan_env, monkeypatch):
    B, L, D = 2, 48, 8
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((D, L)) / L, jnp.float32)
    gate = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)

    y_t = ops.toeplitz_conv(u, h, None, gate, use_kernel=True)
    y_b = get_conv_backend("blockfft")(u, h, None, gate)

    plans = json.loads(plan_env.read_text())
    kt = autotune.plan_key("toeplitz_gated", (B, L, D), jnp.float32)
    kb = autotune.plan_key("blockfft", (B, L, D), jnp.float32)
    assert kt in plans and kb in plans
    R, S = plans[kb]["factors"]
    from repro.core.fftconv import next_fast_len
    assert R * S == next_fast_len(2 * L - 1)

    _set_mode(monkeypatch, "load")
    y_t2 = ops.toeplitz_conv(u, h, None, gate, use_kernel=True)
    y_b2 = get_conv_backend("blockfft")(u, h, None, gate)
    _set_mode(monkeypatch, "off")
    y_t0 = ops.toeplitz_conv(u, h, None, gate, use_kernel=True)
    y_b0 = get_conv_backend("blockfft")(u, h, None, gate)
    np.testing.assert_array_equal(np.asarray(y_t), np.asarray(y_t2))
    np.testing.assert_allclose(
        np.asarray(y_t2), np.asarray(y_t0), rtol=1e-6, atol=1e-6
    )
    # a different (valid) factor split reassociates the DFT sums — allclose,
    # not bit-equal
    np.testing.assert_allclose(
        np.asarray(y_b2), np.asarray(y_b0), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(y_b), np.asarray(y_b2), rtol=1e-6, atol=1e-6
    )


def test_plan_roundtrip_twolevel(plan_env, monkeypatch):
    """The ``"twolevel"`` plan kind (overlapped two-level FFT conv,
    DESIGN.md §14): search through the registered ``blockfft_overlap``
    backend persists a {factors, overlap, block_d} plan whose (R, S)
    split multiplies to the padded length; load returns it without
    searching; planned output matches the off-mode default schedule."""
    B, L, D = 1, 64, 8
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((D, L)) / L, jnp.float32)
    gate = jnp.asarray(rng.standard_normal((B, L, D)), jnp.float32)

    y_search = get_conv_backend("blockfft_overlap")(u, h, None, gate)

    plans = json.loads(plan_env.read_text())
    key = autotune.plan_key("twolevel", (B, L, D), jnp.float32)
    assert key in plans, sorted(plans)
    plan = plans[key]
    assert set(plan) == {"factors", "overlap", "block_d"}, plan
    R, S = plan["factors"]
    from repro.core.fftconv import next_fast_len
    assert R * S == next_fast_len(2 * L - 1), plan
    assert plan["overlap"] >= 1 and plan["block_d"] >= 1

    # load mode (fresh in-memory cache) reuses the persisted plan
    _set_mode(monkeypatch, "load")
    loaded = autotune.plan_for(
        "twolevel", (B, L, D), jnp.float32,
        candidates=[{"factors": [2, 2], "overlap": 1, "block_d": 1}],
        run=lambda **kw: (_ for _ in ()).throw(AssertionError("searched")),
    )
    assert loaded == plan
    y_load = get_conv_backend("blockfft_overlap")(u, h, None, gate)
    _set_mode(monkeypatch, "off")
    y_off = get_conv_backend("blockfft_overlap")(u, h, None, gate)
    np.testing.assert_array_equal(np.asarray(y_search), np.asarray(y_load))
    # a different factor split reassociates the DFT sums — allclose
    np.testing.assert_allclose(
        np.asarray(y_load), np.asarray(y_off), rtol=1e-4, atol=1e-4
    )


def test_load_mode_never_searches(plan_env, monkeypatch):
    _set_mode(monkeypatch, "load")

    def boom(**kw):
        raise AssertionError("load mode must not time candidates")

    got = autotune.plan_for(
        "short_conv", (1, 32, 8), jnp.float32,
        candidates=[{"block_l": 32, "block_d": 8}], run=boom,
    )
    assert got is None  # missing entry → kernel defaults, no search


def test_off_mode_is_inert(plan_env, monkeypatch):
    _set_mode(monkeypatch, "off")
    got = autotune.plan_for(
        "short_conv", (1, 32, 8), jnp.float32,
        candidates=[{"block_l": 32, "block_d": 8}],
        run=lambda **kw: (_ for _ in ()).throw(AssertionError("ran")),
    )
    assert got is None
    assert not os.path.exists(plan_env)


def test_schema_drifted_plan_falls_back_to_defaults(plan_env, monkeypatch):
    """A valid-JSON plan whose params the kernel doesn't know (renamed key,
    hand edit) must degrade to kernel defaults, not TypeError on the first
    request of that shape — load mode is serving-safe."""
    key = autotune.plan_key("short_conv_k3", (1, 32, 8), jnp.float32)
    plan_env.write_text(json.dumps({key: {"block_rows": 99}}))
    _set_mode(monkeypatch, "load")
    got = autotune.plan_for(
        "short_conv_k3", (1, 32, 8), jnp.float32,
        candidates=[{"block_l": 32, "block_d": 8}], run=lambda **kw: None,
    )
    assert got is None


def test_persist_merges_concurrent_writers(plan_env, monkeypatch):
    """A search must not clobber keys another process persisted after this
    process loaded its in-memory mirror (merge-then-replace, per-key
    last-writer-wins)."""
    _set_mode(monkeypatch, "search")
    autotune.plan_for(
        "a", (1, 2, 3), jnp.float32,
        candidates=[{"x": 1}], run=lambda **kw: None,
    )
    plans = json.loads(plan_env.read_text())
    plans["other-process:key"] = {"y": 2}  # external writer, behind our back
    plan_env.write_text(json.dumps(plans))
    autotune.plan_for(
        "b", (1, 2, 3), jnp.float32,
        candidates=[{"z": 3}], run=lambda **kw: None,
    )
    final = json.loads(plan_env.read_text())
    assert "other-process:key" in final
    assert autotune.plan_key("a", (1, 2, 3), jnp.float32) in final
    assert autotune.plan_key("b", (1, 2, 3), jnp.float32) in final


def test_load_mode_picks_up_plan_file_written_later(plan_env, monkeypatch):
    """A load-mode consumer must see plans an offline searcher writes AFTER
    the consumer's first (missing) lookup — no restart required (the
    in-memory mirror is keyed by the file's stat signature)."""
    _set_mode(monkeypatch, "load")
    kwargs = dict(
        candidates=[{"block_l": 32, "block_d": 8}], run=lambda **kw: None
    )
    assert autotune.plan_for(
        "short_conv_k3", (1, 32, 8), jnp.float32, **kwargs
    ) is None
    key = autotune.plan_key("short_conv_k3", (1, 32, 8), jnp.float32)
    plan_env.write_text(json.dumps({key: {"block_l": 32, "block_d": 8}}))
    got = autotune.plan_for(
        "short_conv_k3", (1, 32, 8), jnp.float32, **kwargs
    )
    assert got == {"block_l": 32, "block_d": 8}


def test_corrupt_plan_file_is_empty(plan_env, monkeypatch):
    plan_env.write_text("{not json")
    _set_mode(monkeypatch, "load")
    got = autotune.plan_for(
        "short_conv", (1, 32, 8), jnp.float32,
        candidates=[{"block_l": 32, "block_d": 8}], run=lambda **kw: None,
    )
    assert got is None


def test_bad_mode_raises(monkeypatch):
    monkeypatch.setenv(autotune.ENV_MODE, "always")
    with pytest.raises(ValueError, match="REPRO_AUTOTUNE"):
        autotune.mode()


def test_search_skips_and_logs_failing_candidates(caplog):
    def run(block):
        if block == 7:
            raise ValueError("block 7 does not divide the shape")
        return jnp.ones(4)

    with caplog.at_level("WARNING", logger="repro.core.autotune"):
        best = autotune.search([{"block": 7}, {"block": 8}], run)
    assert best == {"block": 8}
    assert "skipping candidate {'block': 7}" in caplog.text


def test_search_raises_when_every_candidate_fails():
    def run(block):
        raise ValueError(f"compiler refused block {block}")

    with pytest.raises(RuntimeError, match="all 2 candidates failed") as e:
        autotune.search([{"block": 7}, {"block": 9}], run)
    assert "refused block 9" in str(e.value.__cause__)
