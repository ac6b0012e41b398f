"""ExecutionContext substrate tests: mixed-precision policy wiring (train
step + decode quantum), rule-driven state/cache sharding, and the
long-prompt fft_sp routing threshold (DESIGN.md §9)."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.param import split_params
from repro.common.policy import BF16, FP32, Policy
from repro.configs import get_config
from repro.distributed.execution import SP_TOKENS_PER_CHIP, ExecutionContext
from repro.models import lm
from repro.serve.engine import ServeConfig, ServeEngine, generate

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _setup(arch="hyena-153m", seed=0):
    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(cfg, frontend_len=0, frontend=None)
    params, axes = split_params(lm.init_lm(jax.random.PRNGKey(seed), cfg))
    return cfg, params, axes


# ------------------------------------------------------------- precision

def test_policy_cast_compute_wired_into_context():
    ctx = ExecutionContext(policy=BF16)
    tree = {"w": jnp.ones((2, 2), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
    cast = ctx.cast_compute(tree)
    assert cast["w"].dtype == jnp.bfloat16  # floats cast
    assert cast["i"].dtype == jnp.int32  # ints untouched
    assert ExecutionContext().cast_compute(tree)["w"].dtype == jnp.float32


def test_train_step_applies_policy():
    """The trainer's mixed precision is live, not advertised: an fp32
    policy and a bf16 policy produce measurably different losses from the
    same fp32 master params (bf16 rounds the params in compute), while the
    master params themselves stay fp32 under both."""
    from repro.train import optim as O
    from repro.train.trainer import TrainConfig, init_train_state, make_train_step

    cfg, _, _ = _setup()
    cfg = dataclasses.replace(cfg, vocab_size=32, n_layers=2)
    state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 32, (4, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 32, (4, 16)), jnp.int32),
    }
    losses = {}
    for name, pol in (("fp32", FP32), ("bf16", BF16)):
        tcfg = TrainConfig(optimizer=O.AdamWConfig(warmup_steps=0),
                           remat=False, policy=pol)
        st = jax.tree_util.tree_map(lambda x: x, state)
        new_state, metrics = make_train_step(cfg, tcfg)(st, batch)
        losses[name] = float(metrics["loss"])
        for leaf in jax.tree_util.tree_leaves(new_state["params"]):
            assert leaf.dtype == jnp.float32  # masters stay fp32
    assert np.isfinite(losses["fp32"]) and np.isfinite(losses["bf16"])
    assert losses["fp32"] != losses["bf16"]  # the cast actually happened
    assert abs(losses["fp32"] - losses["bf16"]) < 0.1  # ...and is benign


def test_bf16_vs_fp32_decode_smoke():
    """Policy wiring in the decode quantum: an fp32-policy engine is
    token-identical to the fp32 reference ``generate``; a bf16-policy
    engine on the same fp32 caches really serves bf16-cast weights and
    still produces a full, finite token stream."""
    cfg, params, _ = _setup()
    prompt = np.array([3, 5, 7, 2], np.int32)
    scfg32 = ServeConfig(max_len=24, n_slots=2, cache_dtype=jnp.float32)
    eng = ServeEngine(params, cfg, scfg32)
    rid = eng.submit(prompt, max_new_tokens=4)
    out32 = eng.drain()[rid]
    ref = np.asarray(generate(
        params, cfg, jnp.asarray(prompt)[None], scfg=scfg32,
        max_new_tokens=4,
    )[0])
    assert [int(t) for t in out32] == [int(t) for t in ref]

    scfg_bf16 = dataclasses.replace(scfg32, policy=BF16)
    eng_b = ServeEngine(params, cfg, scfg_bf16)
    # the engine holds policy-cast weights (serving never pays fp32 HBM)
    float_leaves = [
        l for l in jax.tree_util.tree_leaves(eng_b.params)
        if jnp.issubdtype(l.dtype, jnp.floating)
    ]
    assert float_leaves and all(l.dtype == jnp.bfloat16 for l in float_leaves)
    rid_b = eng_b.submit(prompt, max_new_tokens=4)
    out_b = eng_b.drain()[rid_b]
    assert len(out_b) == 4
    # bf16 engine matches the bf16-policy reference token-for-token
    ref_b = np.asarray(generate(
        params, cfg, jnp.asarray(prompt)[None], scfg=scfg_bf16,
        max_new_tokens=4,
    )[0])
    assert [int(t) for t in out_b] == [int(t) for t in ref_b]


# ------------------------------------------------------ sharding substrate

def _FakeMesh():
    # AbstractMesh: NamedSharding-compatible without real devices
    from jax.sharding import AbstractMesh

    return AbstractMesh((2, 2), ("data", "model"))


def test_train_state_shardings_generalize_params_rules():
    """Adam moments mirror the param layout; counters replicate — the
    arbitrary-state-tree generalization of the params-only rule engine."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import train_state_shardings

    axes = {"w": ("embed", "mlp")}
    state = {
        "params": {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)},
        "opt": {
            "m": {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)},
            "v": {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)},
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        },
    }
    sh = train_state_shardings(axes, state, _FakeMesh())
    assert sh["params"]["w"].spec == P(None, "model")
    assert sh["opt"]["m"]["w"].spec == P(None, "model")
    assert sh["opt"]["v"]["w"].spec == P(None, "model")
    assert sh["opt"]["step"].spec == P()


def test_tree_shardings_partial_axes_replicate():
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import tree_shardings

    values = {
        "a": jax.ShapeDtypeStruct((4, 16), jnp.float32),
        "b": {"c": jax.ShapeDtypeStruct((3,), jnp.int32)},
    }
    sh = tree_shardings({"a": (None, "mlp")}, values, _FakeMesh())
    assert sh["a"].spec == P(None, "model")
    assert sh["b"]["c"].spec == P()  # unannotated subtree replicates
    # structure mismatch (leaf annotation over a subtree) degrades to
    # replication rather than crashing
    sh2 = tree_shardings({"b": ("mlp",)}, values, _FakeMesh())
    assert sh2["b"]["c"].spec == P()
    assert sh2["a"].spec == P()


def test_cache_shardings_rule_driven():
    """lm.cache_shardings resolves every mixer's cache_shard_axes through
    the rule engine: channel dims on 'model', slot dims on 'data',
    cursors and scan-stack dims replicated."""
    from jax.sharding import PartitionSpec as P

    cfg, _, _ = _setup()
    caches = jax.eval_shape(lambda: lm.init_caches(cfg, 2, 16, jnp.float32))
    sh = lm.cache_shardings(cfg, caches, _FakeMesh())
    g0 = sh["groups"][0]
    # stacked hyena "long": (G, N, S, max_len, D) -> slots on data, D on
    # model, operand-history time replicated (kv_seq finds no free axis)
    assert g0["long"].spec == P(None, None, "data", None, "model")
    assert g0["t"].spec == P()  # cursors replicate
    assert g0["short"].spec == P(None, "data", None, "model")


def test_kv_seq_fallback_shards_long_rings():
    """Production GQA regression: 8 KV heads can't divide a 16-way model
    axis, so the batch-1 500K-token KV ring must shard its time dim over
    the leftover data+model axes (the old heuristic's behavior) instead of
    replicating 2 GB/layer per chip; when heads DO divide, they keep the
    model axis (collective-free decode contraction) and the time dim takes
    only the data axes the idle batch dim left behind."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import resolve_spec

    spec = ("cache_slots", "kv_seq", "heads", None)
    shape = (1, 524288, 8, 64)

    class Pod:
        shape = {"data": 16, "model": 16}

    assert resolve_spec(spec, shape, Pod()) == P(None, ("data", "model"))

    class Pod8:
        shape = {"data": 32, "model": 8}

    assert resolve_spec(spec, shape, Pod8()) == P(None, "data", "model")
    # big-batch decode: the batch dim claims the data axes first
    assert resolve_spec(spec, (128, 32768, 8, 64), Pod8()) == P(
        "data", None, "model"
    )


# ----------------------------------------------------- long-prompt routing

def test_sp_threshold_and_routing(monkeypatch):
    """conv_backend_for: fft_sp past the per-mesh threshold (auto =
    SP_TOKENS_PER_CHIP × model size), the configured backend below it,
    0 = disabled; an explicitly configured backend is never silently
    overridden unless sp_min_len opts back in.  Non-divisible L routes
    too — spconv pads to the next multiple internally (DESIGN.md §12)."""
    from repro.distributed.execution import SP_ENV_VAR

    class Mesh8:
        shape = {"model": 8}

    ctx = ExecutionContext(mesh=Mesh8())
    auto = SP_TOKENS_PER_CHIP * 8
    assert ctx.sp_threshold() == auto
    assert ctx.conv_backend_for(auto) == "fft_sp"
    assert ctx.conv_backend_for(auto - 8) is None  # below threshold
    assert ctx.conv_backend_for(auto + 1) == "fft_sp"  # pads internally
    # explicit sp_min_len opts a configured backend into routing
    ctx2 = ExecutionContext(mesh=Mesh8(), sp_min_len=64,
                            conv_backend="blockfft")
    assert ctx2.conv_backend_for(64) == "fft_sp"
    assert ctx2.conv_backend_for(56) == "blockfft"
    # ...but an explicit backend alone (e.g. $REPRO_CONV_BACKEND through
    # the dry-run) is respected at every length
    ctx3 = ExecutionContext(mesh=Mesh8(), conv_backend="blockfft")
    assert ctx3.conv_backend_for(auto) == "blockfft"
    assert ExecutionContext(mesh=Mesh8(), sp_min_len=0).conv_backend_for(
        1 << 20) is None  # routing disabled
    assert ExecutionContext().conv_backend_for(1 << 20) is None  # no mesh
    # env override of the auto threshold (explicit field still wins)
    monkeypatch.setenv(SP_ENV_VAR, "128")
    assert ExecutionContext(mesh=Mesh8()).sp_threshold() == 128
    assert ExecutionContext(mesh=Mesh8(), sp_min_len=64).sp_threshold() == 64
    monkeypatch.setenv(SP_ENV_VAR, "0")
    assert ExecutionContext(mesh=Mesh8()).sp_threshold() is None
    monkeypatch.delenv(SP_ENV_VAR)

    class NoModel:
        shape = {"data": 8}

    assert ExecutionContext(mesh=NoModel()).sp_threshold() is None


def test_fft_sp_prefill_routing_end_to_end():
    """A hyena prefill whose L crosses the threshold really runs through
    the sequence-parallel conv — and its logits match the default fft
    path (8 forced host devices, subprocess)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    code = textwrap.dedent("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.common.param import split_params
        from repro.configs import get_config
        from repro.distributed import ctx as dctx
        from repro.distributed.execution import ExecutionContext
        from repro.models import lm

        cfg = get_config("hyena-153m").reduced()
        cfg = dataclasses.replace(cfg, frontend_len=0, frontend=None)
        params, _ = split_params(lm.init_lm(jax.random.PRNGKey(0), cfg))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                                    cfg.vocab_size)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        routed = ExecutionContext(mesh=mesh, sp_min_len=16)
        assert routed.conv_backend_for(16) == "fft_sp"
        lg1, _ = lm.prefill(params, cfg, prompt, 24, dtype=jnp.float32,
                            compute_dtype=jnp.float32)
        with dctx.use_mesh(mesh):
            lg2, _ = lm.prefill(params, cfg, prompt, 24, dtype=jnp.float32,
                                compute_dtype=jnp.float32, ctx=routed)
        np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-4000:]}"
    assert "OK" in proc.stdout
