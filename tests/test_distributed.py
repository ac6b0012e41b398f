"""Distributed-runtime tests.  Multi-device cases run in subprocesses with
``--xla_force_host_platform_device_count=8`` so the main test process (and
the smoke tests) keep seeing exactly 1 device."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-4000:]}"
    return proc.stdout


# -------------------------------------------------------- sharding rules

def test_param_sharding_rules_divisibility():
    """40 experts / 40 heads don't divide 16 → replicated fallback; mlp &
    vocab shard; fsdp puts embed dims on data axes."""
    from repro.distributed.sharding import resolve_spec
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))

    # mesh sizes are 1 here, so craft a fake mesh-shape via a real mesh of
    # the production shape is impossible in-process; use the rule engine's
    # divisibility math directly with a mocked mesh-shape mapping.
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    assert resolve_spec(("embed", "mlp"), (1024, 13824), FakeMesh()) == P(None, "model")
    assert resolve_spec(("experts", "embed", "expert_ff"), (40, 1536, 512), FakeMesh()) == P(None, None, "model")
    assert resolve_spec(("experts", "embed", "expert_ff"), (16, 6144, 10752), FakeMesh()) == P("model")
    assert resolve_spec(("vocab", "embed"), (92553, 2048), FakeMesh()) == P()
    assert resolve_spec(("vocab", "embed"), (152064, 8192), FakeMesh(),
                        fsdp=True) == P("model", "data")
    # stacked-layer leading dim is replicated
    assert resolve_spec(("embed", "mlp"), (18, 768, 1536), FakeMesh(),
                        extra_leading=1) == P(None, None, "model")


def test_shard_constraint_noop_without_mesh():
    from repro.distributed.ctx import shard

    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(shard(x, "data", None), x)


def test_data_alias_expands_to_pod():
    from repro.distributed.ctx import _filter_spec
    from jax.sharding import PartitionSpec as P

    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}

    spec = _filter_spec(FakeMesh(), (256, 128), ("data", None))
    assert spec == P(("pod", "data"), None)


# ------------------------------------------------- multi-device (subproc)

def test_sharded_train_step_matches_single_device():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import ctx
        from repro.distributed.sharding import param_shardings
        from repro.common.param import split_params
        from repro.models import lm
        from repro.train import optim as O
        from repro.train.trainer import TrainConfig, init_train_state, make_train_step

        cfg = get_config("hyena-153m").reduced()
        cfg = dataclasses.replace(cfg, vocab_size=64, n_layers=2)
        tcfg = TrainConfig(optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0),
                           remat=False)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, 64)
        batch = {"tokens": tokens, "labels": labels}

        # single device
        state, axes = init_train_state(jax.random.PRNGKey(0), cfg)
        s1, m1 = make_train_step(cfg, tcfg)(state, batch)

        # 2x4 mesh
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        pshard = param_shardings(axes, state["params"], mesh, fsdp=True)
        state2, _ = init_train_state(jax.random.PRNGKey(0), cfg)
        state2 = {
            "params": jax.device_put(state2["params"], pshard),
            "opt": {
                "m": jax.device_put(state2["opt"]["m"], pshard),
                "v": jax.device_put(state2["opt"]["v"], pshard),
                "step": jax.device_put(state2["opt"]["step"],
                                       NamedSharding(mesh, P())),
            },
        }
        bshard = {k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
                  for k, v in batch.items()}
        with ctx.use_mesh(mesh):
            s2, m2 = jax.jit(make_train_step(cfg, tcfg))(state2, bshard)
        print("loss1", float(m1["loss"]), "loss2", float(m2["loss"]))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2
        lr = 1e-3
        for a, b in zip(jax.tree_util.tree_leaves(s1["params"]),
                        jax.tree_util.tree_leaves(s2["params"])):
            d = np.abs(np.asarray(a, np.float32) - np.asarray(jax.device_get(b), np.float32))
            scale = max(np.abs(np.asarray(a, np.float32)).max(), 1e-3)
            # Adam step 1 moves every param by exactly +-lr*(1+eps'); two
            # topologies may disagree by 2*lr where bf16 noise flips the
            # gradient sign near zero. Anything beyond that is a real bug.
            assert d.max() <= 2.2 * lr + 5e-2 * scale, d.max()
        print("OK")
    """)
    assert "OK" in out


def test_sp_fft_conv_matches_reference():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.fftconv import fft_causal_conv
        from repro.distributed.spconv import sp_fft_causal_conv

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        B, L, D = 2, 64, 4
        u = jax.random.normal(jax.random.PRNGKey(0), (B, L, D))
        h = jax.random.normal(jax.random.PRNGKey(1), (D, L)) / L
        skip = jax.random.normal(jax.random.PRNGKey(2), (D,))
        want = fft_causal_conv(u, h, skip)
        got = sp_fft_causal_conv(u, h, skip, mesh, axis="model")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


def test_pipeline_matches_sequential():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_forward

        S, T, mb, d = 4, 6, 3, 8
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pipe",))
        ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) / np.sqrt(d)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, mb, d))

        def stage(w, h):
            return jnp.tanh(h @ w)

        got = pipeline_forward(stage, ws, x, mesh, axis="pipe")
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        # out_specs=P() declares the result replicated: that must be TRUE
        # on every device, not just on stage 0 (zeros elsewhere used to be
        # masked by check=False and whichever shard assembled the global
        # array).  Check the per-device replicas.
        shards = [np.asarray(s.data) for s in got.addressable_shards]
        assert len(shards) == 4
        for sh in shards:
            np.testing.assert_allclose(sh, np.asarray(ref),
                                       rtol=1e-4, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_compressed_psum_accuracy():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        from repro.distributed.ctx import shard_map

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        def body(xb):
            return compressed_psum(xb, "data")

        fn = shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
        got = fn(x)[0]
        want = jnp.sum(x, axis=0)
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        amax = np.abs(np.asarray(x)).max()
        assert err <= 8 * (amax / 127.0) + 1e-6, err  # <= n_shards * 1 ulp
        print("OK")
    """)
    assert "OK" in out


def test_compressed_train_step_on_mesh():
    """grad_compression='int8_ef' lowers and runs under SPMD: the sharded
    compressed step tracks the single-device compressed step (residuals
    and all), to all-reduce-order tolerance."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.distributed import ctx
        from repro.train import optim as O
        from repro.train.trainer import TrainConfig, init_train_state, make_train_step

        cfg = get_config("hyena-153m").reduced()
        cfg = dataclasses.replace(cfg, vocab_size=64, n_layers=2)
        tcfg = TrainConfig(optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0),
                           remat=False, grad_compression="int8_ef")
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, 64)
        batch = {"tokens": tokens, "labels": labels}

        state, axes = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        assert "cgrad" in state
        s1, m1 = make_train_step(cfg, tcfg)(state, batch)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        ectx = tcfg.apply_context(mesh=mesh)
        state2, _ = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        shardings = ectx.train_state_shardings(axes, state2)
        # the rule engine places the residuals exactly like the params
        for ps, cs in zip(jax.tree_util.tree_leaves(shardings["params"]),
                          jax.tree_util.tree_leaves(shardings["cgrad"])):
            assert ps.spec == cs.spec, (ps, cs)
        state2 = jax.device_put(state2, shardings)
        bshard = {k: jax.device_put(v, ectx.data_sharding(v.ndim, v.shape[0]))
                  for k, v in batch.items()}
        with ctx.use_mesh(mesh):
            s2, m2 = jax.jit(make_train_step(cfg, tcfg))(state2, bshard)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2
        assert float(m2["compression_abs_err"]) > 0
        lr = 1e-3
        for a, b in zip(jax.tree_util.tree_leaves(s1["params"]),
                        jax.tree_util.tree_leaves(s2["params"])):
            a = np.asarray(a, np.float32)
            b = np.asarray(jax.device_get(b), np.float32)
            scale = max(np.abs(a).max(), 1e-3)
            # same bound as the uncompressed cross-topology test: Adam step
            # 1 is +-lr per element; quantization + reduce-order noise can
            # flip signs near zero but never exceed the 2*lr envelope
            assert np.abs(a - b).max() <= 2.2 * lr + 5e-2 * scale
        # residuals are carried (nonzero) and bounded by one quantization
        # bucket of their gradient leaf on both topologies
        r2 = max(np.abs(np.asarray(jax.device_get(x), np.float32)).max()
                 for x in jax.tree_util.tree_leaves(s2["cgrad"]))
        assert 0 < r2 < 1.0, r2
        print("OK")
    """)
    assert "OK" in out


# ------------------------------------------------------ error feedback

def test_error_feedback_contracts():
    """Residual stays bounded and compressed grads average to the truth."""
    from repro.distributed import compression as C

    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)), jnp.float32)}
    r = C.init_residuals(g)
    acc = jnp.zeros((64,))
    for _ in range(50):
        out, r, diag = C.compress_decompress_with_feedback(g, r)
        acc = acc + out["w"]
    # mean of compressed equals true gradient to quantization precision
    np.testing.assert_allclose(np.asarray(acc / 50), np.asarray(g["w"]),
                               atol=np.abs(g["w"]).max() / 127.0 * 2)


def test_quantize_roundtrip_property():
    import prop
    from repro.distributed import compression as C

    @prop.given(scale=prop.floats(0.01, 100.0))
    def check(scale):
        x = jnp.asarray(
            np.random.default_rng(3).normal(size=(128,)) * scale, jnp.float32
        )
        q, s = C.quantize_int8(x)
        err = np.abs(np.asarray(C.dequantize_int8(q, s)) - np.asarray(x))
        assert err.max() <= float(s) * 0.5 + 1e-7

    check()
