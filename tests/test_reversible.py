"""Reversible-block training substrate tests (DESIGN.md §15).

What "grad parity" means here: the reversible dual-stream net is a
*different function* from the standard single-stream stack (the streams
diverge after the first coupling), so the contract under test is that the
reconstruct-and-recompute ``custom_vjp`` produces the same gradients as
plain autodiff of the *identical reversible wiring*
(``reversible.reference_vjp()``), per mixer, with and without ``cp_axis``.

Tolerance story: the forward primal is the same computation either way, so
losses agree to fp32 noise.  Gradients additionally carry the stream
*reconstruction* error ``(a + b) - b``, amplified by the inverse chain's
conditioning — at an O(1)-magnitude residual stream (embeddings scaled to
unit RMS, as in any trained model) fp32 parity lands near 1e-5 and the
tests pin 1e-3.  Under bf16 the streams still ride in fp32 (see
reversible.py), so the reconstructed stream rounds back to the
bit-identical bf16 branch input and bf16 parity is *tighter* than fp32
(exact on CPU; 5e-3 documented envelope).  At a *badly* conditioned point
(raw tiny-init embeddings, first-block gain ~100) fp32 parity degrades to
~1e-3 — that is inverse conditioning, not a VJP defect, and it is why the
suite evaluates at the well-scaled point.

Multi-device cases run in subprocesses with
``--xla_force_host_platform_device_count=8`` (same idiom as
test_cp_train.py) so the main process keeps seeing one device.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.policy import BF16, FP32
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.distributed.execution import ExecutionContext
from repro.models import lm
from repro.models import reversible as REV
from repro.train import ft
from repro.train import optim as O
from repro.train import trainer as T
from repro.train.loop import LoopConfig, TrainLoop

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-4000:]}"
    return proc.stdout


def small_cfg(mixer, **kw):
    base = dict(
        name=f"rev-{mixer}", family="test",
        n_layers=4, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=64, vocab_size=64, pattern=(mixer,), local_window=8,
        ssm_state=16, ssd_head_dim=16, rnn_width=32,
        hyena_filter_width=16, hyena_pos_dim=9,
        hyena_se_len=4, hyena_mr_support=8,
    )
    base.update(kw)
    return ModelConfig(**base)


def well_scaled_params(cfg, seed=0):
    """Init params, then scale the embedding table so the residual stream
    enters the stack at O(1) RMS — the well-conditioned point for checking
    the reconstruction VJP (see module docstring)."""
    state, axes = T.init_train_state(jax.random.PRNGKey(seed), cfg)
    params = state["params"]
    params["embed"]["table"] = params["embed"]["table"] * 16.0
    return params, axes


def grad_parity(cfg, tcfg, batch, params):
    """(dloss, worst per-leaf rel grad err) between the custom VJP and
    plain autodiff of the same reversible wiring."""
    ctx = tcfg.apply_context()
    loss = lambda p: T._loss(p, cfg, tcfg, ctx, batch)
    (l_cust, m), g_cust = jax.value_and_grad(loss, has_aux=True)(params)
    with REV.reference_vjp():
        (l_ref, _), g_ref = jax.value_and_grad(loss, has_aux=True)(params)
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(g_cust),
                    jax.tree_util.tree_leaves(g_ref)):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        scale = max(np.abs(b).max(), 1e-6)
        worst = max(worst, float(np.abs(a - b).max() / scale))
    return abs(float(l_cust) - float(l_ref)), worst, m


MIXERS = [
    "attention", "local_attention", "hyena", "ssd", "rglru",
    "hyena_se", "hyena_mr", "hyena_li",
]


# ------------------------------------------------- per-mixer VJP parity

@pytest.mark.parametrize("mixer", MIXERS)
def test_reversible_vjp_matches_autodiff_fp32(mixer):
    """All five base mixers + the SE/MR/LI hyena variants: the scan-level
    custom_vjp (invert → recompute → pull back) matches plain autodiff of
    the identical coupling at fp32."""
    cfg = small_cfg(mixer)
    params, _ = well_scaled_params(cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64),
    }
    tcfg = T.TrainConfig(remat=False, policy=FP32, reversible=True)
    dl, worst, _ = grad_parity(cfg, tcfg, batch, params)
    assert dl < 1e-5, f"{mixer}: dloss={dl:.2e}"
    assert worst < 1e-3, f"{mixer}: grad_rel={worst:.2e}"


def test_reversible_vjp_bf16_documented_tolerance():
    """bf16 envelope (documented in DESIGN.md §15): the dual streams ride
    in fp32, so the reconstructed stream re-rounds to the *bit-identical*
    bf16 branch input and recompute noise does not compound — in practice
    parity is exact on CPU; 5e-3 is the documented envelope (fusion-order
    differences across compilers may break bitwise identity)."""
    cfg = small_cfg("hyena")
    params, _ = well_scaled_params(cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64),
    }
    tcfg = T.TrainConfig(remat=False, policy=BF16, reversible=True)
    dl, worst, _ = grad_parity(cfg, tcfg, batch, params)
    assert dl < 1e-3, f"dloss={dl:.2e}"
    assert worst < 5e-3, f"grad_rel={worst:.2e}"


def test_reversible_vjp_moe_aux_losses_survive():
    """MoE channel mixers inside the coupling: router aux losses are scan
    outputs of the reversible forward and their cotangents feed the
    per-group recompute — parity must hold on *router* grads too, and the
    aux metrics must be live (nonzero) and equal across VJP modes."""
    cfg = small_cfg(
        "hyena", moe=True, n_experts=4, top_k=2, d_ff=64,
        pattern=("hyena", "attention"),
    )
    params, _ = well_scaled_params(cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, 64),
    }
    tcfg = T.TrainConfig(remat=False, policy=FP32, reversible=True)
    dl, worst, metrics = grad_parity(cfg, tcfg, batch, params)
    assert dl < 1e-5, f"dloss={dl:.2e}"
    assert worst < 1e-3, f"grad_rel={worst:.2e}"
    assert float(metrics["moe_load_balance"]) > 0.0


def test_reversible_vjp_multihybrid_hyena_mh_small():
    """Acceptance row: the registry ``hyena-mh-small`` SE-MR-LI-attn
    pattern (reduced dims, full 4-way pattern) through the reversible path
    at fp32."""
    cfg = dataclasses.replace(
        get_config("hyena-mh-small").reduced(),
        vocab_size=64, hyena_se_len=4, hyena_mr_support=8,
    )
    assert cfg.pattern == ("hyena_se", "hyena_mr", "hyena_li", "attention")
    params, _ = well_scaled_params(cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64),
    }
    tcfg = T.TrainConfig(remat=False, policy=FP32, reversible=True)
    dl, worst, _ = grad_parity(cfg, tcfg, batch, params)
    assert dl < 1e-5, f"dloss={dl:.2e}"
    assert worst < 1e-3, f"grad_rel={worst:.2e}"


# --------------------------------------------------- e2e + composition

def test_reversible_full_train_step_composes():
    """End-to-end make_train_step on the reversible path: microbatches,
    MoE aux in the metrics, finite loss, params move.  remat=True is the
    TrainConfig default — the reversible branch must simply bypass it."""
    cfg = small_cfg(
        "hyena", moe=True, n_experts=4, top_k=2,
        pattern=("hyena", "attention"),
    )
    tcfg = T.TrainConfig(
        optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0),
        remat=True, policy=FP32, reversible=True, microbatches=2,
    )
    state, _ = T.init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
    step = T.jit_train_step(cfg, tcfg)
    p0 = np.asarray(jax.tree_util.tree_leaves(state["params"])[0]).copy()
    state, m = step(state, {"tokens": tok})
    state, m = step(state, {"tokens": tok})
    assert np.isfinite(float(m["loss"]))
    assert float(m["moe_load_balance"]) > 0.0
    p1 = np.asarray(jax.tree_util.tree_leaves(state["params"])[0])
    assert np.abs(p1 - p0).max() > 0


def test_reversible_rejects_unroll():
    with pytest.raises(ValueError, match="reversible"):
        ExecutionContext(reversible=True, unroll=True)
    with pytest.raises(ValueError, match="reversible"):
        T.TrainConfig(reversible=True, unroll=True).apply_context()


# ----------------------------------------------- inference invariance

def test_inference_path_ignores_reversible_flag():
    """Training-only transform: prefill logits, populated caches, decode
    logits, and ServeEngine completions are byte-identical whichever way
    the flag is set."""
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = small_cfg("hyena", pattern=("hyena", "attention"))
    state, _ = T.init_train_state(jax.random.PRNGKey(0), cfg)
    params = state["params"]
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)

    ctx_on = ExecutionContext(reversible=True, policy=FP32)
    ctx_off = ExecutionContext(policy=FP32)
    lg_on, caches_on = lm.prefill(
        params, cfg, prompts, 16, dtype=jnp.float32,
        compute_dtype=jnp.float32, ctx=ctx_on,
    )
    lg_off, caches_off = lm.prefill(
        params, cfg, prompts, 16, dtype=jnp.float32,
        compute_dtype=jnp.float32, ctx=ctx_off,
    )
    np.testing.assert_array_equal(np.asarray(lg_on), np.asarray(lg_off))
    for a, b in zip(jax.tree_util.tree_leaves(caches_on),
                    jax.tree_util.tree_leaves(caches_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tok = jnp.argmax(lg_on[:, -1], axis=-1).astype(jnp.int32)
    d_on, _ = lm.decode_step(params, cfg, tok, caches_on,
                             compute_dtype=jnp.float32, ctx=ctx_on)
    d_off, _ = lm.decode_step(params, cfg, tok, caches_off,
                              compute_dtype=jnp.float32, ctx=ctx_off)
    np.testing.assert_array_equal(np.asarray(d_on), np.asarray(d_off))

    # engine path: ectx with the flag set vs. the engine's own default
    # context — resolve_serve_context fills everything else identically
    scfg = ServeConfig(max_len=24, n_slots=2)
    outs = {}
    for name, ectx in (("on", ExecutionContext(reversible=True)),
                       ("off", None)):
        eng = ServeEngine(params, cfg, scfg, ectx=ectx)
        rid = eng.submit(np.asarray(prompts[0]), max_new_tokens=8)
        res = eng.drain()
        outs[name] = list(np.asarray(res[rid]))
    assert outs["on"] == outs["off"]


# --------------------------------------------- checkpoint compatibility

@pytest.mark.parametrize("first,second", [(False, True), (True, False)])
def test_checkpoint_flag_flip_restores_and_continues_bit_identically(
    tmp_path, first, second
):
    """A TrainLoop checkpoint written under one ``reversible`` setting
    restores under the other and continues exactly as a live in-memory
    continuation under that other setting would — param/opt trees are
    identical by construction (proven on the abstract state), so the flag
    is a pure execution choice, never a checkpoint-format choice."""
    cfg = dataclasses.replace(
        get_config("hyena-153m").reduced(),
        vocab_size=32, n_layers=2, d_model=64,
    )
    opt = O.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=6)
    tcfg_a = T.TrainConfig(optimizer=opt, remat=False, policy=FP32,
                           reversible=first)
    tcfg_b = dataclasses.replace(tcfg_a, reversible=second)

    # identical by construction — prove it on the abstract trees
    sa, axa = T.abstract_train_state(cfg, tcfg_a)
    sb, axb = T.abstract_train_state(cfg, tcfg_b)
    assert jax.tree_util.tree_structure(sa) == jax.tree_util.tree_structure(sb)
    for a, b in zip(jax.tree_util.tree_leaves(sa),
                    jax.tree_util.tree_leaves(sb)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert axa == axb

    tok = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 32))
    batch = {"tokens": jnp.asarray(tok)}
    data = lambda s, k: batch  # stateless source: flag flips can't be
    # confounded by loader cursors

    d = str(tmp_path / "ck")
    lcfg_a = LoopConfig(total_steps=4, ckpt_dir=d, ckpt_every=4,
                        log_every=99, heartbeat_interval=None)
    loop_a = TrainLoop(cfg, tcfg_a, lcfg_a,
                       handler=ft.PreemptionHandler(signals=()))
    res_a = loop_a.run(data, key=jax.random.PRNGKey(0))
    assert res_a.status == "done" and res_a.step == 4

    # continue from the on-disk checkpoint under the flipped flag
    lcfg_b = LoopConfig(total_steps=6, ckpt_dir=d, ckpt_every=4,
                        log_every=99, heartbeat_interval=None)
    loop_b = TrainLoop(cfg, tcfg_b, lcfg_b,
                       handler=ft.PreemptionHandler(signals=()))
    res_b = loop_b.run(data, key=jax.random.PRNGKey(0))
    assert res_b.status == "done" and len(res_b.history) == 2

    # reference: the same two steps from the *live* end-of-run-A state
    step_fn = T.jit_train_step(cfg, tcfg_b, donate=False)
    state = res_a.state
    ref_hist = []
    for _ in range(2):
        state, m = step_fn(state, batch)
        ref_hist.append(float(m["loss"]))
    assert res_b.history == ref_hist  # bitwise float equality
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(res_b.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ cp_axis parity

@pytest.mark.slow
def test_reversible_cp_matches_single_device_per_mixer():
    """Nightly matrix: for every mixer, loss AND grads of the reversible
    cp-sharded step (2x4 mesh, cp over 'model') match the single-device
    reversible step under FP32 — the dual-stream carry shards like the
    standard carry and the backward's inverse scan runs under the same
    mesh."""
    mixers = ["attention", "local_attention", "hyena", "ssd", "rglru",
              "hyena_se", "hyena_mr", "hyena_li"]
    out = run_subprocess(f"""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.common.policy import FP32
        from repro.train import optim as O
        from repro.train import trainer as T

        def small_cfg(mixer):
            return ModelConfig(
                name=f"revcp-{{mixer}}", family="test",
                n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                d_ff=64, vocab_size=64, pattern=(mixer,), local_window=8,
                ssm_state=16, ssd_head_dim=16, rnn_width=32,
                hyena_filter_width=16, hyena_pos_dim=9,
                hyena_se_len=4, hyena_mr_support=8,
            )

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        B, L = 8, 32
        for mixer in {mixers!r}:
            cfg = small_cfg(mixer)
            tok = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, 64)
            lab = jax.random.randint(jax.random.PRNGKey(2), (B, L), 0, 64)
            batch = {{"tokens": tok, "labels": lab}}
            tcfg1 = T.TrainConfig(
                optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0),
                remat=False, policy=FP32, reversible=True)
            tcfg2 = dataclasses.replace(tcfg1, cp_axis="model")
            state, axes = T.init_train_state(jax.random.PRNGKey(0), cfg)
            params = state["params"]
            params["embed"]["table"] = params["embed"]["table"] * 16.0

            ctx1 = tcfg1.apply_context()
            (l1, _), g1 = jax.value_and_grad(
                lambda p, b: T._loss(p, cfg, tcfg1, ctx1, b),
                has_aux=True)(params, batch)

            ectx = tcfg2.apply_context(mesh=mesh)
            p2 = jax.device_put(params, ectx.param_shardings(axes, params))
            b2 = {{k: jax.device_put(
                      v, ectx.data_sharding(v.ndim, v.shape[0], v.shape[1]))
                  for k, v in batch.items()}}
            ctx2 = tcfg2.apply_context()
            with ectx.scope():
                (l2, _), g2 = jax.jit(jax.value_and_grad(
                    lambda p, b: T._loss(p, cfg, tcfg2, ctx2, b),
                    has_aux=True))(p2, b2)
                l2 = float(l2)
            dl = abs(float(l1) - l2)
            worst = 0.0
            for a, b in zip(jax.tree_util.tree_leaves(g1),
                            jax.tree_util.tree_leaves(g2)):
                a = np.asarray(a, np.float32)
                b = np.asarray(jax.device_get(b), np.float32)
                scale = max(np.abs(a).max(), 1e-6)
                worst = max(worst, np.abs(a - b).max() / scale)
            assert dl < 1e-4, f"{{mixer}}: dloss={{dl:.2e}}"
            assert worst < 1e-3, f"{{mixer}}: grad_rel={{worst:.2e}}"
            print(f"{{mixer}} dloss={{dl:.2e}} grad_rel={{worst:.2e}} OK")
        print("REV-CP-MIXERS-OK")
    """)
    assert "REV-CP-MIXERS-OK" in out


@pytest.mark.slow
def test_reversible_cp8_multihybrid_and_moe():
    """Acceptance row: 8-way cp_axis runs of (a) the hyena-mh-small
    SE-MR-LI-attn pattern and (b) an MoE pattern, both through the
    reversible path, matching the single-device reversible step — and the
    MoE aux metrics agree, proving the scanned aux cotangent plumbing
    shards cleanly."""
    out = run_subprocess("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.common.policy import FP32
        from repro.train import optim as O
        from repro.train import trainer as T

        cases = {
            "mh": ModelConfig(
                name="revcp-mh", family="test",
                n_layers=4, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                d_ff=64, vocab_size=64,
                pattern=("hyena_se", "hyena_mr", "hyena_li", "attention"),
                local_window=8, hyena_filter_width=16, hyena_pos_dim=9,
                hyena_se_len=4, hyena_mr_support=8,
            ),
            "moe": ModelConfig(
                name="revcp-moe", family="test",
                n_layers=4, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                d_ff=64, vocab_size=64, pattern=("hyena", "attention"),
                local_window=8, hyena_filter_width=16, hyena_pos_dim=9,
                moe=True, n_experts=4, top_k=2,
            ),
        }
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 8), ("data", "model"))
        B, L = 4, 64
        for name, cfg in cases.items():
            tok = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0, 64)
            lab = jax.random.randint(jax.random.PRNGKey(2), (B, L), 0, 64)
            batch = {"tokens": tok, "labels": lab}
            tcfg1 = T.TrainConfig(
                optimizer=O.AdamWConfig(lr=1e-3, warmup_steps=0),
                remat=False, policy=FP32, reversible=True)
            tcfg2 = dataclasses.replace(tcfg1, cp_axis="model")
            state, axes = T.init_train_state(jax.random.PRNGKey(0), cfg)
            params = state["params"]
            params["embed"]["table"] = params["embed"]["table"] * 16.0

            ctx1 = tcfg1.apply_context()
            (l1, m1), g1 = jax.value_and_grad(
                lambda p, b: T._loss(p, cfg, tcfg1, ctx1, b),
                has_aux=True)(params, batch)

            ectx = tcfg2.apply_context(mesh=mesh)
            p2 = jax.device_put(params, ectx.param_shardings(axes, params))
            b2 = {k: jax.device_put(
                      v, ectx.data_sharding(v.ndim, v.shape[0], v.shape[1]))
                  for k, v in batch.items()}
            ctx2 = tcfg2.apply_context()
            with ectx.scope():
                (l2, m2), g2 = jax.jit(jax.value_and_grad(
                    lambda p, b: T._loss(p, cfg, tcfg2, ctx2, b),
                    has_aux=True))(p2, b2)
                l2 = float(l2)
                m2 = {k: float(v) for k, v in m2.items()}
            dl = abs(float(l1) - l2)
            worst = 0.0
            for a, b in zip(jax.tree_util.tree_leaves(g1),
                            jax.tree_util.tree_leaves(g2)):
                a = np.asarray(a, np.float32)
                b = np.asarray(jax.device_get(b), np.float32)
                scale = max(np.abs(a).max(), 1e-6)
                worst = max(worst, np.abs(a - b).max() / scale)
            assert dl < 1e-4, f"{name}: dloss={dl:.2e}"
            assert worst < 1e-3, f"{name}: grad_rel={worst:.2e}"
            if name == "moe":
                assert m2["moe_load_balance"] > 0.0
                assert abs(m2["moe_load_balance"]
                           - float(m1["moe_load_balance"])) < 1e-4
            print(f"{name} dloss={dl:.2e} grad_rel={worst:.2e} OK")
        print("REV-CP8-OK")
    """)
    assert "REV-CP8-OK" in out


# ------------------------------------------------------ memory evidence

@pytest.mark.slow
def test_reversible_peak_memory_below_standard_at_depth():
    """The point of the substrate: at depth 16 the reversible step's XLA
    buffer-assignment peak (temp bytes) undercuts the standard remat step
    at the same config — depth-resident saves are gone."""
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.common.policy import FP32
        from repro.train import optim as O
        from repro.train import trainer as T

        cfg = ModelConfig(
            name="rev-peak", family="test",
            n_layers=16, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            d_ff=128, vocab_size=128, pattern=("hyena", "attention"),
            local_window=32, hyena_filter_width=16, hyena_pos_dim=9,
        )
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, 2048), 0, 128)
        opt = O.AdamWConfig(lr=1e-3, warmup_steps=0)

        def peak(tcfg):
            state, _ = T.init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
            step = jax.jit(T.make_train_step(cfg, tcfg))
            compiled = step.lower(state, {"tokens": tok}).compile()
            return int(compiled.memory_analysis().temp_size_in_bytes)

        p_std = peak(T.TrainConfig(optimizer=opt, remat=True, policy=FP32))
        p_rev = peak(T.TrainConfig(optimizer=opt, remat=True, policy=FP32,
                                   reversible=True))
        print(f"peak standard={p_std} reversible={p_rev}"
              f" ratio={p_std/max(p_rev,1):.2f}")
        assert p_rev < p_std, (p_rev, p_std)
        print("REV-PEAK-OK")
    """, devices=1)
    assert "REV-PEAK-OK" in out
