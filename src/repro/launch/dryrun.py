import os
# CPU-only planning tool: the production meshes are 512 virtual host
# devices, and the dry-run never claims an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# test hook: REPRO_DRYRUN_DEVICES overrides the placeholder-device count
# (still before any jax import — jax locks the device count on first init).
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
cell on the production meshes and capture the roofline inputs.

Per cell this produces artifacts/dryrun/<mesh>/<arch>__<shape>.json with:
  * compile proof: memory_analysis (bytes/device), compile wall-time,
  * cost_analysis of the full compiled step (NOTE: XLA counts while-loop
    bodies ONCE — verified empirically — so scanned layer stacks undercount;
    we therefore also compile depth-1 and depth-2 *unrolled* probes and
    extrapolate: total = overhead + n_groups × (d2 − d1)),
  * per-collective byte counts parsed from the partitioned HLO (same probe
    extrapolation), split by op kind,
  * MODEL_FLOPS = 6·N·D (6·N_active·D for MoE) for the useful-compute ratio.

Cell policy (DESIGN.md §5): `long_500k` needs sub-quadratic attention —
mamba2/recurrentgemma run natively; pure full-attention archs run the cell
with the paper's drop-in swap (`--mixer hyena`, marked "hyena-swap").
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.common.param import split_params
from repro.configs import get_config
from repro.configs.registry import ASSIGNED
from repro.configs.shapes import SHAPES, token_specs
from repro.core.conv_api import resolve_conv_backend
from repro.distributed import ctx
from repro.distributed.execution import ExecutionContext
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.models.mixer_api import resolve_remat_policy
from repro.train import optim as O
from repro.train.trainer import TrainConfig, abstract_train_state, make_train_step

PAPER_ARCHS = ["hyena-153m", "hyena-1.3b"]  # the paper's own models, extra rows

# ---------------------------------------------------------------- HLO parse

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s+((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)


def _type_bytes(typestr: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(typestr):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by collectives (result-shape bytes, '-done'
    ops excluded by matching '-start'/plain forms only)."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        typestr, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0) + _type_bytes(typestr)
    return out


# ------------------------------------------------------------- param specs

def abstract_params(cfg, serve: bool = False):
    """(ShapeDtypeStruct tree, logical axes tree) without allocation."""
    captured = {}

    def build():
        vals, axes = split_params(lm.init_lm(jax.random.PRNGKey(0), cfg))
        captured["axes"] = axes
        return vals

    vals = jax.eval_shape(build)
    if serve:  # serving holds bf16 weights
        vals = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
            if jnp.issubdtype(s.dtype, jnp.floating) else s,
            vals,
        )
    return vals, captured["axes"]


# Sharding decisions all come from the shared ExecutionContext (rule
# engine in repro.distributed.sharding; decode caches from the mixers'
# cache_shard_axes specs) — this module used to carry its own heuristic
# cache-sharding tree and hand-built optimizer-state shardings.


# ------------------------------------------------------------- cell runner

def model_flops_params(cfg, params_struct) -> Dict[str, float]:
    leaves = jax.tree_util.tree_flatten_with_path(params_struct)[0]
    total = 0
    expert = 0
    embed_like = 0
    for path, leaf in leaves:
        n = int(np.prod(leaf.shape))
        keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        total += n
        if "moe" in keys and "router" not in keys:
            expert += n
        if "embed~table" in keys or keys.startswith("head"):
            embed_like += n
    active = total - expert
    if cfg.moe and cfg.n_experts:
        active += expert * cfg.top_k / cfg.n_experts
    return {"n_params": total, "n_active": active, "n_embed": embed_like}


def _reduced_depth_cfg(cfg, groups: int):
    plen = len(cfg.pattern)
    # keep the tail out of probes: body cost comes from (d2 - d1)
    return dataclasses.replace(cfg, n_layers=plen * groups)


def build_step(cfg, shape_name: str, mesh: Mesh, *, unroll=False, probe_groups=None):
    """Returns (fn, args, in_shardings, donate) ready for jit().lower()."""
    shape = SHAPES[shape_name]
    run_cfg = cfg if probe_groups is None else _reduced_depth_cfg(cfg, probe_groups)
    if shape.kind == "train":
        # conv backend resolved once, against the registry: explicit override
        # > $REPRO_CONV_BACKEND > default — unknown names raise here with the
        # registered list, not mid-lowering.
        tcfg = TrainConfig(
            optimizer=O.AdamWConfig(), remat=True, unroll=unroll,
            conv_backend=resolve_conv_backend(),
            remat_policy=resolve_remat_policy(),
            grad_compression=os.environ.get("REPRO_GRAD_COMPRESSION") or None,
        )
        ectx = tcfg.apply_context(mesh=mesh)
        # the trainer's own state description (incl. compression residuals
        # when enabled) — no hand-built {"m","v","step"} mirror here
        state, axes = abstract_train_state(run_cfg, tcfg)
        state_shard = ectx.train_state_shardings(axes, state)
        specs = token_specs(run_cfg, shape)
        batch = {k: v for k, v in specs.items()}
        batch_shard = {
            k: ectx.data_sharding(v.ndim, v.shape[0])
            for k, v in batch.items()
        }
        step = make_train_step(run_cfg, tcfg)
        return step, (state, batch), (state_shard, batch_shard), (0,)
    if shape.kind == "prefill":
        fwd_ctx = ExecutionContext(
            conv_backend=resolve_conv_backend(), unroll=unroll,
            mesh=mesh, fsdp=True,
        )
        params, axes = abstract_params(run_cfg, serve=True)
        pshard = fwd_ctx.param_shardings(axes, params)
        specs = token_specs(run_cfg, shape)
        batch_shard = {
            k: fwd_ctx.data_sharding(v.ndim, v.shape[0])
            for k, v in specs.items()
        }

        def fwd(params, batch):
            logits, _ = lm.forward(
                params, run_cfg, batch["tokens"],
                batch.get("frontend_embeds"), ctx=fwd_ctx,
            )
            return logits

        return fwd, (params, specs), (pshard, batch_shard), ()
    # decode
    serve_ctx = ExecutionContext(unroll=unroll, mesh=mesh, fsdp=True)
    params, axes = abstract_params(run_cfg, serve=True)
    pshard = serve_ctx.param_shardings(axes, params)
    dspecs = input_specs_decode(run_cfg, shape)
    # rule-driven decode-cache shardings from the mixers' cache_shard_axes
    # specs — the exact layout the mesh-native ServeEngine holds its pool in
    cshard = serve_ctx.cache_shardings(run_cfg, dspecs["caches"])
    tok_shard = serve_ctx.data_sharding(1, shape.batch)

    def serve_fn(params, token, caches):
        return lm.decode_step(params, run_cfg, token, caches, ctx=serve_ctx)

    return (
        serve_fn,
        (params, dspecs["token"], dspecs["caches"]),
        (pshard, tok_shard, cshard),
        (2,),
    )


def input_specs_decode(cfg, shape):
    caches = jax.eval_shape(
        lambda: lm.init_caches(cfg, shape.batch, shape.seq, dtype=jnp.bfloat16)
    )
    return {
        "token": jax.ShapeDtypeStruct((shape.batch,), jnp.int32),
        "caches": caches,
    }


def compile_cell(cfg, shape_name: str, mesh: Mesh, *, unroll=False,
                 probe_groups=None, want_text=True) -> Dict[str, Any]:
    fn, args, shardings, donate = build_step(
        cfg, shape_name, mesh, unroll=unroll, probe_groups=probe_groups
    )
    t0 = time.time()
    with ctx.use_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    dt = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):  # jax<0.5 returns a one-dict list per device
        cost = cost[0] if cost else {}
    out = {
        "compile_s": round(dt, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost_analysis": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
        },
    }
    if want_text:
        out["collectives"] = collective_bytes(compiled.as_text())
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             probes: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    if os.environ.get("REPRO_CAPACITY_FACTOR"):
        cfg = dataclasses.replace(
            cfg, capacity_factor=float(os.environ["REPRO_CAPACITY_FACTOR"])
        )
    shape = SHAPES[shape_name]
    swapped = False
    if shape_name == "long_500k" and not cfg.subquadratic:
        cfg = cfg.with_mixer("hyena")  # the paper's drop-in replacement
        swapped = True
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    plen = len(cfg.pattern)
    n_groups = cfg.n_layers // plen
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.shape.values()),
        "chips": n_chips,
        "hyena_swap": swapped,
        "pattern": list(cfg.pattern),
        "n_layers": cfg.n_layers,
        "status": "ok",
        "conv_backend": resolve_conv_backend(),
        "remat_policy": resolve_remat_policy(),
    }
    params_struct, _ = abstract_params(cfg)
    record.update(model_flops_params(cfg, params_struct))
    # tokens processed by this step (for MODEL_FLOPS = 6·N·D)
    if shape.kind == "train":
        record["tokens_per_step"] = shape.batch * shape.seq
    elif shape.kind == "prefill":
        record["tokens_per_step"] = shape.batch * shape.seq
    else:
        record["tokens_per_step"] = shape.batch
    # 6ND counts fwd+bwd (train); fwd-only steps are 2ND
    nd_factor = 6.0 if shape.kind == "train" else 2.0
    record["model_flops"] = nd_factor * record["n_active"] * record["tokens_per_step"]

    record["full"] = compile_cell(cfg, shape_name, mesh, want_text=True)
    if probes and n_groups >= 2:
        d1 = compile_cell(cfg, shape_name, mesh, unroll=True, probe_groups=1)
        d2 = compile_cell(cfg, shape_name, mesh, unroll=True, probe_groups=2)
        record["probe_d1"] = d1
        record["probe_d2"] = d2

        def extrap(f1, f2):
            if f1 is None or f2 is None:
                return None
            body = f2 - f1
            return f1 + (n_groups - 1) * body

        record["extrapolated"] = {
            "flops": extrap(d1["cost_analysis"]["flops"],
                            d2["cost_analysis"]["flops"]),
            "bytes_accessed": extrap(d1["cost_analysis"]["bytes_accessed"],
                                     d2["cost_analysis"]["bytes_accessed"]),
            "collectives": {
                k: extrap(d1["collectives"].get(k, 0), d2["collectives"].get(k, 0))
                for k in set(d1["collectives"]) | set(d2["collectives"])
            },
        }
    elif probes:
        record["extrapolated"] = {
            "flops": record["full"]["cost_analysis"]["flops"],
            "bytes_accessed": record["full"]["cost_analysis"]["bytes_accessed"],
            "collectives": record["full"].get("collectives", {}),
        }
    return record


def cells_for(archs, shapes):
    for a in archs:
        for s in shapes:
            yield a, s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--paper", action="store_true", help="also run paper archs")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ASSIGNED)
    if args.paper and not args.arch:
        archs += PAPER_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for multi in meshes:
        mesh_tag = "pod2x16x16" if multi else "pod16x16"
        outdir = os.path.join(args.out, mesh_tag)
        os.makedirs(outdir, exist_ok=True)
        for arch, shape in cells_for(archs, shapes):
            path = os.path.join(outdir, f"{arch}__{shape}.json")
            if os.path.exists(path) and not args.force:
                print(f"[skip] {mesh_tag} {arch} {shape}")
                continue
            print(f"[run ] {mesh_tag} {arch} {shape}", flush=True)
            t0 = time.time()
            try:
                rec = run_cell(arch, shape, multi, probes=not args.no_probes)
            except Exception as e:  # record the failure, keep sweeping
                rec = {
                    "arch": arch, "shape": shape, "mesh": mesh_tag,
                    "status": "failed", "error": str(e)[-2000:],
                    "traceback": traceback.format_exc()[-4000:],
                }
            rec["wall_s"] = round(time.time() - t0, 1)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[done] {mesh_tag} {arch} {shape} -> {rec['status']} "
                  f"({rec['wall_s']}s)", flush=True)
            jax.clear_caches()  # keep host RAM flat across the 96-cell sweep


if __name__ == "__main__":
    main()
