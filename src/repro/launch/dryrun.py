import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, prove it fits, and extract the roofline inputs.

The two lines above MUST stay the first statements in this module: jax locks
the platform device count at first initialization, and the dry-run needs 512
placeholder host devices to build the 2x16x16 multi-pod mesh.  This module is
the ONLY place that flag is set — smoke tests and benchmarks see 1 device.

Per cell this script:
  1. builds the arch config (bf16 compute / f32 params, full remat + scan),
  2. resolves parameter/optimizer/input NamedShardings via the logical rule
     engine (distributed/sharding.py),
  3. ``jax.jit(step).lower(...)`` with ShapeDtypeStruct inputs (no allocation)
     and ``.compile()`` on the single-pod (16,16) mesh and the multi-pod
     (2,16,16) mesh,
  4. records memory_analysis / cost_analysis / per-collective byte counts
     parsed from the post-SPMD optimized HLO into a JSON artifact (the
     benchmark's roofline, of the eigensolver's SpMV, is bench/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out artifacts/dryrun
"""

import argparse
import dataclasses
import json
import re
import sys
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp


_COLL_RE = re.compile(
    r"(?P<dtype>[a-z0-9]+)\[(?P<dims>[0-9,]*)\][^ ]* "
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum result-buffer bytes of every collective in the optimized HLO.

    Ring-algorithm wire factors: all-reduce moves ~2x its buffer
    (reduce-scatter + all-gather phases); the others ~1x.  This is the
    collective-term numerator of EXPERIMENTS.md §Roofline.
    """
    per_op: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        if op.endswith("-start"):
            op = op[: -len("-start")]
        dims = [int(d) for d in m.group("dims").split(",") if d]
        nbytes = _DTYPE_BYTES.get(m.group("dtype"), 4)
        for d in dims:
            nbytes *= d
        factor = 2.0 if op == "all-reduce" else 1.0
        per_op[op] = per_op.get(op, 0.0) + nbytes * factor
        counts[op] = counts.get(op, 0) + 1
    return {"bytes_by_op": per_op, "counts": counts, "total_bytes": sum(per_op.values())}


def cpu_upcast_artifact_bytes(hlo_text: str) -> int:
    """Estimate XLA-CPU bf16->f32 canonicalization artifacts.

    The CPU backend has no native bf16 dot: it upcasts operands to f32, and
    its loop-invariant hoisting then materializes whole-stack f32 copies of
    bf16 buffers (saved activation stacks, stacked parameters) that would
    never exist on TPU (native bf16 MXU).  Heuristic: any >=100 MB buffer
    whose exact dims appear in the module in BOTH bf16 and f32 with ndim>=3
    counts its f32 size once.  Reported alongside raw temp so the roofline
    table can show a TPU-adjusted footprint (see EXPERIMENTS.md SDry-run).
    """
    dims_by_dtype = {}
    for m in re.finditer(r"\b(bf16|f32)\[([0-9,]+)\]", hlo_text):
        dims_by_dtype.setdefault(m.group(2), set()).add(m.group(1))
    total = 0
    for dims, dtypes in dims_by_dtype.items():
        if {"bf16", "f32"} <= dtypes:
            parts = [int(d) for d in dims.split(",")]
            if len(parts) >= 3:
                n = 4
                for d in parts:
                    n *= d
                if n >= 100 * 2**20:
                    total += n
    return total


def accum_steps_for(cfg, shape, optimizer: str = "adamw") -> int:
    """Gradient-accumulation microbatching for the big archs: the remat-saved
    activation stack scales with the per-step microbatch, so models with
    L x d_model beyond ~200k split the global batch (standard practice —
    global batch semantics unchanged).

    The adafactor/bf16 giants (arctic-480b) accumulate in bf16 (the f32
    gradient-sum tree would cost params x 4 B/device ~ 7.5 GiB)."""
    if shape.mode != "train":
        return 1
    if optimizer == "adafactor":
        return 4
    score = cfg.n_layers * cfg.d_model
    if score >= 400_000:  # qwen2-vl-72b (80x8192), qwen1.5-32b (64x5120)
        return 8
    if score >= 200_000:  # phi3 (40x5120)
        return 4
    return 1


def estimate_param_count(cfg) -> int:
    """Rough parameter count (embedding + blocks), for optimizer selection."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_padded
    hd = cfg.hd
    attn = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + cfg.n_heads * hd * d
    mlp = 3 * d * f
    if cfg.n_experts:
        mlp = cfg.n_experts * 3 * d * f + (3 * d * f if cfg.dense_residual else 0)
    if cfg.family == "ssm":
        di = cfg.d_inner
        per_layer = d * (2 * di + 2 * cfg.ssm_state + cfg.n_ssm_heads) + di * d
    elif cfg.family == "hybrid_rglru":
        w = cfg.lru_width or d
        per_layer = (
            (2 * d * w + w * d + 2 * w * w + mlp + attn) // len(cfg.block_pattern or (1, 1, 1)) * 1
        )
        per_layer = (2 * (2 * d * w + w * d + 2 * w * w + 3 * d * f) + (attn + 3 * d * f)) // 3
    else:
        per_layer = attn + mlp
    emb = v * d * (1 if cfg.tie_embeddings else 2)
    layers = cfg.n_layers + cfg.n_enc_layers
    return int(emb + layers * per_layer)


def plan_cell(cfg, shape, num_devices: int, hbm_per_chip: int = 16 * 2**30):
    """Production planning: optimizer + param dtype for this cell.

    AdamW keeps f32 params + f32 m/v (12 B/param).  When that exceeds ~60%
    of pod HBM (leaving room for activations), switch to bf16 params +
    Adafactor factored second moment (~2.1 B/param) — the arctic-480b case.
    Serving always uses bf16 params.
    """
    n_params = estimate_param_count(cfg)
    if shape.mode != "train":
        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
        if shape.mode == "decode" and cfg.family not in ("ssm",):
            # KV bytes at bf16; quantize to int8 when the pod share is large
            w = min(shape.seq_len, cfg.window or shape.seq_len)
            kv_bytes = 2 * cfg.n_layers * shape.global_batch * w * cfg.n_kv_heads * cfg.hd * 2
            if kv_bytes / num_devices > 4 * 2**30:
                cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
        return cfg, "none", n_params
    adamw_bytes = 12 * n_params
    if adamw_bytes > 0.6 * num_devices * hbm_per_chip:
        return dataclasses.replace(cfg, param_dtype=jnp.bfloat16), "adafactor", n_params
    return cfg, "adamw", n_params


def _build_cell(arch: str, shape_name: str):
    from repro.configs import SHAPES, get_config, input_specs

    cfg = get_config(arch)
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.bfloat16, remat="full", scan_layers=True)
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape)
    return cfg, shape, specs


def lower_cell(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True) -> Dict:
    from functools import partial

    from repro.configs import SHAPES
    from repro.distributed.sharding import sharding_ctx, tree_shardings
    from repro.launch.mesh import make_production_mesh
    from repro.models.common import split_tree
    from repro.models.model import decode_state_axes, decode_step, init_model, prefill
    from repro.training import TrainConfig, make_train_step
    from repro.training.optimizer import OptState

    cfg, shape, specs = _build_cell(arch, shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg, optimizer, n_params = plan_cell(cfg, shape, mesh.size)
    if shape.mode == "decode":  # state template must match the planned dtype
        from repro.configs import input_specs as _ispecs

        specs = _ispecs(cfg, shape)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": f"{dict(mesh.shape)}",
        "num_devices": mesh.size, "mode": shape.mode, "optimizer": optimizer,
        "est_params": n_params,
    }
    t0 = time.perf_counter()

    with sharding_ctx(mesh):
        # abstract params + their shardings (no allocation: eval_shape)
        ptree = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
        params_sds, axes = split_tree(ptree)
        p_shard = tree_shardings(params_sds, axes)
        batch_axes = {
            "tokens": ("batch", None),
            "labels": ("batch", None),
            "frames": ("batch", None, None),
            "positions": (None, "batch", None),
        }

        if shape.mode == "train":
            if optimizer == "adafactor":
                from repro.training.optimizer import FactoredState

                f32sds = lambda shp: jax.ShapeDtypeStruct(shp, jnp.float32)
                opt_sds = FactoredState(
                    step=jax.ShapeDtypeStruct((), jnp.int32),
                    vr=jax.tree.map(
                        lambda p: f32sds(p.shape[:-1] if len(p.shape) >= 2 else p.shape),
                        params_sds,
                    ),
                    vc=jax.tree.map(
                        lambda p: f32sds(p.shape[:-2] + p.shape[-1:] if len(p.shape) >= 2 else ()),
                        params_sds,
                    ),
                )
                slice_axes = lambda sel: jax.tree_util.tree_map(
                    lambda a: sel(a), axes, is_leaf=lambda x: isinstance(x, tuple)
                )
                vr_axes = slice_axes(lambda a: a[:-1] if len(a) >= 2 else a)
                vc_axes = slice_axes(lambda a: a[:-2] + a[-1:] if len(a) >= 2 else ())
                o_shard = FactoredState(
                    step=None,
                    vr=tree_shardings(opt_sds.vr, vr_axes),
                    vc=tree_shardings(opt_sds.vc, vc_axes),
                )
            else:
                opt_sds = OptState(
                    step=jax.ShapeDtypeStruct((), jnp.int32),
                    m=jax.tree.map(
                        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_sds
                    ),
                    v=jax.tree.map(
                        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_sds
                    ),
                )
                o_shard = OptState(step=None, m=p_shard, v=p_shard)
            b_shard = {k: tree_shardings(v, batch_axes[k]) for k, v in specs.items()}
            accum = accum_steps_for(cfg, shape, optimizer)
            rec["accum_steps"] = accum
            accum_dtype = jnp.bfloat16 if optimizer == "adafactor" else None
            step_fn = make_train_step(
                cfg, TrainConfig(accum_steps=accum, optimizer=optimizer, accum_dtype=accum_dtype)
            )
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_shard, o_shard, b_shard),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_sds, opt_sds, specs)
        elif shape.mode == "prefill":
            b_shard = {k: tree_shardings(v, batch_axes[k]) for k, v in specs.items()}
            fn = partial(prefill, cfg=cfg, max_len=min(shape.seq_len, cfg.window or shape.seq_len))
            jitted = jax.jit(
                lambda params, batch: fn(params=params, batch=batch),
                in_shardings=(p_shard, b_shard),
            )
            lowered = jitted.lower(params_sds, specs)
        else:  # decode
            st_axes = decode_state_axes(cfg)
            st_shard = tree_shardings(specs["state"], st_axes)
            tok_shard = tree_shardings(specs["tokens"], ("batch", None))
            jitted = jax.jit(
                lambda params, state, tokens: decode_step(params, cfg, state, tokens),
                in_shardings=(p_shard, st_shard, tok_shard),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(params_sds, specs["state"], specs["tokens"])

        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)

        mem = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(mem, k)
        }
        cost = compiled.cost_analysis()
        rec["cost_analysis"] = {
            k: float(v)
            for k, v in (cost or {}).items()
            if isinstance(v, (int, float))
            and k
            in (
                "flops",
                "bytes accessed",
                "transcendentals",
                "utilization operand 0 {}",
                "bytes accessed output {}",
            )
        }
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes(hlo)
        rec["cpu_upcast_artifact_bytes"] = cpu_upcast_artifact_bytes(hlo)
        rec["hlo_chars"] = len(hlo)
        del hlo

    if verbose:
        ma = rec["memory_analysis"]
        print(
            f"[{arch} x {shape_name} x {'2pod' if multi_pod else '1pod'}] "
            f"compile={rec['compile_s']}s "
            f"args/device={ma.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
            f"temp/device={ma.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
            f"flops/device={rec['cost_analysis'].get('flops', 0):.3e} "
            f"coll={rec['collectives']['total_bytes']/2**30:.3f}GiB "
            f"cpu_artifacts={rec['cpu_upcast_artifact_bytes']/2**30:.2f}GiB"
        )
        print("  memory_analysis:", rec["memory_analysis"])
        print("  cost_analysis:", rec["cost_analysis"])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str)
    ap.add_argument("--shape", type=str)
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default=None, help="JSON output path or dir (--all)")
    args = ap.parse_args()

    from repro.configs import ARCHS, SHAPES, applicable

    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES if applicable(a, s)]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    results = []
    failures = []
    for arch, shp in cells:
        recs = []
        try:
            if not args.multi_pod_only:
                recs.append(lower_cell(arch, shp, multi_pod=False))
            if not args.single_pod_only:
                recs.append(lower_cell(arch, shp, multi_pod=True))
            results.extend(recs)
        except Exception as e:  # noqa: BLE001 — report and continue
            import traceback

            failures.append((arch, shp, f"{type(e).__name__}: {e}"))
            traceback.print_exc()
    if args.out:
        if args.all or len(results) > 1:
            os.makedirs(args.out, exist_ok=True)
            for rec in results:
                tag = "2pod" if rec["num_devices"] == 512 else "1pod"
                path = os.path.join(args.out, f"{rec['arch']}__{rec['shape']}__{tag}.json")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
        else:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    if failures:
        print("FAILURES:")
        for a, s, e in failures:
            print(f"  {a} x {s}: {e}")
        sys.exit(1)
    print(f"dry-run OK: {len(results)} compilations")


if __name__ == "__main__":
    main()
