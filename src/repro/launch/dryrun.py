import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count at first init.
# This module is the ONLY place the 512 placeholder devices exist — smoke
# tests and benches import through other entry points and see 1 device.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this:
  1. builds the full config (exact published shapes, bf16),
  2. resolves parameter/optimizer/cache/batch shardings from the logical
     rules (ZeRO-1 on optimizer moments),
  3. ``jax.jit(step).lower(ShapeDtypeStructs).compile()`` on the production
     mesh — (16,16) "data","model" single-pod and (2,16,16) "pod","data",
     "model" multi-pod — and records memory_analysis of the deployable
     scanned program,
  4. reconstructs exact per-device FLOPs / bytes / collective-bytes:
     ``cost_analysis`` counts a ``lax.scan`` body ONCE (trip count ignored),
     so we compile shallow *unrolled* depth variants (all-segments-depth-1,
     then each segment at depth 2), solve the linear system for per-layer
     costs, and extrapolate to full depth. Glue (embed/unembed/loss/
     optimizer-of-glue) comes out of the same solve.

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system. Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --all [--skip-existing]
    PYTHONPATH=src python -m repro.launch.dryrun --arch codeqwen1.5-7b \
        --shape train_4k --mesh single
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh, mesh_devices
from repro.models import (
    active_param_count,
    cache_specs,
    init_cache,
    init_params,
    param_count,
    param_specs,
)
from repro.models.common import LayerPattern
from repro.optim import AdamWConfig
from repro.training import init_train_state, make_decode_step, make_train_step
from repro.training.steps import make_prefill_step

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Tuned per-arch rule sets (§Perf): archs whose head counts don't divide the
# 16-way model axis run DP-heavy (batch over both axes, ZeRO/FSDP weight
# gathers) — uniform rules would replicate their attention compute 16×.
ARCH_RULES = {
    "musicgen-medium": "dp",
    "minicpm3-4b": "dp",
    "llava-next-34b": "dp",
}

BATCH_SPECS = {
    "tokens": ("batch", "length"),
    "labels": ("batch", "length"),
    "embeds": ("batch", "length", None),
    "patches": ("batch", None, None),
}


def _batch_shardings(specs: dict, mesh, rules):
    return {
        k: NamedSharding(
            mesh, shd.resolve_spec(BATCH_SPECS[k], v.shape, mesh, rules)
        )
        for k, v in specs.items()
    }


def _per_device_bytes(shardings, shapes) -> int:
    total = 0
    for sh, sd in zip(
        jax.tree_util.tree_leaves(shardings), jax.tree_util.tree_leaves(shapes)
    ):
        shard = sh.shard_shape(sd.shape)
        total += int(np.prod(shard)) * sd.dtype.itemsize
    return total


def _named(tree_of_specs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_of_specs,
        is_leaf=lambda t: isinstance(t, P),
    )


def _build(cfg, shape: str, mesh, rules):
    """Build (jitted_step, lower_args, info) for one cell config."""
    sd = configs.SHAPES[shape]
    in_specs = configs.input_specs(cfg, shape)
    batch_sh = _batch_shardings(in_specs, mesh, rules)
    p_specs = param_specs(cfg)
    info: dict = {}
    if sd.kind == "train":
        state_shapes = jax.eval_shape(
            lambda k: init_train_state(cfg, k), jax.random.PRNGKey(0)
        )
        resolved = shd.tree_specs(p_specs, state_shapes["params"], mesh, rules)
        z1 = shd.zero1_tree(resolved, state_shapes["params"], mesh)
        state_sh = {
            "params": _named(resolved, mesh),
            "opt": {
                "mu": _named(z1, mesh),
                "nu": _named(z1, mesh),
                "step": NamedSharding(mesh, P()),
            },
        }
        jitted = jax.jit(
            make_train_step(cfg, AdamWConfig()),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        info["state_bytes_per_device"] = _per_device_bytes(
            state_sh, state_shapes
        )
        return jitted, (state_shapes, in_specs), info
    params_shapes = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.PRNGKey(0)
    )
    resolved = shd.tree_specs(p_specs, params_shapes, mesh, rules)
    params_sh = _named(resolved, mesh)
    info["state_bytes_per_device"] = _per_device_bytes(
        params_sh, params_shapes
    )
    cache_shapes = jax.eval_shape(
        lambda: init_cache(cfg, sd.batch, cfg.cdtype())
    )
    cache_sh = shd.tree_shardings(cache_specs(cfg), cache_shapes, mesh, rules)
    info["cache_bytes_per_device"] = _per_device_bytes(
        cache_sh, cache_shapes
    )
    if sd.kind == "prefill":
        jitted = jax.jit(
            make_prefill_step(cfg),
            in_shardings=(params_sh, batch_sh),
            out_shardings=(None, cache_sh),
        )
        return jitted, (params_shapes, in_specs), info
    jitted = jax.jit(
        make_decode_step(cfg),
        in_shardings=(params_sh, cache_sh, batch_sh, NamedSharding(mesh, P())),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
    )
    cur = jax.ShapeDtypeStruct((), jnp.int32)
    return jitted, (params_shapes, cache_shapes, in_specs, cur), info


def _compile(cfg, shape, mesh, rules):
    jitted, args, info = _build(cfg, shape, mesh, rules)
    with mesh, shd.logical_axis_rules(rules, mesh):
        t0 = time.time()
        lowered = jitted.lower(*args)
        info["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        info["compile_s"] = round(time.time() - t0, 2)
    return compiled, info


def _metrics(compiled) -> dict:
    """Flat linear metrics of one compiled program."""
    out: dict = {}
    try:
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0]
        out["flops"] = float(c.get("flops", 0.0))
        out["bytes_accessed"] = float(c.get("bytes accessed", 0.0))
    except Exception:
        out["flops"] = 0.0
        out["bytes_accessed"] = 0.0
    coll = H.parse_collectives(compiled.as_text())
    for k in H.COLLECTIVES:
        out[f"coll_count:{k}"] = float(coll.counts[k])
        out[f"coll_bytes:{k}"] = float(coll.bytes_by_kind[k])
        out[f"coll_wire:{k}"] = float(coll.wire_bytes_by_kind[k])
    out["coll_bytes_total"] = float(coll.total_bytes)
    out["coll_wire_total"] = float(coll.total_wire_bytes)
    return out


def _memory(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
        if m is None:
            return {}
        return {
            k: int(getattr(m, k))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
            if hasattr(m, k)
        }
    except Exception as e:  # pragma: no cover
        return {"error": repr(e)}


def _with_repeats(cfg, repeats: list[int]):
    """Shallow unrolled analysis variant. Inner tile loops are unrolled too
    (scan_unroll), with larger tiles to bound HLO size — attention/SSD
    flops are tile-size invariant and elementwise bytes nearly so."""
    pats = tuple(
        LayerPattern(r, p.block) for r, p in zip(repeats, cfg.patterns)
    )
    upd = {"pattern": pats, "scan_unroll": True}
    if cfg.attn_chunk:
        upd["attn_chunk"] = max(cfg.attn_chunk, 4096)
    upd["ssm_chunk"] = max(cfg.ssm_chunk, 1024)
    return dataclasses.replace(cfg, **upd)


def analyze_depth(cfg, shape, mesh, rules) -> dict:
    """Reconstruct full-depth per-device metrics from shallow unrolled
    variants: total(metric) = glue + Σ_seg repeat_seg · body_seg."""
    n_seg = len(cfg.patterns)
    base = [1] * n_seg
    f0, info0 = _compile(_with_repeats(cfg, base), shape, mesh, rules)
    m0 = _metrics(f0)
    bodies = []
    for i in range(n_seg):
        reps = list(base)
        reps[i] = 2
        fi, _ = _compile(_with_repeats(cfg, reps), shape, mesh, rules)
        mi = _metrics(fi)
        bodies.append({k: max(mi[k] - m0[k], 0.0) for k in m0})
    glue = {
        k: max(m0[k] - sum(b[k] for b in bodies), 0.0) for k in m0
    }
    total = {
        k: glue[k]
        + sum(cfg.patterns[i].repeat * bodies[i][k] for i in range(n_seg))
        for k in m0
    }
    return {
        "total": total,
        "glue": glue,
        "bodies": bodies,
        "analysis_compile_s": info0["compile_s"],
    }


def run_cell(
    arch: str, shape: str, mesh_kind: str, rules=None, write: bool = True,
    tag: str = "", cfg_override=None, analyze: bool = True,
    compile_full: bool = True, out_dir: pathlib.Path = RESULTS,
) -> dict:
    t_start = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh_devices(mesh)
    cfg = cfg_override or configs.full_config(arch, shape)
    sd = configs.SHAPES[shape]
    rules = rules or shd.DEFAULT_RULES
    rec: dict = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "devices": n_dev, "kind": sd.kind, "tag": tag,
        "params_total": param_count(cfg),
        "params_active": active_param_count(cfg),
    }
    tokens = sd.batch * sd.seq
    if sd.kind == "train":
        rec["model_flops_global"] = 6.0 * rec["params_active"] * tokens
    elif sd.kind == "prefill":
        rec["model_flops_global"] = 2.0 * rec["params_active"] * tokens
    else:
        rec["model_flops_global"] = 2.0 * rec["params_active"] * sd.batch
    try:
        if compile_full:
            compiled, info = _compile(cfg, shape, mesh, rules)
            rec.update(info)
            rec["memory_analysis"] = _memory(compiled)
            rec["scanned_metrics"] = _metrics(compiled)
            del compiled
        if analyze:
            depth = analyze_depth(cfg, shape, mesh, rules)
            rec["per_layer"] = {
                "glue": depth["glue"], "bodies": depth["bodies"],
            }
            tot = depth["total"]
            rec["flops_per_device"] = tot["flops"]
            rec["bytes_accessed_per_device"] = tot["bytes_accessed"]
            rec["collective_bytes_per_device"] = tot["coll_bytes_total"]
            rec["collective_wire_bytes_per_device"] = tot["coll_wire_total"]
            rec["collective_detail"] = {
                k: tot[f"coll_bytes:{k}"] for k in H.COLLECTIVES
            }
            rec["collective_counts"] = {
                k: tot[f"coll_count:{k}"] for k in H.COLLECTIVES
            }
            rec["roofline"] = H.roofline_terms(
                tot["flops"], tot["bytes_accessed"], tot["coll_wire_total"]
            )
            rec["hlo_model_flops_ratio"] = rec["model_flops_global"] / max(
                tot["flops"] * n_dev, 1.0
            )
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = repr(e)
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.time() - t_start, 2)
    if write:
        out_dir.mkdir(parents=True, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        path = out_dir / f"{arch}__{shape}__{mesh_kind}{suffix}.json"
        path.write_text(json.dumps(rec, indent=2, default=str))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCHS)
    ap.add_argument("--shape", choices=tuple(configs.SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-analysis", action="store_true")
    ap.add_argument("--no-full-compile", action="store_true",
                    help="skip the scanned full-depth compile (fast "
                         "iteration on the analysis metrics)")
    ap.add_argument("--rules", choices=tuple(shd.RULE_SETS), default=None,
                    help="override the tuned per-arch rule selection")
    ap.add_argument("--tag", default="",
                    help="suffix for the result JSON (perf experiments)")
    ap.add_argument("--out-dir", type=pathlib.Path, default=RESULTS,
                    help="directory for the result JSONs")
    args = ap.parse_args()
    cells = configs.cells() if args.all else [(args.arch, args.shape)]
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    failures = 0
    for arch, shape in cells:
        for mesh_kind in meshes:
            path = args.out_dir / f"{arch}__{shape}__{mesh_kind}.json"
            if args.skip_existing and path.exists():
                if json.loads(path.read_text()).get("ok"):
                    print(f"[skip] {arch} {shape} {mesh_kind}", flush=True)
                    continue
            rule_name = args.rules or ARCH_RULES.get(arch, "default")
            rec = run_cell(arch, shape, mesh_kind,
                           rules=shd.RULE_SETS[rule_name],
                           tag=args.tag,
                           analyze=not args.no_analysis,
                           compile_full=not args.no_full_compile,
                           out_dir=args.out_dir)
            if rec["ok"]:
                r = rec.get("roofline", {})
                print(
                    f"[ok]   {arch:22s} {shape:12s} {mesh_kind:6s} "
                    f"compile={rec.get('compile_s', 0):7.1f}s "
                    f"flops/dev={rec.get('flops_per_device', 0):.3e} "
                    f"dom={r.get('dominant', '?'):10s} "
                    f"bound={r.get('bound_s', 0):.4f}s wall={rec['wall_s']}s",
                    flush=True,
                )
            else:
                failures += 1
                print(f"[FAIL] {arch} {shape} {mesh_kind}: {rec['error']}",
                      flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
