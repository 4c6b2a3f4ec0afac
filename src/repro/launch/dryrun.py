import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
initialisation, and the production meshes need 512 placeholder host devices.

For each cell this script:
  1. builds ShapeDtypeStruct specs (params via eval_shape — no allocation),
  2. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
  3. records ``memory_analysis()``, ``cost_analysis()`` and collective bytes
     parsed from the optimised (post-SPMD) HLO,
  4. appends a JSON record consumed by the roofline report.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --fed \
      --mesh multi      # federated pod-axis steps (paper's technique)
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import applicable_shapes, get_config, list_architectures
from repro.configs.base import SHAPES_BY_NAME, InputShape, param_count
from repro.dist import stepfns
from repro.launch import specs as specs_mod
from repro.launch.hlo_analysis import analyze as analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import V5E
from repro.optim.optimizers import OptimizerConfig

# ---------------------------------------------------------------------------
# per-cell dry-run
# ---------------------------------------------------------------------------


def _mem_analysis_dict(compiled) -> Optional[Dict]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    keys = (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes", "host_argument_size_in_bytes",
        "host_output_size_in_bytes", "host_temp_size_in_bytes",
    )
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out or {"repr": str(ma)}


def run_cell(
    arch: str,
    shape: InputShape,
    multi_pod: bool,
    fed: bool = False,
    fed_round: bool = False,
    keep_hlo: bool = False,
    config_overrides: Optional[Dict] = None,
) -> Dict:
    """Lower+compile one cell; returns the JSON record."""
    cfg = get_config(arch)
    if config_overrides:
        cfg = cfg.replace(**config_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_pods = mesh.shape.get("pod", 1)
    opt_cfg = OptimizerConfig(name="adamw", state_dtype=cfg.opt_state_dtype)
    rec: Dict = {
        "arch": arch,
        "shape": shape.name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": mesh.size,
        "device_kind": V5E,          # the production mesh is v5e chips
        "kind": shape.kind,
        "fed": fed,
        "fed_round": fed_round,
        "ok": False,
    }
    t0 = time.time()

    with mesh:
        if fed_round:
            step = stepfns.make_fed_round_step(cfg)
            state, state_shardings = specs_mod.state_specs(
                cfg, opt_cfg, mesh, fed=True, n_pods=n_pods
            )
            weights = jax.ShapeDtypeStruct((n_pods,), jnp.float32)
            lowered = jax.jit(
                step,
                in_shardings=(state_shardings, None),
                out_shardings=state_shardings,
                donate_argnums=0,
            ).lower(state, weights)
        elif shape.kind == "train":
            state, state_shardings = specs_mod.state_specs(
                cfg, opt_cfg, mesh, fed=fed, n_pods=n_pods
            )
            # pin the grad-accum carry to the params' layout so the
            # scan -> ZeRO-update boundary needs no involuntary reshard
            if fed:
                from jax.sharding import NamedSharding, PartitionSpec

                inner = specs_mod.state_spec_tree(
                    specs_mod.state_shapes(cfg, opt_cfg, 0), cfg, mesh,
                    fed=False,
                )
                grad_sh = jax.tree.map(
                    lambda p: NamedSharding(mesh, p), inner.params,
                    is_leaf=lambda x: isinstance(x, PartitionSpec),
                )
                step = stepfns.make_fed_train_step(
                    cfg, opt_cfg, grad_shardings=grad_sh,
                    spmd_axis_name="pod",
                )
            else:
                step = stepfns.make_train_step(
                    cfg, opt_cfg,
                    grad_shardings=state_shardings.params,
                )
            batch = specs_mod.train_batch_specs(
                cfg, shape, mesh, fed=fed, n_pods=n_pods
            )
            lowered = jax.jit(
                step,
                in_shardings=(state_shardings,
                              jax.tree.map(lambda s: s.sharding, batch)),
                out_shardings=(state_shardings, None),
                donate_argnums=0,
            ).lower(state, batch)
        elif shape.kind == "prefill":
            step = stepfns.make_prefill_step(cfg)
            pstate, p_shardings = specs_mod.state_specs(cfg, opt_cfg, mesh)
            params, param_shardings = pstate.params, p_shardings.params
            tokens, cache, cache_shardings, extra = (
                specs_mod.prefill_input_specs(cfg, shape, mesh)
            )
            args = (params, tokens, cache) + ((extra,) if extra is not None else ())
            in_sh = (param_shardings, tokens.sharding, cache_shardings) + (
                (extra.sharding,) if extra is not None else ()
            )
            lowered = jax.jit(
                step,
                in_shardings=in_sh,
                out_shardings=(None, cache_shardings),
                donate_argnums=2,          # cache buffers alias in place
            ).lower(*args)
        else:  # decode
            step = stepfns.make_decode_step(cfg)
            pstate, p_shardings = specs_mod.state_specs(cfg, opt_cfg, mesh)
            params, param_shardings = pstate.params, p_shardings.params
            token, cache, cache_shardings = specs_mod.decode_input_specs(
                cfg, shape, mesh
            )
            lowered = jax.jit(
                step,
                in_shardings=(param_shardings, token.sharding, cache_shardings),
                out_shardings=(None, cache_shardings),
                donate_argnums=2,          # cache buffers alias in place
            ).lower(params, token, cache)

        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        cost = compiled.cost_analysis() or {}
        rec["cost_analysis"] = {
            k: float(v)
            for k, v in cost.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "transcendentals", "bytes accessed")
                or k.startswith("bytes accessed")
            )
        }
        rec["memory_analysis"] = _mem_analysis_dict(compiled)

        hlo = compiled.as_text()
        rec["hlo_bytes"] = len(hlo)
        # while-loop trips by nesting depth: [grad-accum,] unit-scan, inner
        trips = [max(cfg.n_units, 1)]
        if cfg.ssm is not None and shape.kind in ("train", "prefill"):
            seq = shape.seq_len - cfg.n_frontend_tokens
            trips.append(max(seq // cfg.ssm.chunk, 1))   # SSD chunk scan
        if shape.kind == "train" and cfg.grad_accum > 1 and not fed_round:
            trips = [cfg.grad_accum] + trips
        if fed_round:
            trips = [1]
        analysis = analyze_hlo(hlo, loop_trips=trips)
        rec["hlo_flops"] = analysis["flops"]
        rec["hlo_hbm_bytes"] = analysis["hbm_bytes"]
        rec["hlo_dot_count"] = analysis["dot_count"]
        rec["collectives"] = analysis["collectives"]
        rec["loop_trips"] = trips
        if keep_hlo:
            rec["hlo"] = hlo

        pc = param_count(cfg)
        rec["params_total"] = pc["total"]
        rec["params_active"] = pc["active"]
        rec["ok"] = True
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x applicable shape) cell")
    ap.add_argument("--fed", action="store_true",
                    help="lower the federated pod-axis steps instead")
    ap.add_argument("--fed-round", action="store_true",
                    help="lower the cross-pod FedAvg round step")
    ap.add_argument("--out", default=None, help="append JSON records here")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf exps)")
    args = ap.parse_args(argv)

    cells = []
    archs = list_architectures() if (args.all or not args.arch) else [args.arch]
    for arch in archs:
        cfg = get_config(arch)
        shapes = (
            applicable_shapes(cfg) if (args.all or not args.shape)
            else [SHAPES_BY_NAME[args.shape]]
        )
        for shape in shapes:
            meshes = {
                "single": [False], "multi": [True], "both": [False, True]
            }[args.mesh]
            for multi in meshes:
                cells.append((arch, shape, multi))

    overrides = json.loads(args.override) if args.override else None
    records = []
    failures = 0
    for arch, shape, multi in cells:
        label = f"{arch} x {shape.name} x {'2x16x16' if multi else '16x16'}"
        try:
            rec = run_cell(arch, shape, multi, fed=args.fed,
                           fed_round=args.fed_round,
                           config_overrides=overrides)
            flops = rec["cost_analysis"].get("flops", 0)
            coll = rec["collectives"]["total_bytes"]
            print(
                f"[ok] {label}: lower {rec['lower_s']}s compile "
                f"{rec['compile_s']}s flops {flops:.3e} coll {coll:.3e}B",
                flush=True,
            )
        except Exception as e:
            failures += 1
            rec = {
                "arch": arch, "shape": shape.name,
                "mesh": "2x16x16" if multi else "16x16",
                "ok": False, "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:],
            }
            print(f"[FAIL] {label}: {type(e).__name__}: {e}", flush=True)
        records.append(rec)
        if args.out:
            with open(args.out, "a") as f:
                for r in records[-1:]:
                    f.write(json.dumps(r) + "\n")

    print(f"\n{len(records) - failures}/{len(records)} cells OK")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
