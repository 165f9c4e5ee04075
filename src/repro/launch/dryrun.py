import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real jitted program (train_step for training
shapes, prefill/serve_step for inference shapes) with full-size
ShapeDtypeStruct inputs and production shardings, compiles it, and records:

  * memory_analysis()      — per-device bytes (proves the cell fits HBM)
  * cost_analysis()        — per-device HLO FLOPs / bytes accessed
  * collective byte totals — parsed from the post-SPMD HLO text, per op kind
  * lowering/compile wall times

Results are cached as JSON under experiments/dryrun/ (one file per cell);
repro.roofline.analysis consumes them for EXPERIMENTS.md §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
      --shape train_4k --mesh single --policy qm
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs, policies
from repro.configs.base import SHAPES, cells_for, input_specs
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.model import DecoderModel
from repro.roofline import hlo_collectives, jaxpr_cost
from repro.serve import engine
from repro.train import step as train_step_mod
from repro.train.state import TrainState

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str):
    """Sum result-operand sizes of every collective op, by kind."""
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", line)
        if not m:
            continue
        type_str, op = m.groups()
        base = op
        for suffix in ("-start", "-done"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
        if base not in _COLLECTIVES:
            continue
        if op.endswith("-done"):
            continue  # counted at -start
        stats[base]["count"] += 1
        stats[base]["bytes"] += _shape_bytes(type_str)
    stats["total_bytes"] = sum(
        v["bytes"] for k, v in stats.items() if isinstance(v, dict))
    return stats


def _microbatches_for(shape) -> int:
    return 4 if shape.kind == "train" else 1


def _policy_from(name: str) -> policies.Policy:
    """Any registry policy (or '+'-composition); sfp8 realized stash."""
    return policies.get(name, container="sfp8")


def build_cell(arch_name: str, shape_name: str, multi_pod: bool,
               policy_name: str, layout: str = "tp",
               num_microbatches: int = None):
    """Returns (jitted_fn, arg_shapes tuple) ready to lower."""
    cfg = configs.get(arch_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = shd.rules_for(mesh, layout=layout)
    policy = _policy_from(policy_name)
    model = DecoderModel(cfg, policy, mesh=mesh, rules=rules)

    param_axes = model.param_axes()
    param_sh = shd.tree_shardings(mesh, param_axes, rules)
    repl = shd.replicated(mesh)
    specs = input_specs(cfg, shape)
    batch_p = shd.batch_specs(rules, shape.kind, "cond_embeddings" in specs)
    batch_sh = {k: NamedSharding(mesh, batch_p[k]) for k in specs}

    if shape.kind == "train":
        nm = (num_microbatches if num_microbatches is not None
              else (1 if layout == "fsdp" else _microbatches_for(shape)))
        tc = train_step_mod.TrainConfig(num_microbatches=nm,
                                        param_shardings=param_sh)
        fn = train_step_mod.make_train_step(model, tc)
        state_shapes = jax.eval_shape(
            lambda k: train_step_mod.init_state(model, k, tc),
            jax.random.PRNGKey(0))
        state_sh = TrainState(
            params=param_sh,
            opt=state_shapes.opt._replace(m=param_sh, v=param_sh, count=repl),
            pstate=jax.tree.map(lambda _: repl, state_shapes.pstate),
            step=repl, rng=repl, grad_residual=None)
        state_sh = shd.refine_shardings(state_shapes, state_sh, mesh)
        batch_sh = shd.refine_shardings(specs, batch_sh, mesh)
        jfn = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                      donate_argnums=(0,))
        return jfn, (state_shapes, specs), mesh

    if shape.kind == "prefill":
        fn = engine.make_prefill_step(model, max_len=shape.seq_len)
        params_shapes = model.param_shapes()
        cax = engine.cache_axes(model, shape.global_batch, shape.seq_len)
        cache_sh = shd.tree_shardings(mesh, cax, rules)
        args = [params_shapes, specs["tokens"]]
        in_sh = [param_sh, batch_sh["tokens"]]
        if "cond_embeddings" in specs:
            args.append(specs["cond_embeddings"])
            in_sh.append(batch_sh["cond_embeddings"])
        jfn = jax.jit(fn, in_shardings=tuple(in_sh),
                      out_shardings=(NamedSharding(
                          mesh, batch_p["tokens"]), cache_sh))
        return jfn, tuple(args), mesh

    # decode
    fn = engine.make_serve_step(model)
    params_shapes = model.param_shapes()
    cache_shapes = model.init_cache(shape.global_batch, shape.seq_len,
                                    spec_only=True)
    cax = engine.cache_axes(model, shape.global_batch, shape.seq_len)
    cache_sh = shd.tree_shardings(mesh, cax, rules)
    cache_sh = shd.refine_shardings(cache_shapes, cache_sh, mesh)
    tok_sh = shd.refine_shardings(specs["tokens"], batch_sh["tokens"], mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    jfn = jax.jit(fn, in_shardings=(param_sh, cache_sh, tok_sh, repl),
                  donate_argnums=(1,))
    return jfn, (params_shapes, cache_shapes, specs["tokens"], pos), mesh


def run_cell(arch_name: str, shape_name: str, mesh_kind: str,
             policy_name: str, out_dir: Path, force: bool = False,
             layout: str = "tp", num_microbatches=None):
    tag = f"{arch_name}__{shape_name}__{mesh_kind}__{policy_name}"
    if layout != "tp":
        tag += f"__{layout}"
    out_file = out_dir / f"{tag}.json"
    if out_file.exists() and not force:
        print(f"[skip] {tag} (cached)")
        return json.loads(out_file.read_text())

    print(f"[cell] {tag} ...", flush=True)
    multi_pod = mesh_kind == "multi"
    record = {"arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
              "policy": policy_name, "layout": layout, "ok": False}
    t0 = time.time()
    try:
        jfn, args, mesh = build_cell(arch_name, shape_name, multi_pod,
                                     policy_name, layout=layout,
                                     num_microbatches=num_microbatches)
        with mesh:
            t1 = time.time()
            lowered = jfn.lower(*args)
            t2 = time.time()
            compiled = lowered.compile()
            t3 = time.time()

        record["lower_s"] = round(t2 - t1, 2)
        record["compile_s"] = round(t3 - t2, 2)
        record["n_devices"] = 512 if multi_pod else 256

        try:
            ca = compiled.cost_analysis()
            record["cost_analysis"] = {
                k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "bytes accessed", "transcendentals",
                 "bytes accessed0{}", "bytes accessed1{}",
                 "bytes accessedout{}", "optimal_seconds")}
        except Exception as e:  # pragma: no cover
            record["cost_analysis_error"] = str(e)

        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                record["memory_analysis"] = {
                    a: int(getattr(ma, a)) for a in (
                        "argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "alias_size_in_bytes",
                        "generated_code_size_in_bytes")
                    if hasattr(ma, a)}
        except Exception as e:  # pragma: no cover
            record["memory_analysis_error"] = str(e)

        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
        record["collectives"] = parse_collectives(hlo)
        record["collectives_trip_weighted"] = hlo_collectives.parse(hlo)
        record["hlo_bytes"] = len(hlo)

        # Jaxpr-level global flops/bytes with exact scan trip counts (the
        # CPU backend's cost_analysis does not unroll while bodies —
        # EXPERIMENTS.md §Roofline).
        try:
            t4 = time.time()
            record["jaxpr_cost"] = jaxpr_cost.estimate(jfn, *args)
            record["jaxpr_cost_s"] = round(time.time() - t4, 2)
        except Exception as e:  # pragma: no cover
            record["jaxpr_cost_error"] = str(e)
        record["ok"] = True
        print(f"  ok in {time.time() - t0:.1f}s "
              f"(lower {record['lower_s']}s, compile {record['compile_s']}s)",
              flush=True)
    except Exception as e:
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        print(f"  FAILED: {record['error']}", flush=True)

    out_dir.mkdir(parents=True, exist_ok=True)
    out_file.write_text(json.dumps(record, indent=2))
    return record


def all_cells(mesh_kinds, policy):
    """Cells of the production matrix. ``policy`` may be a comma list
    (e.g. ``qm,qm+qe,bitwave``): every policy gets its own cell per
    (arch, shape, mesh) point, so composed policies are first-class
    members of the matrix rather than a side experiment."""
    for cfg in configs.ASSIGNED:
        for shape in cells_for(cfg):
            for mk in mesh_kinds:
                for pol in policy.split(","):
                    yield cfg.name, shape.name, mk, pol.strip()


def summarize_hlo_vs(results, baseline_policy: str = "qm"):
    """Compare compiled-HLO sizes of each policy against ``baseline_policy``
    per (arch, shape, mesh) point — the cost of a composed policy's extra
    quantization machinery in program size."""
    base = {(r["arch"], r["shape"], r["mesh"]): r["hlo_bytes"]
            for r in results
            if r.get("ok") and r["policy"] == baseline_policy
            and "hlo_bytes" in r}
    rows = []
    for r in results:
        if not r.get("ok") or "hlo_bytes" not in r:
            continue
        key = (r["arch"], r["shape"], r["mesh"])
        if r["policy"] == baseline_policy or key not in base:
            continue
        rows.append({
            "arch": key[0], "shape": key[1], "mesh": key[2],
            "policy": r["policy"], "hlo_bytes": r["hlo_bytes"],
            f"vs_{baseline_policy}": r["hlo_bytes"] / base[key],
        })
    return rows


def _print_hlo_rows(results, baseline_policy: str = "qm"):
    for row in summarize_hlo_vs(results, baseline_policy):
        print(f"  hlo {row['arch']} {row['shape']} {row['mesh']} "
              f"{row['policy']}: {row['hlo_bytes']} bytes "
              f"({row[f'vs_{baseline_policy}']:.2f}x {baseline_policy})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--policy", default="qm",
                    metavar="NAME[+NAME...][,NAME...]",
                    help="precision policy from the registry "
                         f"({'/'.join(policies.names())}), composable "
                         "with '+' and comma-separable into multiple "
                         "matrix cells, e.g. qm,qm+qe,bitwave")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--layout", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    out_dir = Path(args.out)
    mesh_kinds = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.list:
        for cell in all_cells(mesh_kinds, args.policy):
            print("  ".join(cell))
        return

    if args.all:
        results = [run_cell(*cell, out_dir, args.force, layout=args.layout,
                            num_microbatches=args.microbatches)
                   for cell in all_cells(mesh_kinds, args.policy)]
        ok = sum(r["ok"] for r in results)
        print(f"\n== {ok}/{len(results)} cells compiled ==")
        _print_hlo_rows(results)
        if ok < len(results):
            for r in results:
                if not r["ok"]:
                    print(f"  FAIL {r['arch']} {r['shape']} {r['mesh']}: "
                          f"{r.get('error')}")
            raise SystemExit(1)
        return

    assert args.arch and args.shape, "--arch/--shape or --all required"
    results = []
    for mk in mesh_kinds:
        for pol in args.policy.split(","):
            r = run_cell(args.arch, args.shape, mk, pol.strip(), out_dir,
                         args.force, layout=args.layout,
                         num_microbatches=args.microbatches)
            results.append(r)
            if r["ok"]:
                print(json.dumps({k: r[k] for k in
                                  ("cost_analysis", "memory_analysis",
                                   "collectives") if k in r}, indent=2))
    _print_hlo_rows(results)


if __name__ == "__main__":
    main()
