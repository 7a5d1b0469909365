import os
os.environ["XLA_FLAGS"] = (os.environ.get("_DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above run before ANY jax import (jax pins the device count at
first init): the single CPU pretends to be 512 devices so the production
meshes materialize.  Nothing is executed — every input is a
ShapeDtypeStruct; success proves the sharding config is coherent, and
memory_analysis/cost_analysis feed EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
  python -m repro.launch.dryrun --all --skip-existing
Results: benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, get_config, list_archs
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes
from repro.launch.roofline import DRYRUN_TARGET, analyze_compiled, hw_for
from repro.launch.rules import cache_logical_axes, rules_for
from repro.launch.specs import default_flags, input_specs, shape_applicable
from repro.models import build_model
from repro.parallel.sharding import logical_to_spec
from repro.train import AdamWConfig, make_state_shardings, make_train_step
from repro.train.optimizer import adamw_init
from repro.train.train_step import batch_sharding

RESULTS_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "benchmarks", "results",
    "dryrun")


def _sharding_tree(mesh, rules, axes_tree):
    is_leaf = lambda a: isinstance(a, tuple)
    return jax.tree.map(
        lambda a: jax.sharding.NamedSharding(mesh, logical_to_spec(rules, a)),
        axes_tree, is_leaf=is_leaf)


def lower_cell(arch: str, shape: str, multi_pod: bool, flags=None,
               opt_overrides=None):
    """Lower + compile one cell; returns (compiled, report-ready context)."""
    cfg = get_config(arch)
    seq, batch, kind = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(mesh.devices.size)
    if flags is None:
        flags = default_flags(cfg, shape, mesh)
    if opt_overrides:
        flags = dataclasses.replace(flags, **opt_overrides)
    rules = rules_for(cfg, mesh, flags)
    model = build_model(cfg, flags, rules)
    specs = input_specs(cfg, shape, flags)

    with jax.set_mesh(mesh):
        if kind == "train":
            opt_cfg = AdamWConfig(
                moment_dtype="bfloat16" if cfg.param_count() > 100e9
                else "float32")
            step = make_train_step(model, opt_cfg)
            def abstract_state(k):
                params = model.init(k)
                return {"params": params,
                        "opt": adamw_init(params, opt_cfg),
                        "step": jnp.zeros((), jnp.int32)}

            state_shapes = jax.eval_shape(abstract_state, jax.random.key(0))
            state_sh = make_state_shardings(model, mesh, rules,
                                            zero1=flags.zero1)
            batch_sh = batch_sharding(mesh, specs)
            fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None))
            lowered = fn.lower(state_shapes, specs)
        elif kind == "prefill":
            def prefill(params, b):
                logits, _, _ = model.forward(params, b)
                return logits
            param_shapes = jax.eval_shape(model.init, jax.random.key(0))
            param_sh = _sharding_tree(mesh, rules,
                                      model.param_logical_axes())
            batch_sh = batch_sharding(mesh, specs)
            fn = jax.jit(prefill, in_shardings=(param_sh, batch_sh),
                         out_shardings=None)
            lowered = fn.lower(param_shapes, specs)
        else:  # decode
            param_shapes = jax.eval_shape(model.init, jax.random.key(0))
            param_sh = _sharding_tree(mesh, rules,
                                      model.param_logical_axes())
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(batch, seq))
            cache_sh = _sharding_tree(
                mesh, rules, cache_logical_axes(cache_shapes))
            data_axes = tuple(a for a in ("pod", "data")
                              if a in mesh.axis_names)
            bspec = (jax.sharding.PartitionSpec() if flags.seq_shard_decode
                     else jax.sharding.PartitionSpec(data_axes))
            tok_sh = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(
                    mesh, bspec if getattr(s, "ndim", 0) >= 1
                    else jax.sharding.PartitionSpec()), specs)
            fn = jax.jit(model.decode_step,
                         in_shardings=(param_sh, cache_sh, tok_sh),
                         out_shardings=(None, cache_sh))
            lowered = fn.lower(param_shapes, cache_shapes, specs)
        compiled = lowered.compile()
    return compiled, dict(cfg=cfg, mesh=mesh, n_dev=n_dev, flags=flags)


def run_cell(arch: str, shape: str, multi_pod: bool,
             skip_existing: bool = True, opt_overrides=None,
             tag: str = "") -> dict:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = os.path.join(
        RESULTS_DIR, f"{arch}__{shape}__{mesh_name}{tag}.json")
    if skip_existing and os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)
    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "status": "skipped", "reason": reason}
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec
    t0 = time.time()
    try:
        compiled, ctx = lower_cell(arch, shape, multi_pod,
                                   opt_overrides=opt_overrides)
        rep = analyze_compiled(
            compiled, arch=arch, shape=shape, mesh_name=mesh_name,
            num_devices=ctx["n_dev"], hw=hw_for(DRYRUN_TARGET),
            cfg=ctx["cfg"])
        rec = {"status": "ok", "compile_s": round(time.time() - t0, 1),
               "flags": dataclasses.asdict(ctx["flags"]),
               **rep.row()}
    except Exception as e:  # noqa: BLE001 — record the failure verbatim
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:],
               "compile_s": round(time.time() - t0, 1)}
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None])
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true", default=True)
    ap.add_argument("--no-skip-existing", dest="skip_existing",
                    action="store_false")
    args = ap.parse_args()

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if args.shape is None else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp,
                               skip_existing=args.skip_existing)
                status = rec.get("status")
                line = (f"[{status:7s}] {arch:28s} {shape:12s} "
                        f"{'multipod' if mp else 'pod':8s} "
                        f"t={rec.get('compile_s', 0):6.1f}s")
                if status == "ok":
                    line += (f" bottleneck={rec['bottleneck']:10s} "
                             f"frac={rec['roofline_fraction']:.3f}")
                elif status == "error":
                    line += " " + rec["error"][:120]
                    failures += 1
                print(line, flush=True)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
