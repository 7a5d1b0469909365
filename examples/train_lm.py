"""End-to-end driver: train a ~100M-param LM with the full stack —
FFD-packed data pipeline, logical-axis sharding, AdamW, checkpointing,
crash-safe resume.

Defaults are sized for this CPU container (--smoke trains a 3M model in
seconds).  The full ~110M config is `--preset 100m --steps 300`; on real
hardware the same script scales out by swapping make_local_mesh for
make_production_mesh.

Run:  PYTHONPATH=src python examples/train_lm.py --smoke
      PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.data import PackedLMDataset
from repro.launch.mesh import make_local_mesh
from repro.launch.rules import rules_for
from repro.models import RuntimeFlags, build_model
from repro.train import AdamWConfig, CheckpointManager, make_train_step
from repro.train.optimizer import adamw_init

PRESETS = {
    "smoke": ArchConfig(
        name="train-smoke", family="dense", num_layers=4, d_model=128,
        num_heads=4, num_kv_heads=4, head_dim=32, d_ff=512,
        vocab_size=2048),
    "100m": ArchConfig(
        name="train-100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=12, head_dim=64, d_ff=3072,
        vocab_size=32000),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=list(PRESETS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()
    if args.smoke:
        args.preset = "smoke"

    cfg = PRESETS[args.preset]
    print(f"model: {cfg.name} ({cfg.param_count() / 1e6:.1f}M params)")

    mesh = make_local_mesh()
    flags = RuntimeFlags(param_dtype="float32", compute_dtype="float32",
                         remat="none")
    rules = rules_for(cfg, mesh, flags)
    model = build_model(cfg, flags, rules)

    opt_cfg = AdamWConfig(peak_lr=3e-4, warmup_steps=20,
                          total_steps=args.steps)
    step_fn = jax.jit(make_train_step(model, opt_cfg))

    ds = PackedLMDataset(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         batch_size=args.batch, seed=0)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)

    # crash-safe resume
    state, manifest = mgr.restore()
    if state is None:
        params = model.init(jax.random.key(0))
        state = {"params": params, "opt": adamw_init(params, opt_cfg),
                 "step": jnp.zeros((), jnp.int32)}
        start = 0
    else:
        start = manifest["step"]
        ds.restore(manifest["extra"]["data"])
        print(f"resumed from step {start}")

    it = iter(ds)
    t_last, losses = time.perf_counter(), []
    with jax.set_mesh(mesh):
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()
                     if k != "segments"}
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
            if (step + 1) % 5 == 0:
                dt = (time.perf_counter() - t_last) / 5
                t_last = time.perf_counter()
                print(f"step {step + 1:4d}  loss {losses[-1]:.4f}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"{dt * 1e3:.0f} ms/step")
            if (step + 1) % args.ckpt_every == 0:
                mgr.save(step + 1, state, extra={"data": ds.state()})
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
