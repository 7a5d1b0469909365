"""Batched serving example: prefill + decode with KV caches (ring buffers
on windowed layers), greedy sampling, per-step latency stats.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.launch.mesh import make_local_mesh
from repro.launch.rules import rules_for
from repro.models import RuntimeFlags, build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b",
                    help="served as its reduced() smoke config on CPU")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    mesh = make_local_mesh()
    flags = RuntimeFlags(param_dtype="float32", compute_dtype="float32",
                         remat="none")
    rules = rules_for(cfg, mesh, flags)
    model = build_model(cfg, flags, rules)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    B = args.batch
    prompts = jnp.asarray(
        rng.integers(1, cfg.vocab_size, (B, args.prompt_len)), jnp.int32)
    max_len = args.prompt_len + args.new_tokens
    cache = model.init_cache(B, max_len)

    step = jax.jit(model.decode_step)

    # prefill token by token (teacher forcing into the cache), then decode
    t0 = time.perf_counter()
    tok = prompts[:, :1]
    for t in range(args.prompt_len):
        logits, cache = step(params, cache,
                             {"tokens": prompts[:, t:t + 1],
                              "pos": jnp.asarray(t, jnp.int32)})
    prefill_s = time.perf_counter() - t0

    out_tokens = []
    lat = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for t in range(args.prompt_len, max_len):
        t1 = time.perf_counter()
        logits, cache = step(params, cache,
                             {"tokens": tok, "pos": jnp.asarray(t, jnp.int32)})
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        jax.block_until_ready(tok)
        lat.append(time.perf_counter() - t1)
        out_tokens.append(np.asarray(tok)[:, 0])

    gen = np.stack(out_tokens, axis=1)
    print(f"arch: {cfg.name} ({cfg.param_count() / 1e6:.1f}M params, "
          f"{'ring-buffer SWA cache' if cfg.window else 'full KV cache'})")
    print(f"prefill: {args.prompt_len} tokens x {B} seqs in "
          f"{prefill_s * 1e3:.0f} ms")
    print(f"decode : {args.new_tokens} steps, median "
          f"{np.median(lat) * 1e3:.1f} ms/step, p99 "
          f"{np.quantile(lat, 0.99) * 1e3:.1f} ms")
    print(f"sample generation (batch 0): {gen[0][:16].tolist()} ...")
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    print("OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
