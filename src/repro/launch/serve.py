"""Serving launcher: batched decode against a randomly initialized model
(``--preset smoke``, the CPU-scale variant, by default; ``--preset full
--n-layers N`` serves the published widths with only the depth cut).

  PYTHONPATH=src python -m repro.launch.serve --arch musicgen-medium \
      --batch 4 --prompt-len 16 --new-tokens 16
  python -m repro.launch.serve --preset full --n-layers 1 --engine paged \
      --batch 4 --prompt-len 128 --new-tokens 16 --page-size 16

``--engine paged`` routes through the production tier (paged KV cache +
continuous-batching scheduler + single fixed-shape jitted step); the
default ``naive`` engine is the whole-batch parity reference.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.common.compile_cache import use_compile_cache
from repro.configs import ARCH_IDS
from repro.launch.train import add_preset_args, launcher_config
from repro.models.registry import build_model
from repro.serve.engine import DecodeEngine, PagedDecodeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=ARCH_IDS)
    add_preset_args(ap)
    ap.add_argument("--attn-impl", default="",
                    choices=["", "naive", "flash_jnp", "flash_pallas"],
                    help="override the arch's attention implementation "
                         "(--preset full defaults to flash_pallas, which "
                         "also selects the Pallas paged-decode kernel)")
    ap.add_argument("--engine", default="naive", choices=["naive", "paged"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = launcher_config(args)
    lm = build_model(cfg)
    params = lm.init(jax.random.key(0))
    key = jax.random.key(1)
    B, S = args.batch, args.prompt_len
    batch = {}
    if cfg.family == "audio":
        batch["tokens"] = jax.random.randint(
            key, (B, S, cfg.n_codebooks), 0, cfg.vocab_size)
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    if cfg.family == "vlm":
        batch["vis_embeds"] = jax.random.normal(
            key, (B, cfg.n_vis_tokens, cfg.d_vis), jnp.float32)

    if args.engine == "paged":
        engine = PagedDecodeEngine(
            lm=lm, params=params, max_batch=B,
            max_seq_len=S + args.new_tokens + 16,
            max_new=args.new_tokens, page_size=args.page_size,
            prefill_chunk=max(S, 8), temperature=args.temperature)
        t0 = time.time()
        out = engine.generate(batch, args.new_tokens)
        dt = time.time() - t0
        extra = f" step_traces={engine.step_traces}"
    else:
        engine = DecodeEngine(lm, params, max_seq_len=S + args.new_tokens)
        t0 = time.time()
        out = engine.generate(batch, args.new_tokens,
                              temperature=args.temperature)
        dt = time.time() - t0
        extra = ""
    print(f"[serve:{args.engine}] {args.arch}: generated {out.shape} in "
          f"{dt:.2f}s ({args.new_tokens * B / dt:.1f} tok/s){extra}")
    print(out[0].tolist()[:8])


if __name__ == "__main__":
    main()
