"""Batched serving example: prefill a batch of prompts, then greedy-decode
with the sharded KV cache — the decode_32k cells lower exactly this step.

Run:  PYTHONPATH=src python examples/serve_decode.py [--arch tinyllama-1.1b]
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.models import Model
from repro.serve import generate, make_decode_step, make_prefill_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=sorted(ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = ARCHS[args.arch].reduced()       # smoke-scale weights on CPU
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    prompts = jax.random.randint(rng, (args.batch, args.prompt_len),
                                 0, cfg.vocab)
    max_seq = args.prompt_len + args.new_tokens + (cfg.vision_tokens or 0)

    print(f"{args.arch} (reduced): batch={args.batch} "
          f"prompt={args.prompt_len} new={args.new_tokens}")
    t0 = time.perf_counter()
    out = generate(model, params, prompts, max_new=args.new_tokens,
                   max_seq=max_seq)
    dt = time.perf_counter() - t0
    total_new = args.batch * args.new_tokens
    print(f"generated {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s incl. compile)")
    print("first sequence:", list(map(int, out[0][:12])), "...")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
