"""End-to-end training driver: a ~100M-param dense LM with the production
stack — AdamW+ZeRO states, microbatch accumulation, atomic checkpointing,
restart-from-latest, straggler monitoring — on whatever devices exist.

Default runs a ~20M model for 60 steps (a few minutes on 1 CPU core);
--preset 100m trains the ~100M config for --steps steps.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 60] [--preset 20m]
"""

import argparse
import dataclasses
import tempfile

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.fault_tolerance import ResilientTrainLoop
from repro.data import gen_text_tokens
from repro.models import Model
from repro.train import AdamWConfig, TrainOptions, init_state, make_train_step

PRESETS = {
    "20m": ArchConfig(name="lm-20m", family="dense", n_layers=8, d_model=256,
                      n_heads=8, n_kv_heads=4, d_ff=1024, vocab=8192,
                      rope_theta=1e4, dtype="float32"),
    "100m": ArchConfig(name="lm-100m", family="dense", n_layers=12,
                       d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
                       vocab=32000, rope_theta=1e4, dtype="float32"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="20m", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    print(f"{cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{len(jax.devices())} device(s)")
    model = Model(cfg)
    state = init_state(model, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(
        model, AdamWConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps),
        TrainOptions(accum=2)))

    def batch_fn(step):
        rng = jax.random.PRNGKey(step)            # deterministic replay
        toks = gen_text_tokens(rng, args.batch * (args.seq + 1), cfg.vocab
                               ).reshape(args.batch, args.seq + 1)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    with tempfile.TemporaryDirectory() as ckpt_dir:
        loop = ResilientTrainLoop(step_fn, ckpt_dir,
                                  ckpt_every=args.ckpt_every)
        result = loop.run(state, batch_fn, num_steps=args.steps)
    hist = result.metrics_history
    print(f"steps={len(hist)} restarts={result.restarts} "
          f"stragglers_flagged={len(result.straggler_reports)}")
    print(f"loss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"(ce {hist[0]['ce']:.3f} -> {hist[-1]['ce']:.3f})")
    assert hist[-1]["loss"] < hist[0]["loss"], "training did not learn"
    print("OK: loss decreased.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
