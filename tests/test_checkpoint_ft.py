"""Checkpoint/restart, async writer, fault injection, elastic resharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.distributed.fault_tolerance import ResilientTrainLoop
from repro.faults import InjectedFailure
from repro.models import Model
from repro.train import AdamWConfig, TrainOptions, init_state, make_train_step
from repro.train.checkpoint import (AsyncCheckpointer, available_steps,
                                    latest_step, restore_checkpoint,
                                    save_checkpoint)


def _tiny_state(rng):
    cfg = ARCHS["tinyllama-1.1b"].reduced()
    model = Model(cfg)
    return cfg, model, init_state(model, rng)


def test_checkpoint_roundtrip(tmp_path, rng):
    cfg, model, state = _tiny_state(rng)
    save_checkpoint(state, str(tmp_path), step=7)
    assert latest_step(str(tmp_path)) == 7
    restored = restore_checkpoint(state, str(tmp_path))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_no_partial_checkpoints(tmp_path, rng):
    _, _, state = _tiny_state(rng)
    save_checkpoint(state, str(tmp_path), step=1)
    names = os.listdir(tmp_path)
    assert all(not n.startswith(".tmp") for n in names)


def test_async_checkpointer_gc(tmp_path, rng):
    _, _, state = _tiny_state(rng)
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(state, s)
    ck.wait()
    assert available_steps(str(tmp_path)) == [3, 4]


def test_resilient_loop_recovers_from_failures(tmp_path, rng):
    cfg, model, state = _tiny_state(rng)
    step_fn = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3),
                                      TrainOptions()))

    def batch_fn(step):
        r = jax.random.PRNGKey(step)          # deterministic data replay
        toks = jax.random.randint(r, (2, 16), 0, cfg.vocab)
        return {"tokens": toks, "labels": toks}

    fails = {5, 9}

    def injector(step):
        if step in fails:
            fails.discard(step)
            raise InjectedFailure(f"node died at step {step}")

    loop = ResilientTrainLoop(step_fn, str(tmp_path), ckpt_every=3)
    result = loop.run(state, batch_fn, num_steps=12,
                      failure_injector=injector)
    assert result.restarts == 2
    assert int(result.state.step) == 12
    assert all(np.isfinite(m["loss"]) for m in result.metrics_history)


def test_elastic_restore_with_new_shardings(tmp_path, rng):
    """A checkpoint restores onto a different mesh (elastic scaling)."""
    cfg, model, state = _tiny_state(rng)
    save_checkpoint(state, str(tmp_path), step=1)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    from repro.distributed.sharding import named, param_specs
    specs = named(param_specs(state.params, mesh, cfg=cfg), mesh)
    shardings = type(state)(params=specs,
                            opt={"mu": specs, "nu": specs, "master": specs},
                            step=jax.NamedSharding(mesh, jax.P()))
    restored = restore_checkpoint(state, str(tmp_path), shardings=shardings)
    assert int(restored.step) == int(state.step)
    a = jax.tree.leaves(restored.params)[0]
    assert isinstance(a.sharding, jax.sharding.NamedSharding)


def test_loss_decreases_and_compression_works(rng):
    cfg, model, state = _tiny_state(rng)
    toks = jax.random.randint(rng, (4, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    for compress in (False, True):
        st = init_state(model, rng)
        step_fn = jax.jit(make_train_step(
            model, AdamWConfig(lr=3e-3, warmup_steps=1),
            TrainOptions(compress_grads=compress)))
        losses = []
        for _ in range(8):
            st, m = step_fn(st, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], f"compress={compress}: {losses}"


def test_microbatch_accumulation_matches_full_batch(rng):
    cfg, model, _ = _tiny_state(rng)
    toks = jax.random.randint(rng, (4, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    s1 = init_state(model, rng)
    s2 = init_state(model, rng)
    f1 = jax.jit(make_train_step(model, AdamWConfig(), TrainOptions(accum=1)))
    f2 = jax.jit(make_train_step(model, AdamWConfig(), TrainOptions(accum=2)))
    s1, m1 = f1(s1, batch)
    s2, m2 = f2(s2, batch)
    # same data => losses close; grads averaged identically up to reordering
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-2
