"""Paper reproduction, single workload: Proxy TeraSort vs 'Hadoop' TeraSort.

Mirrors the paper's §3 through the unified execution API: profile the
original at scale, emit the Table-3 proxy as a versioned spec, load it
back, run it on the ``openmp`` and ``hadoop`` stacks via the uniform
``Stack.run()`` contract, auto-tune over the pytree parameter space, and
print the Table-6/Fig-5 numbers.

Run:  PYTHONPATH=src python examples/proxy_terasort.py [--scale small|full]
"""

import argparse
import json
import os
import tempfile

import jax
import jax.numpy as jnp

from repro.api import ProxySpec, get_stack
from repro.core import characterize, vector_accuracy
from repro.core.autotune import autotune
from repro.core.metrics import REPORT_METRICS
from repro.core.workloads import PROXY_SPECS, SCALES, workload_step_fn
from repro.data import gen_records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["tiny", "small", "full"])
    args = ap.parse_args()

    print(f"== Hadoop TeraSort ({args.scale}: "
          f"{SCALES[args.scale]['terasort_n']:,} records) ==")
    fn, wargs = workload_step_fn("terasort", args.scale)
    orig = characterize(fn, wargs, name="terasort", execute=True, exec_iters=2)
    print(f"   sort step: {orig.exec_s:.3f} s")

    # Hadoop-substrate run with host-spilled intermediates (the I/O axis)
    keys, _ = gen_records(jax.random.PRNGKey(0), SCALES[args.scale]["terasort_n"])
    rep = get_stack("hadoop").map_reduce(
        lambda c: jnp.sort(c.reshape(-1)), lambda x: jnp.sort(x), keys,
        n_chunks=8)
    print(f"   hadoop-substrate: {rep.wall_s:.2f} s, spill "
          f"{rep.io_bytes/1e6:.0f} MB ({rep.io_bandwidth/1e6:.0f} MB/s)")

    print("== Proxy TeraSort spec round-trip (versioned ProxySpec) ==")
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump(PROXY_SPECS["terasort"], f)
    try:
        spec = ProxySpec.load(f.name)
    finally:
        os.unlink(f.name)
    print(f"   spec v{spec.spec_version}: {len(spec.edges)} edges, "
          f"default stack={spec.stack!r}")
    for stack_name in ("openmp", "hadoop"):
        r = get_stack(stack_name).run(spec)
        print(f"   run[{stack_name:7s}] wall={r.wall_s:.3f}s "
              f"io={r.io_bytes/1e3:.0f} kB")

    print("== Auto-tune over the pytree parameter space ==")
    res = autotune(spec.to_benchmark(), orig.metrics, tol=0.15, max_iter=20)
    pp = res.proxy.profile(execute=True, exec_iters=3)
    keys_m = [k for k in REPORT_METRICS if k in orig.metrics]
    acc = vector_accuracy(orig.metrics, pp.metrics, keys=keys_m)
    print(f"   tuned in {res.iterations} iterations; proxy runs "
          f"{pp.exec_s*1e3:.1f} ms")
    print(f"   speedup {orig.exec_s/pp.exec_s:.0f}x   "
          f"avg accuracy {acc['avg']:.3f} "
          f"(paper: 136x-336x at >=90%)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
