"""Quickstart: the dwarf methodology end-to-end in ~a minute (CPU).

  1. profile an original workload (JAX Kmeans)        — 'perf' stage
  2. decompose its HLO cost channels into dwarfs      — hotspot analysis
  3. load the Table-3 proxy from its versioned spec   — proxy construction
  4. run it on a software stack via Stack.run()       — uniform execution
  5. auto-tune over the pytree parameter space        — adjust/feedback
  6. report Eq.1 accuracy + runtime speedup           — Fig.5/Table-6 style

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax

from repro.api import ProxySpec, get_stack
from repro.core import characterize, decompose_to_dwarfs, vector_accuracy
from repro.core.autotune import autotune
from repro.core.metrics import REPORT_METRICS
from repro.core.workloads import PROXY_SPECS, workload_step_fn


def main():
    print("== 1. profile the original (Hadoop-Kmeans analog, 'small') ==")
    fn, args = workload_step_fn("kmeans", "small")
    orig = characterize(fn, args, name="kmeans", execute=True, exec_iters=2)
    print(f"   exec={orig.exec_s*1e3:.1f} ms  "
          f"flops={orig.metrics['flops']:.3g}  "
          f"AI={orig.metrics['arithmetic_intensity']:.1f}")

    print("== 2. dwarf decomposition (execution-ratio weights) ==")
    for dwarf, w in sorted(decompose_to_dwarfs(orig.report).items(),
                           key=lambda kv: -kv[1]):
        if w > 0.01:
            print(f"   {dwarf:10s} {w:.2f}")

    print("== 3. load the Table-3 proxy from its spec ==")
    spec = ProxySpec.from_json(PROXY_SPECS["kmeans"])
    proxy = spec.to_benchmark()
    print(f"   {spec.name}: v{spec.spec_version}, {len(spec.edges)} edges, "
          f"default stack={spec.stack!r}")

    print("== 4. uniform execution on a software stack ==")
    rep = get_stack(spec.stack).run(spec, rng=jax.random.PRNGKey(0))
    print(f"   run[{spec.stack}] wall={rep.wall_s:.3f}s "
          f"io={rep.io_bytes:.0f} B")

    print("== 5. auto-tune over the pytree parameter space "
          "(<=15% deviation) ==")
    res = autotune(proxy, orig.metrics, tol=0.15, max_iter=20)
    print(f"   converged={res.converged} after {res.iterations} iterations "
          f"({res.profiles_run} profiles)")

    print("== 6. validation ==")
    pp = res.proxy.profile(execute=True, exec_iters=2)
    keys = [k for k in REPORT_METRICS if k in orig.metrics]
    acc = vector_accuracy(orig.metrics, pp.metrics, keys=keys)
    print(f"   avg accuracy (Eq.1): {acc['avg']:.3f}")
    print(f"   runtime: original {orig.exec_s*1e3:.1f} ms -> proxy "
          f"{pp.exec_s*1e3:.2f} ms  ({orig.exec_s/pp.exec_s:.0f}x faster)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
