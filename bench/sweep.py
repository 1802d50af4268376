"""Find the serving knee of a cell's configuration, once, on the chip.

    python bench/sweep.py --config <name> --traffic <poisson mix> \\
        --rates 10,20,40 --seconds <s> --seed <n>

Sets a one-chip serving cell of that configuration and mix up once (it
needs no entry in ``BENCHMARK.json``) and runs one window per offered
rate (the mix's ``rate_rps`` replaced), printing the latency percentiles
and whether the backlog grew: the mean latency of the window's last
fifth over its first fifth.  The knee is the highest rate whose backlog
does not grow; a serving cell's mix fixes its rate below it.  Benchmark
runs never run this.
"""

import argparse
import json
import pathlib
import sys

import numpy as np

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    c = harness.open_cell(_ROOT, {"name": "sweep", "config": args.config,
                                  "traffic": args.traffic, "chips": 1})
    rates = [float(r) for r in args.rates.split(",")]
    mix = dict(c.mix, rate_rps=max(rates))
    client = harness.Serving(c.config, mix, c.stack, args.seed, args.seconds)
    client.setup()
    for rate in rates:
        mix["rate_rps"] = rate
        client.begin(args.seed)
        run = harness.Run(c)
        e2e = client.window(args.seconds, run)
        lat = [(r["done"] - r["due"]) for r in client.records
               if r["done"] is not None]
        fifth = max(len(lat) // 5, 1)
        growth = float(np.mean(lat[-fifth:]) / np.mean(lat[:fifth]))
        print(json.dumps(dict(e2e, rate_rps=rate, requests=len(lat),
                              failed=client.failed, backlog_growth=growth,
                              service_p50_ms=1e3 * run.serve_report
                              .service_s["p50"])), flush=True)
    client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
