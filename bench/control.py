"""Readings behind a cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 3 --seconds <s> --out <file.json>

Sets the cell up once, then for each seed runs a window of ``--seconds``
at the cell's own load and compares the same seeded sample of answers as
a run does: the program's gap to the float32 reference (the sound
reading) and, on the first ``--control-seeds`` seeds, the gap of the
control, the reference one precision below what the configuration
states (``check.control``) put in the program's place.  The
limit in the configuration lies between the largest sound reading and
the smallest control reading.  Benchmark runs never run this.
"""

import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    c = harness.open_cell(_ROOT, args.workload)
    client = harness.CLIENTS[c.mix["kind"]](c.config, c.mix, c.stack,
                                            seeds[0], args.seconds)
    client.setup()
    out = []
    for j, seed in enumerate(seeds):
        client.begin(seed)
        e2e = client.window(args.seconds, harness.Run(c))
        t = time.perf_counter()
        got = harness.readings(client, c.config, c.mix, seed,
                               control=j < args.control_seeds)
        rec = {"seed": seed, "window": e2e,
               "answers": len(client.answers()),
               "reference_s": time.perf_counter() - t,
               "sound": max(r["gap"] for r in got),
               "control": min((r["control_gap"] for r in got
                               if "control_gap" in r), default=None),
               "control_max": max((r["control_gap"] for r in got
                                   if "control_gap" in r), default=None),
               "items": got}
        out.append(rec)
        print(json.dumps({k: v for k, v in rec.items() if k != "items"}),
              flush=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    client.close()
    sound = max(r["sound"] for r in out)
    ctl = [r["control_max"] for r in out if r["control_max"] is not None]
    print(json.dumps({"workload": args.workload, "lower": sound,
                      "upper": min(ctl) if ctl else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
