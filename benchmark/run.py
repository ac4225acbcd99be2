"""python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|2>

One process: loads a cell, warms every program it will use (set-up), measures
for --seconds, prints earlier lines and then ONE last line with the contract's
keys. --trace 2 is a --trace 0 run that, once the window has closed and its
numbers are taken, traces a few seconds of the same traffic through the
program's capture control and adds the per-layer metrics to that line.
--trace 1 (a window traced from its first second by a profiler of the
harness, retired in PR 27) is still taken and runs as --trace 2. Exit code 2
and no result line when the program is not importable, when jax finds no
TPU, when the device kind is not in peaks.json or when the device count is
not the cell's `chips`. There is no CPU mode.
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    from benchmark import common  # starts the set-up clock

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="PATH",
                    help="builder only: also copy the traced run's "
                         ".xplane.pb to PATH.xplane.pb, to look at by hand")
    args = ap.parse_args(argv)
    retired = args.trace == 1
    if retired:
        args.trace = 2

    from benchmark import manifest

    try:
        import luminaai_tpu  # noqa: F401  the system under test
    except ImportError as e:
        print(f"benchmark: the program is not here: {e}", file=sys.stderr)
        return 2
    try:
        bench = manifest.load_benchmark()
        faults = manifest.check(bench)
        if faults:
            raise manifest.ManifestError("; ".join(faults))
        cell = manifest.Cell(bench, args.workload)
        cache = common.setup_compile_cache()
        device = common.require_device(cell.chips)
    except (manifest.ManifestError, common.Refused, OSError) as e:
        print(f"benchmark: refused: {e}", file=sys.stderr)
        return 2
    common.say("start", workload=cell.name, config=cell.config_name,
               traffic=cell.traffic_name, seed=args.seed,
               seconds=args.seconds, trace=args.trace,
               **({"note": "--trace 1 is retired and runs as --trace 2"}
                  if retired else {}),
               compile_cache=cache,
               device={k: device[k] for k in ("platform", "kind", "count")})
    kind = cell.traffic["kind"]
    if kind == "train":
        from benchmark import train_cell as driver
    elif kind in ("open_loop", "closed_loop"):
        from benchmark import serve_cell as driver
    else:
        print(f"benchmark: unknown traffic kind {kind!r}", file=sys.stderr)
        return 2
    result = driver.run(cell, args, device)
    sys.stdout.flush()
    print(common.result_line(**result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
