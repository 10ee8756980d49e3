"""Run one benchmark cell once and print its result as the last line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a run under the JAX profiler.  Without as many
accelerator chips as the cell asks for it exits with code 3 and prints no
result.  The last lines on standard error, and the ``checks`` key of the
result, give each number compared for ``correct`` beside its limit.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_workload(args.workload)
    try:
        result = harness.run(spec, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), started=STARTED)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
