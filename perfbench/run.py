#!/usr/bin/env python3
"""The benchmark's one command: one cell, one run.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Set-up (signing the corpus, booting the topology, the verify tile's
compile from <checkout>/.jax_cache, warm-up traffic) is timed from the
first line of this file to the window's first scheduled send. With
--trace 0 the last stdout line carries the cell's end-to-end metrics,
with --trace 1 its per-layer metrics (from a device capture inside the
window). Exits non-zero, printing no result, when the verify tile is
not on a TPU, when it has fewer chips than the cell asks for, or when
the repo is not there.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tile_process_hook():
    """Runs in every process the topology spawns (they import this
    script as __mp_main__): with --trace 1 the device tile's profiler
    window goes under the checkout, not /tmp."""
    prof = os.environ.get("PERFBENCH_PROF_DIR")
    if prof:
        from firedancer_tpu.prof import device
        device.trace_dir = lambda topo, tile: os.path.join(
            prof, f"{topo}_{tile}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offered frames/s instead of the cell's own "
                         "(the knee sweep only; the driver never sets it)")
    args = ap.parse_args(argv)
    from perfbench.harness import BenchError, report, run_cell
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START, rate=args.rate)
    except BenchError as e:
        print(f"perfbench: FAILED: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:        # the verify tile's process owns the chip
        print("perfbench: FAILED: the harness imported jax", file=sys.stderr)
        return 1
    report(out)
    return 0


if __name__ == "__mp_main__":
    tile_process_hook()

if __name__ == "__main__":
    sys.exit(main())
