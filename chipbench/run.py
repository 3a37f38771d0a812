"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  One process
loads the cell's configuration and traffic mix, checks that JAX sees a TPU
with as many chips as the cell asks for, builds and warms up the system
under test (set-up), measures for ``--seconds``, compares what the timed
path produced with the plain reference, and prints one JSON object as the
last line of standard output.  ``--trace 1`` records a profiler trace of
the window and reports the cell's per-layer metrics instead of its
end-to-end ones.  Without a TPU, or with too few chips, it prints no result
and exits non-zero.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()          # set-up is timed from process start

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def open_devices(chips: int) -> list:
    """The chips the cell runs on; an error where JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise harness.BenchError(
            f"JAX found no TPU (platform {devices[0].platform!r}); the "
            f"benchmark runs only on the chip")
    if len(devices) < chips:
        raise harness.BenchError(
            f"the cell needs {chips} chips, JAX found {len(devices)}")
    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices[:chips]


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.Cell.load(bench, args.workload)
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t0=T0)
    driver = importlib.import_module(f"chipbench.drivers.{cell.config['driver']}")
    run.devices = open_devices(cell.chips)
    run.count_compiles()
    outcome = driver.run(run)
    line = harness.result_line(run, outcome)
    harness.report_checks(outcome.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        sys.exit(2)
