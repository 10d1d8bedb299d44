#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The last line of stdout is the result object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number compared
with its limit). The checks are also the last lines of stderr. With no TPU,
fewer chips than the cell asks for, a device kind without published peaks,
or no program beside the benchmark, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv):
    p = argparse.ArgumentParser(prog="bench/run.py", description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True, help="a workload name in BENCHMARK.json")
    p.add_argument("--seed", type=int, required=True, help="makes the rows' data")
    p.add_argument("--seconds", type=float, required=True, help="the measured window")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0,
                   help="1: trace the window and report the per-layer metrics")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import load_cell, run_cell, say

    try:
        from repro.launch.cli import use_compile_cache
    except ImportError as e:
        say(f"the program is not beside the benchmark ({e}); nothing measured")
        return 2
    cache = use_compile_cache()
    import jax

    # cache every program, however quick to compile, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.peaks import peaks_for

    cell = load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        say(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform!r} device(s); nothing measured")
        return 2
    try:
        peaks = peaks_for(devices[0].device_kind)
    except KeyError as e:
        say(f"{e}; nothing measured")
        return 2
    say(f"{cell.name}: {devices[0].device_kind!r} x{len(devices)}, compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START, peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
