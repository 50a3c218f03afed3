#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the weights from the seed, builds the engine at the
configuration's pinned plan and warms up every shape the cell's traffic
uses; the window then offers that traffic for ``--seconds``. After it,
a sample of the finished requests is compared with the configuration's
plain reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics with ``--trace 0``, the per-layer ones with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, the
numbers compared beside their limits, which also end standard error.
Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for.

``--control 1`` puts the lower-precision control in the program's place
in that comparison, so that its gaps decide ``correct``, which then has
to read false (not part of a benchmark run).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))
# the compile cache sits at one fixed path inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchlib import spec
    cell = spec.resolve(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[bench] compile cache {enable_compile_cache()}", flush=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchlib import cell_run
    result = cell_run.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace),
                          control=bool(args.control), t_start=T_START)
    cell_run.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
