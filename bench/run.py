"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, traffic mix,
driver, metrics and limits are read from ``BENCHMARK.json`` and the files
under ``bench/`` (see ``bench/harness.py``). The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last the
``checks``: each number compared with its limit); the same checks are the
last lines of standard error. Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits 1.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                             args.workload)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # every program of the cell goes to the persistent cache, however
    # quickly it compiled, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = harness.find_chips(cell)
    if devices is None:
        return 1
    line = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            T_START, devices,
                            harness.peaks_for(cell, devices[0].device_kind))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
