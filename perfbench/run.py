"""Benchmark entry point: one run of one cell.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``checks`` last); the last lines
of standard error give each checked number beside its limit.  Without a
TPU, or with fewer chips than the cell needs, it exits with 1 and prints
no result.  Compiled programs persist in ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()  # set-up is counted from process start

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from perfbench import harness
    from repro.core.cache import use_persistent_compile_cache

    use_persistent_compile_cache()
    # every program of the cell, however quick to compile, is kept, so a
    # second run of the cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
