"""Record a short traced window of a cell for the reducer's tests:

    python3 bench/record_trace.py --workload char-ptb.decode --seed 7 \
        --trace-seconds 0.05 --out bench/tests/data/char-ptb.decode.xplane.pb

A `--trace 1` run of the cell, except that only `--trace-seconds` of its
window are traced and the trace file is kept."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace-seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from bench.harness import run_cell

    r = run_cell(args.workload, args.seed, max(4.0, args.trace_seconds),
                 True, t_start=T_START, keep_trace=args.out,
                 trace_seconds=args.trace_seconds)
    print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
