"""Find an open-loop cell's knee on the chip: one engine, one window per
offered rate, and for each whether the backlog grew.

    python3 bench/sweep.py --workload char-ptb.chat --seed 11 --seconds 12 --rates 4,8,12,16

Prints one JSON line per rate.  The cell's traffic file keeps the rate it
runs at (4/5 of the knee); this tool is how that number was found."""
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from bench.harness import sweep

    rows = sweep(args.workload, args.seed, args.seconds,
                 [float(r) for r in args.rates.split(",")], t_start=T_START)
    for r in rows:
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
