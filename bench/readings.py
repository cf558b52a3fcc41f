"""Read the numbers that decide `correct` over many seeds in one process,
for the program and, on the seeds asked for, for the control (the
reference at `high` put in the program's place).  This is how the limits
in bench/limits/<cell>.json were set; the benchmark's own runs do not run
the control.

    python3 bench/readings.py --workload char-ptb.decode --seconds 5 \
        --seeds 101,102,...,112 --control-seeds 101,102,103

Prints one JSON line per seed, then a summary line."""
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from bench.harness import run_cell

    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for s in (int(x) for x in args.seeds.split(",")):
        r = run_cell(args.workload, s, args.seconds, False,
                     t_start=time.perf_counter(), control=s in ctl)
        row = {"seed": s, "correct": r["correct"],
               **{k: c["value"] for k, c in r["checks"].items()},
               "readings": r["readings"]}
        if "control" in r:
            row["control"] = r["control"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {k: max(r[k] for r in rows) for k in ("greedy_gap", "state_err")}
    summary.update({f"control_min_{k}": min(r["control"][k] for r in rows
                                            if "control" in r)
                    for k in ("greedy_gap", "state_err") if ctl})
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
