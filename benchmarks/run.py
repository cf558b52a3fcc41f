"""Paper-table harness — one entry per paper table/figure, trained as
reduced reproductions on the CPU (no speed records: those come from the
chip benchmark, `bench/`).

  PYTHONPATH=src python -m benchmarks.run            # all tables
  PYTHONPATH=src python -m benchmarks.run --quick    # smoke pass
  PYTHONPATH=src python -m benchmarks.run --only table1_char_lm train_rnn

Prints a compact CSV per table and writes results/benchmarks/*.json.
"""
from __future__ import annotations

import argparse
import time

from benchmarks import tables as T


def _train_rnn(quick=False):
    from benchmarks.train_rnn import train_rnn_pipeline
    return train_rnn_pipeline(quick=quick)


BENCHES = {
    "train_rnn": _train_rnn,
    "table1_char_lm": T.table1_char_lm,
    "table1b_convergence": T.table1b_convergence,
    "table2_text8": T.table2_text8,
    "table3_word_lm": T.table3_word_lm,
    "table4_mnist": T.table4_mnist,
    "table5_qa": T.table5_qa,
    "table6_gru": T.table6_gru,
    "table7_hardware": lambda quick=False: T.table7_hardware(),
    "fig1b_variance": T.fig1b_stochastic_variance,
    "fig2_generalization": T.fig2_generalization,
    "fig3_batch_size": T.fig3_batch_size,
}


def _print_rows(name, rows):
    print(f"\n=== {name} ===")
    for r in rows:
        if isinstance(r, dict):
            print(",".join(f"{k}={v}" for k, v in r.items()
                           if k not in ("train_curve_bpc",)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)

    names = args.only or list(BENCHES)
    t_all = time.time()
    failures = []
    for name in names:
        t0 = time.time()
        try:
            rows = BENCHES[name](quick=args.quick)
            _print_rows(name, rows)
            print(f"[{name}: {time.time() - t0:.1f}s]")
        except Exception as e:  # keep the harness going; report at the end
            failures.append((name, repr(e)))
            print(f"[{name}: FAILED {e!r}]")
    print(f"\ntotal {time.time() - t_all:.1f}s; "
          f"{len(names) - len(failures)}/{len(names)} benches ok")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
