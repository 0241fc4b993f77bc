"""Benchmark harness — one module per paper table/figure.

  Fig 8   -> aggregation.py    (single-CPU aggregation operator)
  Table 5 -> comm_volume.py    (pre/post/hybrid/Int2 volumes + times)
  Fig 7   -> speedup_model.py  (Eqn-8 speedup vs P, measured alpha/beta/gamma/delta)
  Figs 9/10 -> scaling.py      (epoch time w/ & w/o comm opts + measured)
  Fig 11/Table 3 -> convergence.py (FP32/Int2 x LP accuracy + cd-5 baseline)
  Serving -> serving.py        (online inference latency/QPS + bit-parity)

Prints ``name,us_per_call,derived`` CSV.
"""

from __future__ import annotations

import argparse
import sys
import time

MODULES = ["aggregation", "comm_volume", "speedup_model", "scaling",
           "convergence", "bits_ablation", "serving"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=MODULES, default=None)
    args = ap.parse_args()
    mods = [args.only] if args.only else MODULES
    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            for row in mod.run():
                print(f"{row['name']},{row['us_per_call']},{row['derived']}")
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}:{e}", file=sys.stderr)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
