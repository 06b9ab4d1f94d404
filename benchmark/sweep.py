"""Find a cell's knee: one set-up, then a short window at each rate.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 40,80,120

Prints one line per rate: latency, failed share, whether the backlog grew
(the last quarter's median latency over the first quarter's). The knee is
the highest rate with no failed request and no growing backlog; the cell's
file then fixes 0.8 of it as a number. Not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.session import Session, log, percentile  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    rates = [float(r) for r in args.rates.split(",")]
    ses = Session(args.workload, args.seed, smoke=args.smoke)
    counts = [max(1, int(round(r * args.seconds))) for r in rates]
    try:
        ses.start(sum(counts))
        first = 0
        for rate, n in zip(rates, counts):
            win = ses.measure(rate, args.seconds, first=first)
            first += n
            lat = (win.rec.done - win.rec.due) * 1e3
            order = np.argsort(win.rec.due)
            q = max(1, len(order) // 4)
            head, tail = np.nanmedian(lat[order[:q]]), np.nanmedian(lat[order[-q:]])
            print(json.dumps({
                "rate": rate, "attempted": win.attempted, "failed": win.failed,
                **win.end_to_end(), "p99_ms": percentile(win.latency_ms, 0.99),
                "first_quarter_p50_ms": float(head), "last_quarter_p50_ms": float(tail),
            }), flush=True)
            time.sleep(2.0)  # let the queue drain before the next rate
    finally:
        log(f"[device] {ses.close()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
