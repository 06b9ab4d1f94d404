"""The control: the reference in the next precision down, in the program's
place, judged exactly as a run's answers are. It has to come out not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed: the catalog and the window's seed sets as a run of that seed
and length draws them, the control's answers (``answer.control`` in the
configuration file says what is lowered), and the numbers compared beside
their limits. No server, no window: the control need not be served to be
judged. A benchmark run never runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import Lowered  # noqa: E402
from benchmark.session import Session  # noqa: E402


def control_numbers(workload: str, seed: int, seconds: float, smoke: bool = False) -> dict:
    ses = Session(workload, seed, smoke=smoke)
    ses.build()
    n = max(1, int(round(float(ses.wl["rate_rps"]) * seconds)))
    ses.draw(n)
    win = types.SimpleNamespace(
        sets=ses.sets, full=np.ones(len(ses.sets), dtype=bool), never=0, rec=None
    )
    correct, checked = ses.judge(win, lowered=Lowered(**ses.cfg["answer"]["control"]))
    return {"seed": seed, "correct": correct, "checked": checked}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_numbers(args.workload, seed, args.seconds, args.smoke)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
