"""Run one cell once: set up, measure for ``--seconds``, check, print.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never touches JAX while the server child holds the chip. The
last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``)
and, last, ``checked``: each number compared beside its limit.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import readers  # noqa: E402
from benchmark import server as server_mod  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402
from benchmark.session import NoAccelerator, Session, log  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--smoke", action="store_true",
        help="tests only: the configuration's toy sizes, any platform",
    )
    args = ap.parse_args()

    ses = Session(args.workload, args.seed, smoke=args.smoke, trace=bool(args.trace),
                  t_process=T_PROCESS)
    rate = float(ses.wl["rate_rps"])
    capture_path = log_in_window = None
    try:
        ses.start(max(1, int(round(rate * args.seconds))))
        win = ses.measure(rate, args.seconds)
        if args.trace:
            capture_path = ses.wait_capture()
            log_in_window = ses.window_log(win)
    except NoAccelerator as exc:
        log(str(exc))
        return 3
    except server_mod.ServerFailed as exc:
        log(f"server failed: {exc}")
        return 4
    finally:
        device_line = ses.close()
    if device_line is None:
        log("the server child printed no device line\n" + ses.srv.tail())
        return 4

    # the reference runs now: the window has closed, the peak has been read
    # and the server's state is freed
    correct, checked = ses.judge(win)

    cell = ses.cell
    device = {
        "platform": device_line["platform"], "kind": device_line["kind"],
        "count": device_line["count"],
        "memory_peak_bytes": max(device_line["peak_bytes"] or [0]),
    }
    metrics: dict = {}
    result = {"correct": bool(correct), "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": device}
    if not args.trace:
        values = dict(win.end_to_end(), setup_s=ses.setup_s)
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        if not capture_path:
            log("--trace 1: the server wrote no capture")
            return 5
        try:
            reduced = trace_mod.reduce(capture_path, allow_host=ses.smoke)
        except trace_mod.EmptyCapture as exc:
            log(f"--trace 1: {exc}")
            return 5
        capture_s = ses.capture["seconds"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = max(capture_s, reduced["window_s"])
        # the roofline join is by the window's totals: what was sent while
        # the capture was open, over the module's time in the capture
        t_open = ses.capture.get("at_unix", win.rec.t0_unix) - win.rec.t0_unix
        inside = win.full & (win.rec.sent >= t_open) & (win.rec.sent < t_open + capture_s)
        ctx = {
            "prom_start": win.prom_start, "prom_end": win.prom_end, "config": ses.cfg,
            "peaks": cell.peaks, "device_kind": device["kind"], "trace": reduced,
            "traced_seed_lens": [len(win.sets[i]) for i in np.flatnonzero(inside)],
            "log_in_window": log_in_window,
            "harness": {"ready_s": ses.srv.ready_s},
        }
        for m, spec in cell.per_layer():
            value = readers.read(spec["reader"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
        }
        log(f"[trace] {capture_path}: busy {reduced['busy_s']:.3f}s of "
            f"{device['window_s']:.3f}s, {len(ctx['traced_seed_lens'])} requests sent inside, "
            f"modules {sorted((k, len(v)) for k, v in reduced['modules'].items())}")
    result["checked"] = checked
    for name, pair in checked.items():
        log(f"[checked] {name} {pair['value']} limit {pair['limit']}")
    log(f"[checked] correct {correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
