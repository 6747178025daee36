"""Find the served cell's knee: offer rising fixed rates to one built
project and report, for each, the completed rate, the tail and whether
the backlog of outstanding requests grew through the window.

    python3 perfbench/sweep.py --workload s11_fleet.served --seed <n> \
        --seconds 8 --rates 50,100,150,200

The knee is the highest offered rate at which completed replies stay at
98% of offered or more and outstanding requests do not grow from
mid-window to its end. Runs on the chip only, like ``run.py``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def outstanding(rows, t):
    return sum(1 for r in rows if r[3] is not None and r[3] <= t < r[4])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--grace", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench.run import prepare

    try:
        cell, devices = prepare(args.workload)
    except (RuntimeError, KeyError) as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from perfbench.drivers import served
    from perfbench.harness.counters import CompileCounter, Spans, percentile

    cfg = cell.config
    os_index, speed = served.fleet(cfg, args.seed)
    server = served.build_project(cfg, os_index, speed)
    served.warm_dispatch(int(cfg["cache_slots"]))
    spans = Spans()
    served.Recorder(server, spans)
    counter = CompileCounter.get()

    def offer(rate):
        cell.traffic.update(rate_per_s=rate, grace_s=args.grace)
        out = asyncio.run(served._serve(server, cell, args.seed, args.seconds, None,
                                        spans, counter))
        w0, w1 = out["w0"], out["w1"]
        rows = out["gen"]["rows"]
        win = [r for r in rows if r[1] == "window"]
        lat = [(r[4] - r[2]) if r[7] == "ok" else float("inf") for r in win]
        done = sum(1 for r in rows if r[7] == "ok" and w0 <= r[4] <= w1) / args.seconds
        mid, end = outstanding(rows, (w0 + w1) / 2), outstanding(rows, w1)
        line = {
            "offered_per_s": rate, "completed_per_s": done,
            "completed_share": done / rate,
            "p50_ms": percentile(lat, 0.5) * 1e3, "p99_ms": percentile(lat, 0.99) * 1e3,
            "outstanding_mid": mid, "outstanding_end": end,
            "waves": out["marks"]["w1"][1]["waves"] - out["marks"]["w0"][1]["waves"],
            "sustained": done >= 0.98 * rate and end <= max(mid, 2),
        }
        print(json.dumps(line), flush=True)
        return line["sustained"]

    knee, failed_at = 0.0, None
    for rate in [float(x) for x in args.rates.split(",")]:
        if offer(rate):
            knee = max(knee, rate)
        elif failed_at is None:
            failed_at = rate
        else:
            break  # two rates past the knee
    if failed_at is not None and failed_at > knee:
        for k in range(1, 4):  # refine between the last sustained and first failed rate
            rate = round(knee + (failed_at - knee) * k / 4)
            if offer(rate):
                knee = max(knee, rate)
            else:
                break
    print(json.dumps({"knee_per_s": knee, "cell_rate_per_s": round(0.8 * knee)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
