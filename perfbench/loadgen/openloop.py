"""Open-loop load generator for the scheduler service, run as its own process.

    python3 perfbench/loadgen/openloop.py '<json parameters>'

It never imports JAX and never touches the chip: the service it drives
blocks its event loop for every dispatch wave, so a generator sharing that
loop would be held back by exactly what it measures.

Requests follow a schedule fixed in advance from the seed (see
:func:`schedule`), each sent at its due time over a small pool of pooled
TCP connections, with replies matched to requests by sequence number. Each
request is timed from its due time on the schedule, so a stall counts
against every request it delays, and the generator records how late it
sent each one. When the last request is sent it waits for the replies,
at most ``grace_s`` past the window's close, then prints one JSON object on
standard output.

The wire format is the service's newline protocol (``WORK`` frames out,
``JOBS`` / ``ERR`` frames back), written and parsed here without the
program's codec.
"""
from __future__ import annotations

import asyncio
import json
import math
import sys
import time
from typing import Dict, List, Optional

import numpy as np


def _gaps(rng: np.random.Generator, n: int, length: float) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps that fill ``length`` seconds: the
    exponential distribution's quantiles, scaled to sum to ``length`` and
    put in an order drawn from ``rng``. Every seed gets the same set of
    gaps, so the offered load is the same; only the order differs."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    g *= length / g.sum()
    return rng.permutation(g)


def schedule(p: Dict) -> List[Dict]:
    """The requests of one run, in due order: warm-up, then the window.

    ``p`` holds ``seed``, ``rate_per_s``, ``warmup_s``, ``seconds``,
    ``fleet`` (hosts are drawn uniformly from ids 1..fleet) and
    ``req_runtime_h`` (lo, hi: each request asks for CPU work spread
    evenly over that range, in an order drawn from the seed)."""
    rng = np.random.default_rng([int(p["seed"]), 7])
    lo, hi = (float(x) * 3600.0 for x in p["req_runtime_h"])
    out: List[Dict] = []
    t0 = 0.0
    for phase, length in (("warmup", float(p["warmup_s"])), ("window", float(p["seconds"]))):
        n = max(1, int(round(float(p["rate_per_s"]) * length)))
        due = t0 + np.cumsum(_gaps(rng, n, length))
        rts = lo + (hi - lo) * rng.permutation((np.arange(n) + 0.5) / n)
        hosts = rng.integers(1, int(p["fleet"]) + 1, n)
        for d, rt, h in zip(due, rts, hosts):
            out.append({"due": float(d), "rt": float(rt), "host": int(h), "phase": phase})
        t0 += length
    return out


def encode_work(seq: int, host: int, req_runtime: float) -> bytes:
    return (
        f"WORK {seq} host={host} disk={1e12!r} cpu={req_runtime!r}:{0.0!r}:{0.0!r}\n"
    ).encode()


def decode_reply(line: str):
    """(seq, status, jobs): status is ``ok`` or ``err:<code>``; jobs is a
    list of [job_id, instance_id, version_id, est_runtime, est_flops]."""
    toks = line.rstrip("\r\n").split(" ")
    seq = int(toks[1])
    if toks[0] == "ERR":
        return seq, f"err:{toks[2] if len(toks) > 2 else ''}", []
    if toks[0] != "JOBS":
        return seq, f"err:verb-{toks[0]}", []
    jobs = []
    for tok in toks[2:]:
        key, _, val = tok.partition("=")
        if key == "job":
            for item in val.split(","):
                c = item.split(":")
                jobs.append([int(c[0]), int(c[1]), int(c[2]), float(c[3]), float(c[4])])
    return seq, "ok", jobs


async def run(p: Dict) -> Dict:
    reqs = schedule(p)
    t_start = float(p["t_start"])
    conns = []
    for _ in range(int(p["connections"])):
        r, w = await asyncio.open_connection(p["host"], int(p["port"]), limit=1 << 20)
        conns.append((r, w))
    sent: List[Optional[float]] = [None] * len(reqs)
    recv: List[Optional[float]] = [None] * len(reqs)
    status: List[str] = ["none"] * len(reqs)
    jobs: List[list] = [[] for _ in reqs]
    done = asyncio.Event()
    outstanding = {"n": len(reqs)}

    async def reader(r: asyncio.StreamReader) -> None:
        while True:
            raw = await r.readline()
            if not raw:
                return
            t = time.perf_counter()
            seq, st, js = decode_reply(raw.decode())
            i = seq - 1
            if 0 <= i < len(reqs) and recv[i] is None:
                recv[i], status[i], jobs[i] = t, st, js
                outstanding["n"] -= 1
                if outstanding["n"] == 0:
                    done.set()

    readers = [asyncio.create_task(reader(r)) for r, _ in conns]
    try:
        for i, q in enumerate(reqs):
            delay = t_start + q["due"] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            _, w = conns[i % len(conns)]
            sent[i] = time.perf_counter()
            w.write(encode_work(i + 1, q["host"], q["rt"]))
            await w.drain()
        close = t_start + float(p["warmup_s"]) + float(p["seconds"]) + float(p["grace_s"])
        try:
            await asyncio.wait_for(done.wait(), max(0.0, close - time.perf_counter()))
        except asyncio.TimeoutError:
            pass
    finally:
        for _, w in conns:
            w.close()
        for t in readers:
            t.cancel()
        await asyncio.gather(*readers, return_exceptions=True)
    rows = []
    for i, q in enumerate(reqs):
        rows.append([
            i + 1, q["phase"], t_start + q["due"], sent[i],
            recv[i] if recv[i] is not None else math.inf,
            q["host"], q["rt"], status[i], jobs[i],
        ])
    return {"t_start": t_start, "rows": rows}


def main(argv: List[str]) -> int:
    p = json.loads(argv[1])
    out = asyncio.run(run(p))
    sys.stdout.write(json.dumps(out))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
