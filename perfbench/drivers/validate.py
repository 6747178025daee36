"""Driver of validation traffic: passes of the transitioner over a backlog of
reported tensor results.

Each pass reports ``jobs_per_pass`` jobs' results into the store (the
results' payloads are views into pools made from the seed at set-up, at a
distinct offset per job, so no pass sees the arrays of an earlier one) and
runs one ``Transitioner.tick``, whose validate step groups each job's
results through the program's fuzzy-comparison path. A job's replicas are
honest (the job's payload with seeded noise far inside the comparator's
tolerance) or corrupt: an honest replica with a few elements moved far
outside tolerance, at seeded positions inside one site of the payload (the
tail past its last full row of lanes, the rest of its last block of rows,
its first block, anywhere), the sites taken in turn, so that a comparison
which skips any part of a payload misjudges some replica. A job whose
first two replicas disagree carries a third. Each pass holds the same set
of corruption patterns, in an order drawn from the seed, so every seed
offers the same work. Afterwards the plain reference decides a sample of
the window's jobs, drawn from the seed, again.
"""
from __future__ import annotations

import itertools
import math
import time
from typing import Dict, List, Tuple

import numpy as np

from perfbench.harness import device, trace as tracing
from perfbench.harness.cell import Cell, Run
from perfbench.harness.counters import CompileCounter, Spans, earlier_line
from perfbench.reference.quorum import verdict


def payload_elements(cfg: Dict) -> int:
    """Elements of one result: every leaf of the parameter set it holds."""
    return int(sum(math.prod(shape) for shape in cfg["payload_leaves"].values()))


def patterns(corrupt_prob: float, jobs: int) -> List[Tuple[bool, ...]]:
    """Corruption flags per replica for one pass: the expected number of
    jobs of each pattern (a job whose first pair is honest has two
    replicas, any other three), rounded by largest remainder."""
    p = float(corrupt_prob)
    kinds: List[Tuple[Tuple[bool, ...], float]] = [((False, False), (1 - p) ** 2)]
    for flags in itertools.product((False, True), repeat=3):
        if flags[0] or flags[1]:
            w = 1.0
            for f in flags:
                w *= p if f else 1 - p
            kinds.append((flags, w))
    exact = [w * jobs for _, w in kinds]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(len(kinds)), key=lambda k: -(exact[k] - counts[k]))
    for k in order[: jobs - sum(counts)]:
        counts[k] += 1
    out: List[Tuple[bool, ...]] = []
    for (flags, _), c in zip(kinds, counts):
        out.extend([flags] * c)
    return out


def sites(e: int, tile: Dict) -> Dict[str, Tuple[int, int]]:
    """Element ranges ``[lo, hi)`` of a payload of ``e`` elements laid out
    in rows of ``lanes`` and blocks of ``block_rows`` rows: the tail past the
    last full row (the last row where rows divide evenly), the rest of the
    last block, the first block, and the whole payload."""
    lanes = int(tile["lanes"])
    block = int(tile["block_rows"]) * lanes
    tail0 = e - (e % lanes or lanes)
    last0 = (e - 1) // block * block
    return {"tail": (tail0, e),
            "last_block": (last0, tail0) if tail0 > last0 else (last0, e),
            "first_block": (0, min(e, block)),
            "anywhere": (0, e)}


class Payloads:
    """Seeded pools the results are cut from: for replica position r, an
    honest pool (the truth times 1 + noise_r, noise_0 = 0). A corrupt
    replica is a copy of its honest one with ``corrupt_elements`` elements
    moved by ``corrupt_abs`` inside the next of ``corrupt_sites``."""

    def __init__(self, cfg: Dict, tr: Dict, seed: int) -> None:
        self.e = payload_elements(cfg)
        n = self.e * int(tr["pool_payloads"])
        rng = np.random.default_rng([int(seed), 3])
        truth = rng.standard_normal(n, dtype=np.float32)
        eps = float(cfg["honest_rel_noise"])
        self.honest = [truth]
        for _ in range(2):
            noise = rng.uniform(-eps, eps, n).astype(np.float32)
            self.honest.append(truth * (np.float32(1.0) + noise))
        self.bad_abs = tuple(float(x) for x in cfg["corrupt_abs"])
        self.bad_n = int(cfg["corrupt_elements"])
        ranges = sites(self.e, cfg["tile"])
        self.sites = [ranges[name] for name in cfg["corrupt_sites"]]
        self.turn = 0
        # job k's payload starts at (start + k * step) mod span: distinct
        # offsets for the first ``span`` jobs, in an order drawn from the seed
        rng = np.random.default_rng([int(seed), 4])
        self.span = n - self.e + 1
        self.start = int(rng.integers(0, self.span))
        self.step = 1
        while True:
            step = int(rng.integers(1, self.span + 1))
            if math.gcd(step, self.span) == 1:
                self.step = step
                break
        self.k = 0
        self.rng = np.random.default_rng([int(seed), 7])

    def offset(self) -> int:
        off = (self.start + self.k * self.step) % self.span
        self.k += 1
        return off

    def replica(self, off: int, r: int, corrupt: bool) -> np.ndarray:
        x = self.honest[r][off: off + self.e]
        if not corrupt:
            return x
        lo, hi = self.sites[self.turn % len(self.sites)]
        self.turn += 1
        x = x.copy()
        k = min(self.bad_n, hi - lo)
        pos = lo + self.rng.choice(hi - lo, k, replace=False)
        sign = np.where(self.rng.random(k) < 0.5, -1.0, 1.0)
        x[pos] += (sign * self.rng.uniform(*self.bad_abs, k)).astype(np.float32)
        return x


def build(cfg: Dict):
    from repro.core import (App, AppVersion, Host, Platform, ProcessingResource,
                            ProjectServer, ResourceType, default_cpu_plan_class,
                            fuzzy_comparator, next_id, reset_ids)

    cpu = ResourceType.CPU
    reset_ids()
    server = ProjectServer(name=cfg["name"], purge_delay=1e18,
                           engine_backend=cfg["engine_backend"])
    app = App(
        name="grad",
        min_quorum=int(cfg["min_quorum"]),
        init_ninstances=int(cfg["min_quorum"]),
        comparator=fuzzy_comparator(rtol=float(cfg["rtol"]), atol=float(cfg["atol"]),
                                    max_bad_fraction=float(cfg["max_bad_fraction"])),
    )
    version = AppVersion(id=next_id("appver"), app_name="grad",
                         platform=Platform("linux", "x86_64"), version_num=1,
                         plan_class=default_cpu_plan_class())
    app.add_version(version)
    server.add_app(app)
    for h in range(int(cfg["hosts"])):
        server.add_host(Host(id=h + 1, platforms=(Platform("linux", "x86_64"),),
                             resources={cpu: ProcessingResource(cpu, 4, 16.5e9)},
                             volunteer_id=h + 1))
    return server, version.id


class Stager:
    """Reports one pass's results into the store, as hosts would."""

    def __init__(self, server, version_id: int, cfg: Dict, tr: Dict, seed: int) -> None:
        self.server = server
        self.vid = version_id
        self.hosts = int(cfg["hosts"])
        self.payloads = Payloads(cfg, tr, seed)
        self.patterns = patterns(float(tr["corrupt_prob"]), int(tr["jobs_per_pass"]))
        self.rng = np.random.default_rng([int(seed), 5])
        self.next_host = 0
        self.flops = float(cfg["job_flops"])

    def stage(self, now: float) -> List[Tuple[object, List[object], List[np.ndarray]]]:
        from repro.core import InstanceOutcome, InstanceState, Job, next_id

        store = self.server.store
        out = []
        for k in self.rng.permutation(len(self.patterns)):
            flags = self.patterns[int(k)]
            off = self.payloads.offset()
            job = self.server.submit_job(
                Job(id=next_id("job"), app_name="grad", est_flop_count=self.flops,
                    max_success_instances=len(flags) + 2), now)
            insts, arrays = [], []
            for r, bad in enumerate(flags):
                x = self.payloads.replica(off, r, bad)
                inst = store.create_instance(job)
                inst.host_id = self.next_host % self.hosts + 1
                self.next_host += 1
                inst.app_version_id = self.vid
                inst.state = InstanceState.IN_PROGRESS
                inst.state = InstanceState.OVER
                inst.outcome = InstanceOutcome.SUCCESS
                inst.runtime = 3600.0 + r
                inst.peak_flop_count = inst.runtime * 16.5e9
                inst.output = x
                insts.append(inst)
                arrays.append(x)
            out.append((job, insts, arrays))
        return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices: List,
        t_process_start: float, trace_dir: str) -> Run:
    cfg, tr = cell.config, cell.traffic
    server, vid = build(cfg)
    stager = Stager(server, vid, cfg, tr, seed)
    transitioner = server.transitioners[0]
    spans = Spans()
    counter = CompileCounter.get()
    # warm-up: one whole pass of the window's shapes
    now = 60.0
    stager.stage(now)
    transitioner.tick(now)

    c0 = counter.snapshot()
    if trace:
        tracing.start(trace_dir)
        with spans.span("clock"):
            pass
    w0 = time.perf_counter()
    window_jobs = []
    passes = []  # (t0, t1, jobs, results)
    stage_s = 0.0
    while True:
        now += 60.0
        ts = time.perf_counter()
        staged = stager.stage(now)
        stage_s += time.perf_counter() - ts
        t0 = time.perf_counter()
        with spans.span("validate_pass"):
            transitioner.tick(now)
        t1 = time.perf_counter()
        passes.append((t0, t1, len(staged), sum(len(a) for _, _, a in staged)))
        window_jobs.extend(staged)
        if t1 - w0 >= seconds:
            break
    w1 = time.perf_counter()
    trace_path = tracing.stop(trace_dir) if trace else None
    c1 = counter.snapshot()

    got = [([i.validate_state.value for i in insts],
            next((k for k, i in enumerate(insts) if i.id == job.canonical_instance_id), None))
           for job, insts, _ in window_jobs]
    verdicts = sum(s in ("valid", "invalid") for states, _ in got for s in states)
    results = sum(len(states) for states, _ in got)
    e2e = {"validated_per_s": verdicts / (w1 - w0), "setup_s": w0 - t_process_start}
    dev = device.describe(devices)
    earlier_line("validate", {
        "elements": stager.payloads.e, "passes": len(passes), "jobs": len(window_jobs),
        "results": results, "verdicts": verdicts, "window_s": w1 - w0,
        "pass_s_min_max": [min(b - a for a, b, _, _ in passes),
                           max(b - a for a, b, _, _ in passes)],
        "staging_s": stage_s,
        "compiles_in_window": c1[0] - c0[0], "cache_hits_in_window": c1[1] - c0[1],
    })

    # -- correctness: a seeded sample of the window's jobs decided again -----
    t_ref = time.perf_counter()
    rng = np.random.default_rng([int(seed), 6])
    n = min(len(window_jobs), int(tr["reference_jobs"]))
    sample = sorted(rng.choice(len(window_jobs), n, replace=False).tolist())
    checked = [window_jobs[k] for k in sample]
    expect = [verdict(arrays, float(cfg["rtol"]), float(cfg["atol"]), int(cfg["min_quorum"]))
              for _, _, arrays in checked]
    checks = compare([got[k] for k in sample], expect, tr["limits"])
    earlier_line("validate.reference", {"seconds": time.perf_counter() - t_ref,
                                        "jobs": n})

    run_ = Run(setup_s=e2e["setup_s"], end_to_end=e2e, attempted=results, failed=0,
               checks=checks, device=dev, window=(w0, w1))
    e = stager.payloads.e
    run_.data = {"passes": passes, "spans": spans, "elements": e,
                 "least_bytes": [r * e * 4 for _, _, _, r in passes],
                 "checked": checked, "expect": expect}
    if trace:
        run_.trace = tracing.load(trace_path)
        run_.trace_offset_ns = tracing.clock_offset(run_.trace, spans.records)
    return run_


def compare(got: List, expect: List, limits: Dict) -> Dict[str, Tuple[float, float]]:
    """Results whose validate state differs from the reference's, and jobs
    whose canonical result differs."""
    states = canon = 0
    for (gs, gc), (es, ec) in zip(got, expect):
        states += sum(a != b for a, b in zip(gs, es)) + abs(len(gs) - len(es))
        canon += gc != ec
    return {
        "state_mismatch": (float(states), float(limits["state_mismatch"])),
        "canonical_mismatch": (float(canon), float(limits["canonical_mismatch"])),
    }


def control(cell: Cell, run_: Run) -> Dict[str, Tuple[float, float]]:
    """The comparison applied to the control: the reference deciding on
    payloads rounded to bfloat16, put in the program's place, against the
    float64 reference."""
    import ml_dtypes

    cfg = cell.config
    low = [verdict(arrays, float(cfg["rtol"]), float(cfg["atol"]), int(cfg["min_quorum"]),
                   dtype=ml_dtypes.bfloat16)
           for _, _, arrays in run_.data["checked"]]
    return compare(low, run_.data["expect"], cell.traffic["limits"])
