"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core import (  # noqa: E402
    App,
    AppVersion,
    Job,
    Platform,
    ProjectServer,
    default_cpu_plan_class,
    fuzzy_comparator,
    next_id,
    reset_ids,
)
from repro.core.jax_backend import configure_compile_cache  # noqa: E402

# benches that touch the jax backend share one persistent compile cache
configure_compile_cache()

#: Every ``emit`` row of the current process, for machine-readable output
#: (``BENCH_daemons.json``; see ``write_bench_json``).
RESULTS: List[Dict[str, Any]] = []


def emit(name: str, us_per_call: float, derived: str) -> None:
    """The harness output contract: ``name,us_per_call,derived`` CSV."""
    print(f"{name},{us_per_call:.3f},{derived}")
    RESULTS.append({"name": name, "us_per_call": us_per_call, "derived": derived})


def write_bench_json(
    path: Optional[str] = None,
    extra: Optional[Dict[str, Any]] = None,
    rows: Optional[List[Dict[str, Any]]] = None,
) -> str:
    """Dump emitted rows (all of ``RESULTS`` by default, or an explicit
    slice) as JSON so CI can track the perf trajectory. Default path:
    ``benchmarks/BENCH_daemons.json`` (override with ``BENCH_JSON_PATH``)."""
    path = path or os.environ.get(
        "BENCH_JSON_PATH", str(Path(__file__).resolve().parent / "BENCH_daemons.json")
    )
    payload: Dict[str, Any] = {"schema": 1, "rows": RESULTS if rows is None else rows}
    if extra:
        payload.update(extra)
    Path(path).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {path}", file=sys.stderr)
    return path


def timer() -> float:
    return time.perf_counter()


def make_project(
    name: str = "bench",
    min_quorum: int = 2,
    adaptive: bool = False,
    delay_bound: float = 6 * 3600.0,
    cache_size: int = 1024,
) -> ProjectServer:
    server = ProjectServer(name=name, purge_delay=1e18, cache_size=cache_size)
    app = App(
        name="work",
        min_quorum=min_quorum,
        init_ninstances=min_quorum,
        delay_bound=delay_bound,
        adaptive_replication=adaptive,
        comparator=fuzzy_comparator(rtol=1e-6, atol=1e-9),
    )
    for osn in ("windows", "mac", "linux"):
        app.add_version(
            AppVersion(
                id=next_id("appver"),
                app_name="work",
                platform=Platform(osn, "x86_64"),
                version_num=1,
                plan_class=default_cpu_plan_class(),
            )
        )
    server.add_app(app)
    return server


def submit_jobs(server: ProjectServer, n: int, est_flops: float = 0.25 * 3600 * 16.5e9,
                submitter: str = "default", now: float = 0.0):
    jobs = [
        Job(id=next_id("job"), app_name="work", est_flop_count=est_flops, submitter=submitter)
        for _ in range(n)
    ]
    for j in jobs:
        server.submit_job(j, now)
    return jobs
