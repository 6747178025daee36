"""Each cell of the chip benchmark at a tiny size on the CPU: its driver
and its plain reference agree, a broken timed path makes ``correct`` come
out false, and the control (the reference in the next lower precision,
put in the program's place) fails the comparison.

The drivers are called through ``perfbench/run.py``'s ``main`` with the
look for a chip steered here: the device check hands over the CPU, the
cell's configuration is cut to a tiny size, and the persistent compile
cache is left off."""
import copy
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

pytest.importorskip("jax")

import jax  # noqa: E402

from perfbench import run as bench_run  # noqa: E402
from perfbench.drivers import served, validate  # noqa: E402
from perfbench.harness import cell as cellmod, device  # noqa: E402
from perfbench.loadgen import openloop  # noqa: E402

TINY = {
    "s11_fleet.served": (
        {"hosts": 3000, "jobs": 2000, "cache_slots": 128},
        {"rate_per_s": 40.0, "warmup_s": 0.5, "connections": 8, "grace_s": 10.0},
    ),
    "mamba2_grad_quorum.validate": (
        {"payload_leaves": {"w": [515, 513]}},  # 3 past the last full row, 2 blocks
        {"jobs_per_pass": 8},
    ),
}
_find = cellmod.find_cell


def tiny_cell(name, root=cellmod.ROOT):
    c = _find(name, root)
    conf, traffic = TINY[name]
    c.config.update(copy.deepcopy(conf))
    c.traffic.update(copy.deepcopy(traffic))
    return c


@pytest.fixture
def steered(monkeypatch):
    """Run the command on the CPU at a tiny size."""
    from repro.core import jax_backend

    monkeypatch.setattr(device, "require_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(device, "peaks", lambda kind: {"hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(cellmod, "find_cell", tiny_cell)
    monkeypatch.setattr(jax_backend, "configure_compile_cache", lambda: None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")


def run_cell(workload, capsys, seconds="1"):
    rc = bench_run.main(["--workload", workload, "--seed", str(2**31 + 3),
                         "--seconds", seconds, "--trace", "0"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", list(TINY))
def test_cell_runs_tiny_and_agrees_with_its_reference(workload, steered, capsys):
    line = run_cell(workload, capsys)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert list(line)[-1] == "checks"


# -- faults planted under the timed path --------------------------------------


def _alter_answer(monkeypatch):
    import repro.service.server as svc

    orig = svc.reply_to_wire

    def altered(seq, reply):
        out = orig(seq, reply)
        for j in out.jobs:
            j.job_id += 1
        return out

    monkeypatch.setattr(svc, "reply_to_wire", altered)


def _drop_half(monkeypatch):
    from repro.core import ProjectServer
    from repro.core.scheduler import ScheduleReply

    orig_batch, orig_one = ProjectServer.rpc_batch, ProjectServer.rpc
    seen = {"n": 0}

    def batch(self, requests, now):
        half = len(requests) // 2
        return orig_batch(self, requests[:half], now) + [ScheduleReply() for _ in requests[half:]]

    def one(self, request, now):
        seen["n"] += 1
        return orig_one(self, request, now) if seen["n"] % 2 else ScheduleReply()

    monkeypatch.setattr(ProjectServer, "rpc_batch", batch)
    monkeypatch.setattr(ProjectServer, "rpc", one)


def _stale_cache(monkeypatch):
    from repro.core.scheduler import Feeder

    monkeypatch.setattr(Feeder, "fill", lambda self: 0)


def _skip_tick(monkeypatch):
    from repro.core.fsm import Transitioner

    monkeypatch.setattr(Transitioner, "tick", lambda self, now: 0)


def _half_pending(monkeypatch):
    from repro.core.store import JobStore

    orig = JobStore.pending_transitions

    def half(self, *a, **k):
        jobs = orig(self, *a, **k)
        return jobs[: len(jobs) // 2]

    monkeypatch.setattr(JobStore, "pending_transitions", half)


def _all_agree(monkeypatch):
    from repro.core import jax_backend

    monkeypatch.setattr(jax_backend, "quorum_group_codes",
                        lambda mat, rtol, atol: np.zeros(mat.shape[0], dtype=np.int64))


FAULTS = {
    ("s11_fleet.served", "answer_altered"): _alter_answer,
    ("s11_fleet.served", "half_the_batch_left_out"): _drop_half,
    ("s11_fleet.served", "state_unchanged"): _stale_cache,
    ("mamba2_grad_quorum.validate", "state_unchanged"): _skip_tick,
    ("mamba2_grad_quorum.validate", "half_the_batch_left_out"): _half_pending,
    ("mamba2_grad_quorum.validate", "answer_altered"): _all_agree,
}


@pytest.mark.parametrize("workload,fault", list(FAULTS))
def test_a_broken_timed_path_is_not_correct(workload, fault, steered, monkeypatch, capsys):
    FAULTS[(workload, fault)](monkeypatch)
    line = run_cell(workload, capsys, seconds="0.5")
    assert line["correct"] is False, line["checks"]


def _partial_compare(monkeypatch, upto):
    """The comparison kernel replaced by one that reads only the first
    ``upto(n)`` elements of each flattened pair."""
    from repro.kernels.quorum_compare import ops

    def compare(a, b, *, rtol, atol, interpret=None):
        a = np.asarray(a, dtype=np.float32).ravel()
        b = np.asarray(b, dtype=np.float32).ravel()
        k = upto(a.size)
        d = np.abs(a[:k] - b[:k])
        bad = ~(d <= atol + rtol * np.abs(b[:k]))
        return np.int32(bad.sum()), np.float32((d * d).sum())

    monkeypatch.setattr(ops, "quorum_compare", compare)


PARTIAL = {
    "first_block_only": lambda n: min(n, 1024 * 256),
    "tail_left_out": lambda n: n - n % 256,
}


@pytest.mark.parametrize("fault", list(PARTIAL))
def test_a_comparison_that_skips_part_of_each_payload_is_not_correct(fault, steered,
                                                                     monkeypatch, capsys):
    """At the cell's own payload size (3,765,320 elements: 72 past the last
    full 256-lane row, 15 blocks of 1024 rows) and traffic."""
    monkeypatch.setattr(cellmod, "find_cell", _find)
    _partial_compare(monkeypatch, PARTIAL[fault])
    line = run_cell("mamba2_grad_quorum.validate", capsys, seconds="0.5")
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["state_mismatch"]["value"] > 0


# -- the control -----------------------------------------------------------------


@pytest.mark.parametrize("workload,driver", [("s11_fleet.served", served),
                                             ("mamba2_grad_quorum.validate", validate)])
def test_the_control_fails_the_comparison(workload, driver):
    import time

    cell = tiny_cell(workload)
    run = driver.run(cell, 2**31 + 11, 1.0, False, jax.devices()[:1], time.perf_counter(),
                     "unused")
    assert all(v <= lim for v, lim in run.checks.values()), run.checks
    ctrl = driver.control(cell, run)
    assert any(v > lim for v, lim in ctrl.values()), ctrl


# -- inputs from the seed ---------------------------------------------------------------


def test_inputs_come_from_the_seed():
    p = {"seed": 2**31 + 9, "rate_per_s": 50.0, "warmup_s": 1.0, "seconds": 4.0,
         "fleet": 1000, "req_runtime_h": [0.5, 4.0]}
    a, b = openloop.schedule(p), openloop.schedule(p)
    assert a == b
    c = openloop.schedule(dict(p, seed=p["seed"] + 1))
    assert len(c) == len(a) == 250
    # another seed: the same sizes and the same gaps, in another order
    assert sorted(q["rt"] for q in c) == pytest.approx(sorted(q["rt"] for q in a))
    assert [q["rt"] for q in c] != [q["rt"] for q in a]
    assert a[-1]["due"] == pytest.approx(5.0)
    cfg = cellmod.find_cell("s11_fleet.served").config
    cfg = dict(cfg, hosts=5000)
    (o1, s1), (o2, s2) = served.fleet(cfg, 1), served.fleet(cfg, 2)
    assert np.bincount(o1).tolist() == np.bincount(o2).tolist() == [4250, 350, 400]
    assert np.allclose(np.sort(s1), np.sort(s2)) and not np.array_equal(s1, s2)
    assert s1.mean() == pytest.approx(16.5e9, rel=0.02)
    assert validate.payload_elements(cellmod.find_cell("mamba2_grad_quorum.validate").config) \
        == 3_765_320
    pats = validate.patterns(0.15, 32)
    assert len(pats) == 32 and sum(len(f) == 3 for f in pats) == 9
