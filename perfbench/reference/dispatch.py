"""Plain reference for the served scheduler: the paper's job cache (§5.1)
and dispatch policy (§6.3–6.4), replayed request by request.

It shares no code with the program. It holds the deployment the served
cell builds (one app with one CPU version per OS, identical jobs of
``init_instances`` replicas each, a fleet of hosts with one CPU plan class)
and replays what the service handed the project, in order: dispatch waves,
single RPCs and feeder passes. For each request it returns the assignments
``(job, instance, app version, est_flops, est_runtime)`` that the policy
gives:

* feeder: vacant cache slots are filled in position order with the oldest
  unsent instances not already cached;
* shards: cache position ``p`` is owned by shard ``p % n_shards`` until a
  starved shard (fewer than ``low_watermark`` live slots) takes the
  lowest-position live slots of its ring successors, up to
  ``refill_target``, never leaving a donor below the watermark; a host is
  served by shard ``host % n_shards``, whose scan draws its random start
  from its own ``random.Random(shard)`` stream;
* scan: the shard's cached slots in rotated order from the random start,
  the first slot of each job, ranked by the skipped-before bonus (stable);
* checks: a job whose availability-scaled runtime would pass its delay
  bound, or whose volunteer already holds an instance of it, is skipped
  (and its slot's skip count rises); a job already in the reply is passed
  over; dispatch stops once the requested runtime is covered.

All runtime arithmetic runs in ``dtype``: float64 is the reference, and a
lower precision is the control that the comparison has to reject.
"""
from __future__ import annotations

import random
from typing import Dict, List, Sequence, Tuple

import numpy as np

W_SKIPPED = 5.0
Assignment = Tuple[int, int, int, float, float]


class DispatchReference:
    def __init__(
        self,
        os_index: np.ndarray,  # per host (id - 1): index into the OS list
        speed: np.ndarray,  # per host: peak FLOPS of one CPU
        *,
        n_jobs: int,
        init_instances: int,
        job_flops: float,
        delay_bound: float,
        availability: float,  # CPU availability × on-fraction
        cache_size: int,
        n_shards: int,
        low_watermark: int = 4,
        refill_target: int = 8,
        max_moves: int = 64,
        dtype=np.float64,
    ) -> None:
        self.os_index = os_index
        self.speed = speed
        self.init = init_instances
        self.n_inst = n_jobs * init_instances
        self.job_flops = dtype(job_flops)
        self.delay_bound = dtype(delay_bound)
        self.avail = dtype(availability)
        self.dtype = dtype
        self.cache_size = cache_size
        self.n_shards = n_shards
        self.low_watermark = low_watermark
        self.refill_target = refill_target
        self.max_moves = max_moves
        # slot: [instance id, job id, skip count] or None
        self.slots: List = [None] * cache_size
        self.owner = [p % n_shards for p in range(cache_size)]
        self.rng = [random.Random(s) for s in range(n_shards)]
        self.next_unsent = 1  # instances are handed out oldest first
        self.job_volunteers: Dict[int, set] = {}
        self.fill()

    # -- feeder and shards ----------------------------------------------------

    def fill(self) -> None:
        for p in range(self.cache_size):
            if self.slots[p] is None and self.next_unsent <= self.n_inst:
                iid = self.next_unsent
                self.slots[p] = [iid, (iid - 1) // self.init + 1, 0]
                self.next_unsent += 1

    def _live(self, shard: int) -> List[int]:
        return [p for p in range(self.cache_size)
                if self.owner[p] == shard and self.slots[p] is not None]

    def rebalance(self, shard: int) -> None:
        if self.low_watermark <= 0 or self.n_shards < 2:
            return
        mine = len(self._live(shard))
        if mine >= self.low_watermark:
            return
        moved = 0
        for step in range(1, self.n_shards):
            if mine >= self.refill_target or moved >= self.max_moves:
                break
            donor = self._live((shard + step) % self.n_shards)
            while (mine < self.refill_target and moved < self.max_moves
                   and len(donor) > self.low_watermark):
                self.owner[donor.pop(0)] = shard
                moved += 1
                mine += 1

    # -- one request -----------------------------------------------------------

    def request(self, host: int, req_runtime: float) -> List[Assignment]:
        shard = host % self.n_shards
        start = self.rng[shard].randrange(self.cache_size)
        owned = [p for p in range(self.cache_size) if self.owner[p] == shard]
        first_skip: Dict[int, int] = {}
        for p in owned:
            s = self.slots[p]
            if s is not None and s[1] not in first_skip:
                first_skip[s[1]] = s[2]
        seen = set()
        cands = []
        for k in range(self.cache_size):
            p = (start + k) % self.cache_size
            s = self.slots[p]
            if self.owner[p] != shard or s is None or s[1] in seen:
                continue
            seen.add(s[1])
            cands.append(p)
        cands.sort(key=lambda p: -W_SKIPPED * min(first_skip[self.slots[p][1]], 5))

        dt = self.dtype
        pf = float(self.speed[host - 1])
        version = int(self.os_index[host - 1]) + 1
        queue_dur = dt(0.0)
        remaining = dt(req_runtime)
        idle = 0.0
        sending = set()
        reply: List[Assignment] = []
        for p in cands:
            iid, job, _ = self.slots[p]
            est = self.job_flops / dt(pf)
            scaled = est / self.avail
            if queue_dur + scaled > self.delay_bound:
                self.slots[p][2] += 1
                continue
            if job in sending:
                continue
            vols = self.job_volunteers.setdefault(job, set())
            if host in vols:
                self.slots[p][2] += 1
                continue
            vols.add(host)
            sending.add(job)
            reply.append((job, iid, version, float(dt(pf)), float(est)))
            self.slots[p] = None
            queue_dur = dt(queue_dur + scaled)
            remaining = dt(remaining - scaled)
            idle -= 1.0
            if remaining <= 0 and idle <= 0:
                break
        return reply

    # -- replay ----------------------------------------------------------------

    def replay(self, events: Sequence) -> Dict[Tuple[int, float], List[Assignment]]:
        """Replies keyed by (host, requested runtime), for the events the
        service drove in order: ``("wave", [(host, runtime), ...])`` for a
        coalesced wave, ``("rpc", (host, runtime))`` for a single RPC and
        ``("fill",)`` for a feeder pass."""
        out: Dict[Tuple[int, float], List[Assignment]] = {}
        for ev in events:
            if ev[0] == "fill":
                self.fill()
            elif ev[0] == "rpc":
                host, rt = ev[1]
                self.rebalance(host % self.n_shards)
                out[(host, rt)] = self.request(host, rt)
            else:
                by_shard: Dict[int, list] = {}
                for host, rt in ev[1]:
                    by_shard.setdefault(host % self.n_shards, []).append((host, rt))
                for shard in sorted(by_shard):
                    self.rebalance(shard)
                    for host, rt in by_shard[shard]:
                        out[(host, rt)] = self.request(host, rt)
        return out
