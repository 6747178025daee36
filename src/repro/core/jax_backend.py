"""JAX execution backend for the batch engines (ROADMAP item 1).

Every batch engine (dispatch scoring, the slot-major WRR client passes,
world accrual/completion) runs on NumPy by default. This module provides
the ``backend="jax"`` execution path behind the *same* engine interfaces:
the dense O(slots)/O(J·H)/O(Q·H) inner passes run as ``jax.jit`` kernels,
while the sparse host-side tails (group resolution, lexsort ordering,
per-row locality adjustments, REC debits) stay on the oracle's exact
NumPy/Python code. The contract is the repo's standing one, extended one
level: scalar oracle ⇒ NumPy engine ⇒ JAX engine, *bit-identical* —
asserted whole-run by the 4th parity axis in ``core/scenarios.run_parity``.

The bit-identity is asserted on XLA:CPU, where XLA's CPU emitter may let
LLVM contract a ``mul`` feeding an ``add``/``sub`` inside one fusion into
an FMA (the product is never rounded). The installed JAX (0.9.0) is not
relied on to block that with a flag or ``lax.optimization_barrier``;
instead every kernel here is **staged**: multiplies that feed
accumulations run in their own jit (the dispatch boundary materializes
the rounded product), and the adds run in a second jit. What the CPU
parity tests (``tests/test_jax_backend.py``, the scenario matrix) pin
inside a single jit is: elementwise mul, div, sub, compares,
``where``/min/max, boolean logic, gathers/scatters; add/sub chains whose
operands are materialized (row folds, ``fori_loop`` carries); and mul by
an exact power of two feeding an add.

On a TPU v5e the kernels compile as they are, but the chip has no native
f64: XLA emulates it, and the results are not IEEE f64. On the chip the
mirrored floats differ from NumPy's in the last bits (up to ~6e-14
relative), while every decision mask matches; ``chip_smoke.py`` measures
this per field. A host decision taken on such a float therefore needs a
margin, never an exact tie (``world.COMPLETION_TOL``). See the tolerance
table in ``docs/ARCHITECTURE.md`` ("execution backends"). The Pallas ``quorum_compare`` digest path
compares in f32 by design (kernel contract) and is therefore gated to
payloads whose agreement/disagreement is far from the tolerance boundary
(the digest contract ``core/validator.py`` documents).

Shapes are padded to power-of-two buckets so jit retraces stay O(log n)
per call site. Padding lanes are neutralized (masks forced False, scatter
indices out of range with ``mode="drop"``), never observable.
"""
from __future__ import annotations

import os
from functools import partial
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import tracing

try:  # pragma: no cover - exercised only when jax is absent
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from jax import lax

    HAVE_JAX = True
except Exception:  # pragma: no cover
    jax = None  # type: ignore[assignment]
    jnp = None  # type: ignore[assignment]
    lax = None  # type: ignore[assignment]
    HAVE_JAX = False

BACKENDS = ("numpy", "jax")

_REPO_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for a chip run.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    path is set here; otherwise the cache lives at the fixed
    ``<repo>/.jax_cache`` (git-ignored; the path is part of the cache key,
    so it never moves). The staged jits each compile well under JAX's
    default one-second threshold, so the threshold is dropped to keep
    them. Returns the cache directory in use."""
    if not HAVE_JAX:
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def resolve_backend(backend: str) -> str:
    """Validate a ``backend=`` engine argument; ``"jax"`` requires jax."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "jax" and not HAVE_JAX:
        raise RuntimeError(
            "backend='jax' requested but jax is not importable in this "
            "environment; install jax[cpu] or use backend='numpy'"
        )
    return backend


def _bucket(n: int, lo: int = 8) -> int:
    """Next power-of-two ≥ max(n, lo): bounds jit retraces per call site."""
    b = lo
    while b < n:
        b <<= 1
    return b


if HAVE_JAX:

    # ------------------------------------------------------------------
    # dispatch kernels (core/batch_dispatch.candidate_rows)
    # ------------------------------------------------------------------

    @jax.jit
    def _k_elig(valid, target, start, host_id):
        # rotated-scan eligibility: slot j of the output corresponds to
        # feeder position (start + j) % n, exactly the scalar scan order
        v = jnp.roll(valid, -start)
        t = jnp.roll(target, -start)
        return v & ((t < 0) | (t == host_id))

    @jax.jit
    def _k_group_mask(g_ok_inv, hr_rep, host_hr_rep, kok):
        hr_ok = (hr_rep == -1) | (hr_rep == host_hr_rep)
        return g_ok_inv & hr_ok & kok

    @jax.jit
    def _k_score_terms(kvec, bal, prio, skips, w_kw, w_bal, w_pr, w_sk):
        # multiplies only — the jit boundary materializes each rounded
        # product before the sum stage can see it (FMA staging contract)
        return (
            w_kw * kvec,
            w_bal * bal,
            w_pr * prio,
            w_sk * jnp.minimum(skips, 5.0),
        )

    @jax.jit
    def _k_score_sum3(t_kw, t_pr, t_sk):
        return (t_kw + t_pr) + t_sk

    @jax.jit
    def _k_score_sum4(t_kw, t_bal, t_pr, t_sk):
        return ((t_kw + t_bal) + t_pr) + t_sk

    @jax.jit
    def _k_est_scaled(flop, pf, avail):
        est = jnp.where(pf > 0.0, flop / pf, jnp.inf)
        scaled = jnp.where(avail > 0.0, est / avail, jnp.inf)
        return est, scaled

    # ------------------------------------------------------------------
    # client kernels (core/batch_client slot-major greedy passes)
    # ------------------------------------------------------------------

    @jax.jit
    def _k_run_set_greedy(
        live_s, cu_s, wss_s, gpu_s, nci_s, u_stack, has_stack, nins_stack,
        ram0, rhs1, rhs2,
    ):
        # §6.1 greedy maximal feasible set, one rank per fori step; every
        # op is add/sub/compare/where on materialized carries — no muls,
        # so a single jit is bit-identical to the NumPy rank loop
        J, H = live_s.shape
        R = u_stack.shape[0]

        def body(r, carry):
            cap, cpu_cpu, cpu_all, ram_left, chosen = carry
            lv = live_s[r]
            cu = cu_s[r]
            gpu_r = gpu_s[r]
            feas = lv
            for i in range(R):
                u = u_stack[i, r]
                bad = (cap[i] < u - 1e-12) & (u > 0.0)
                feas = feas & ~bad
            feas = feas & ~((~gpu_r) & ((cpu_cpu + cu) > rhs1))
            feas = feas & ((cpu_all + cu) <= rhs2)
            feas = feas & (wss_s[r] <= ram_left)
            feas = feas | (nci_s[r] & lv)
            chosen = chosen.at[r].set(feas)
            for i in range(R):
                sel = feas & has_stack[i]
                cap = cap.at[i].set(jnp.where(sel, cap[i] - u_stack[i, r], cap[i]))
            cpu_cpu = jnp.where(feas & ~gpu_r, cpu_cpu + cu, cpu_cpu)
            cpu_all = jnp.where(feas, cpu_all + cu, cpu_all)
            ram_left = jnp.where(feas, ram_left - wss_s[r], ram_left)
            return cap, cpu_cpu, cpu_all, ram_left, chosen

        init = (
            nins_stack,
            jnp.zeros(H),
            jnp.zeros(H),
            ram0,
            jnp.zeros((J, H), dtype=bool),
        )
        return lax.fori_loop(0, J, body, init)[4]

    @jax.jit
    def _k_wrr_greedy(
        order_live, active, u_stack, ueps_stack, uzero_stack, wss_w,
        has_stack, nins_stack, ram,
    ):
        # WRR-order greedy under per-resource caps + RAM (the event-loop
        # feasibility pass). No muls; single jit is bit-identical.
        J, H = order_live.shape
        R = u_stack.shape[0]

        def body(k, carry):
            cap, ram_left, running = carry
            feas = order_live[k] & active
            for i in range(R):
                feas = feas & ((cap[i] >= ueps_stack[i, k]) | uzero_stack[i, k])
            feas = feas & (wss_w[k] <= ram_left)
            running = running.at[k].set(feas)
            for i in range(R):
                sel = feas & has_stack[i]
                cap = cap.at[i].set(jnp.where(sel, cap[i] - u_stack[i, k], cap[i]))
            ram_left = jnp.where(feas, ram_left - wss_w[k], ram_left)
            return cap, ram_left, running

        init = (nins_stack, ram, jnp.zeros((J, H), dtype=bool))
        cap, _, running = lax.fori_loop(0, J, body, init)
        return running, cap

    # ------------------------------------------------------------------
    # world kernels (core/world accrual + completion masks)
    # ------------------------------------------------------------------

    @partial(jax.jit, static_argnums=(0,))
    def _k_advance1(k, q_total, q_runtime, q_frac, q_running, idx, lane, dts):
        # gather + clamped accrual; the only arithmetic is sub/div/where,
        # none of which XLA can contract — single jit, bit-identical.
        # Only the first k queue rows (the occupied depth, power-of-two
        # bucketed by the caller) are gathered: rows >= k have
        # q_running == False everywhere, so skipping them is a no-op the
        # NumPy K-loop also takes.
        tot = q_total[:k, idx]
        run = q_runtime[:k, idx]
        frac = q_frac[:k, idx]
        m = q_running[:k, idx] & lane[None, :]
        rem = tot - run
        rem = jnp.where(rem < 0.0, 0.0, rem)
        d2 = jnp.broadcast_to(dts[None, :], tot.shape)
        eff = jnp.where(d2 < rem, d2, rem)
        eff = jnp.where(m, eff, 0.0)
        run2 = jnp.where(m, run + eff, run)
        denom = jnp.where(tot > 1e-9, tot, 1e-9)
        fr = run2 / denom
        fr = jnp.where(fr > 1.0, 1.0, fr)
        frac2 = jnp.where(m, fr, frac)
        return m, run2, frac2, eff

    @partial(jax.jit, static_argnums=(0,))
    def _k_products(k, q_cpu, q_weight, idx, eff):
        # the accrual charge products — staged alone so the downstream
        # accumulation jits see rounded (materialized) products, never an
        # LLVM-contracted FMA
        return eff * q_cpu[:k, idx], eff * q_weight[:k, idx]

    @jax.jit
    def _k_fold(m, binc, winc, busy_sub):
        # row-sequential accumulation in queue-row order, matching the
        # scalar/NumPy per-row loop; adds only
        Q = m.shape[0]

        def body(k, carry):
            busy, debit = carry
            busy = jnp.where(m[k], busy + binc[k], busy)
            debit = jnp.where(m[k], debit + winc[k], debit)
            return busy, debit

        init = (busy_sub, jnp.zeros(m.shape[1]))
        return lax.fori_loop(0, Q, body, init)

    @jax.jit
    def _k_gather_busy(busy, idx):
        return busy[idx]

    def _k_scatter(q_runtime, q_frac, busy, idx, run2, frac2, busy_sub):
        # pad lanes carry idx == n_cols (out of range): mode="drop";
        # row extent comes from run2's (k-sliced) shape
        k = run2.shape[0]
        q_runtime = q_runtime.at[:k, idx].set(run2, mode="drop")
        q_frac = q_frac.at[:k, idx].set(frac2, mode="drop")
        busy = busy.at[idx].set(busy_sub, mode="drop")
        return q_runtime, q_frac, busy

    _k_scatter = jax.jit(_k_scatter, donate_argnums=(0, 1, 2))

    @jax.jit
    def _k_completed(q_running, q_runtime, q_total, idx, counts):
        m = q_running[:, idx]
        run = q_runtime[:, idx]
        tot = q_total[:, idx]
        Q = m.shape[0]
        rowmask = jnp.arange(Q)[:, None] < counts[None, :]
        return m & (run >= tot - 1e-6) & rowmask  # world.COMPLETION_TOL

    @jax.jit
    def _k_col_upload(dev, host_vals, cols):
        return dev.at[:, cols].set(host_vals)

    @jax.jit
    def _k_vec_upload(dev, host_vals, cols):
        return dev.at[cols].set(host_vals)

    # ------------------------------------------------------------------
    # validation staging (fuzzy_digest_jax / quorum_group_codes)
    # ------------------------------------------------------------------

    @jax.jit
    def _k_stack_rows(*rows):
        return jnp.stack([r.reshape(-1) for r in rows])

    @jax.jit
    def _k_nan_rows(mat):
        return jnp.isnan(mat).any(axis=1)


# ----------------------------------------------------------------------
# dispatch wrappers
# ----------------------------------------------------------------------


def dispatch_elig(valid: np.ndarray, target: np.ndarray, start: int,
                  host_id: int) -> np.ndarray:
    """Rotated-scan eligibility mask on device; entry j refers to feeder
    position ``(start + j) % n`` (the caller's ``rot`` order)."""
    with tracing.span("boinc.dispatch.device", kernel="elig"):
        return np.asarray(_k_elig(valid, target, start, host_id))


def dispatch_group_mask(g_ok_inv: np.ndarray, hr_rep: np.ndarray,
                        host_hr_rep: np.ndarray, kok: np.ndarray) -> np.ndarray:
    M = len(g_ok_inv)
    P = _bucket(M)

    def pad(a):
        out = np.zeros(P, dtype=a.dtype)  # pad lanes: g_ok False → masked
        out[:M] = a
        return out

    with tracing.span("boinc.dispatch.device", kernel="group_mask"):
        return np.asarray(
            _k_group_mask(pad(g_ok_inv), pad(hr_rep), pad(host_hr_rep), pad(kok))
        )[:M]


def dispatch_scores(
    kvec: np.ndarray,
    bal: Optional[np.ndarray],
    prio: np.ndarray,
    skips: np.ndarray,
    flop: np.ndarray,
    pf: np.ndarray,
    avail: float,
    weights: Tuple[float, float, float, float],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """§6.4 base score + runtime estimates for the masked candidate set.

    Staged: the four weighted terms are products in one jit, the sum runs
    in a second jit in the NumPy engine's exact accumulation order
    (``t_kw (+ t_bal) + t_pr + t_sk``); the sparse locality / size-match
    adjustments stay host-side in the caller. Returns (scores, est, scaled).
    """
    w_kw, w_bal, w_pr, w_sk = weights
    M = kvec.shape[0]
    P = _bucket(M)

    def pad(a):
        out = np.zeros(P, dtype=np.float64)
        out[:M] = a
        return out

    has_bal = bal is not None
    with tracing.span("boinc.dispatch.device", kernel="scores"):
        t_kw, t_bal, t_pr, t_sk = _k_score_terms(
            pad(kvec), pad(bal) if has_bal else np.zeros(P), pad(prio),
            pad(skips), w_kw, w_bal, w_pr, w_sk,
        )
        if has_bal:
            scores = _k_score_sum4(t_kw, t_bal, t_pr, t_sk)
        else:
            scores = _k_score_sum3(t_kw, t_pr, t_sk)
        est, scaled = _k_est_scaled(pad(flop), pad(pf), avail)
        return (
            np.asarray(scores)[:M].copy(),
            np.asarray(est)[:M].copy(),
            np.asarray(scaled)[:M].copy(),
        )


# ----------------------------------------------------------------------
# client wrappers
# ----------------------------------------------------------------------


def run_set_greedy(
    live_s: np.ndarray,
    cu_s: np.ndarray,
    wss_s: np.ndarray,
    gpu_s: np.ndarray,
    nci_s: np.ndarray,
    u_s: Dict,
    has: Dict,
    nins: Dict,
    ram0: np.ndarray,
    rhs1: np.ndarray,
    rhs2: np.ndarray,
) -> np.ndarray:
    """JAX run of ``BatchClientEngine._run_set_pass``'s greedy rank loop.

    ``u_s``/``has``/``nins`` are keyed by the non-CPU resource types in the
    snapshot's iteration order (the order the NumPy loop visits them).
    ``ram0`` is the host-side ``ram * ram_frac`` product — computed by the
    caller in NumPy so the in-loop RAM subtractions never share a jit with
    the multiply. Returns the chosen [J, H] mask.
    """
    J, H = live_s.shape
    JP, HP = _bucket(J), _bucket(H)
    rts = list(u_s)
    R = len(rts)

    def pad2(a, dtype=np.float64):
        out = np.zeros((JP, HP), dtype=dtype)
        out[:J, :H] = a
        return out

    def pad1(a, dtype=np.float64):
        out = np.zeros(HP, dtype=dtype)
        out[:H] = a
        return out

    u_stack = np.zeros((R, JP, HP))
    has_stack = np.zeros((R, HP), dtype=bool)
    nins_stack = np.zeros((R, HP))
    for i, rt in enumerate(rts):
        u_stack[i, :J, :H] = u_s[rt]
        has_stack[i, :H] = has[rt]
        nins_stack[i, :H] = nins[rt]

    chosen = _k_run_set_greedy(
        pad2(live_s, bool), pad2(cu_s), pad2(wss_s), pad2(gpu_s, bool),
        pad2(nci_s, bool), u_stack, has_stack, nins_stack,
        pad1(ram0), pad1(rhs1), pad1(rhs2),
    )
    return np.asarray(chosen)[:J, :H]


class WRRGreedyContext:
    """Device-resident WRR inputs for one ``_wrr_raw`` call: the static
    per-event arrays (usage, thresholds, caps, RAM) are uploaded once and
    each event's greedy pass runs as one jit over them."""

    def __init__(self, s, u_w: Dict, u_eps: Dict, u_zero: Dict,
                 wss_w: np.ndarray) -> None:
        J, H = s.J, s.H
        self.J, self.H = J, H
        self.JP, self.HP = _bucket(J), _bucket(H)
        self.rtypes = list(s.rtypes)
        R = len(self.rtypes)

        u_stack = np.zeros((R, self.JP, self.HP))
        ueps_stack = np.full((R, self.JP, self.HP), -1e-12)
        uzero_stack = np.ones((R, self.JP, self.HP), dtype=bool)
        has_stack = np.zeros((R, self.HP), dtype=bool)
        nins_stack = np.zeros((R, self.HP))
        for i, rt in enumerate(self.rtypes):
            u_stack[i, :J, :H] = u_w[rt]
            ueps_stack[i, :J, :H] = u_eps[rt]
            uzero_stack[i, :J, :H] = u_zero[rt]
            has_stack[i, :H] = s.has[rt]
            nins_stack[i, :H] = s.nins[rt]
        wss = np.zeros((self.JP, self.HP))
        wss[:J, :H] = wss_w
        ram = np.zeros(self.HP)
        ram[:H] = s.ram

        self._u = jnp.asarray(u_stack)
        self._ueps = jnp.asarray(ueps_stack)
        self._uzero = jnp.asarray(uzero_stack)
        self._has = jnp.asarray(has_stack)
        self._nins = jnp.asarray(nins_stack)
        self._wss = jnp.asarray(wss)
        self._ram = jnp.asarray(ram)

    def greedy(self, order_live: np.ndarray, active: np.ndarray):
        """One greedy maximal-set pass; returns (running [J,H], caps dict)."""
        J, H = self.J, self.H
        ol = np.zeros((self.JP, self.HP), dtype=bool)
        ol[:J, :H] = order_live
        act = np.zeros(self.HP, dtype=bool)
        act[:H] = active
        running, cap = _k_wrr_greedy(
            ol, act, self._u, self._ueps, self._uzero, self._wss,
            self._has, self._nins, self._ram,
        )
        running = np.asarray(running)[:J, :H]
        cap_np = np.asarray(cap)[:, :H]
        return running, {rt: cap_np[i].copy() for i, rt in enumerate(self.rtypes)}


# ----------------------------------------------------------------------
# world device mirror (core/world.HostArrays, backend="jax")
# ----------------------------------------------------------------------


class WorldDeviceMirror:
    """Device-resident mirrors of the accrual-relevant ``HostArrays``
    columns, with a dirty-range upload contract.

    Upload direction (host → device): mutation hooks mark the touched
    dense slot (``HostArrays._touch``); before each device pass only the
    dirty slots' columns are re-uploaded. Array growth or compaction
    reallocates host storage, so a shape change forces a full re-upload
    (``all_dirty``). Compute direction: the accrual pass updates
    ``q_runtime``/``q_frac``/``busy`` on device with donated buffers and
    writes the touched slice back to the host arrays, so host and device
    stay equal after every pass (asserted by the dirty-upload regression
    tests).
    """

    _COLS = ("q_total", "q_runtime", "q_frac", "q_weight")

    def __init__(self) -> None:
        self._shape: Optional[Tuple[int, int]] = None
        self.all_dirty = True
        self.dirty: set = set()
        self.q_total = None
        self.q_runtime = None
        self.q_frac = None
        self.q_running = None
        self.q_weight = None
        self.q_cpu = None
        self.busy = None

    # -- upload ---------------------------------------------------------

    def mark(self, slot: int) -> None:
        self.dirty.add(slot)

    def sync(self, world) -> None:
        """Apply the dirty-range upload contract against ``world``."""
        from .types import ResourceType

        cpu_u = world.q_usage[ResourceType.CPU]
        shape = cpu_u.shape
        if self._shape != shape or self.all_dirty:
            self.q_total = jnp.asarray(world.q_total)
            self.q_runtime = jnp.asarray(world.q_runtime)
            self.q_frac = jnp.asarray(world.q_frac)
            self.q_running = jnp.asarray(world.q_running)
            self.q_weight = jnp.asarray(world.q_weight)
            self.q_cpu = jnp.asarray(cpu_u)
            self.busy = jnp.asarray(world.busy)
            self._shape = shape
            self.all_dirty = False
            self.dirty.clear()
            return
        if not self.dirty:
            return
        # bucketed column count; pad lanes repeat the last dirty column, so
        # they rewrite it with its own (identical) values
        n_dirty = len(self.dirty)
        cols = np.empty(_bucket(n_dirty), dtype=np.int64)
        cols[:n_dirty] = sorted(self.dirty)
        cols[n_dirty:] = cols[n_dirty - 1]
        cj = jnp.asarray(cols)
        self.q_total = _k_col_upload(self.q_total, world.q_total[:, cols], cj)
        self.q_runtime = _k_col_upload(self.q_runtime, world.q_runtime[:, cols], cj)
        self.q_frac = _k_col_upload(self.q_frac, world.q_frac[:, cols], cj)
        self.q_running = _k_col_upload(self.q_running, world.q_running[:, cols], cj)
        self.q_weight = _k_col_upload(self.q_weight, world.q_weight[:, cols], cj)
        self.q_cpu = _k_col_upload(self.q_cpu, cpu_u[:, cols], cj)
        self.busy = _k_vec_upload(self.busy, world.busy[cols], cj)
        self.dirty.clear()

    # -- compute --------------------------------------------------------

    def advance(self, world, sub: np.ndarray, dts: np.ndarray):
        """Device accrual pass over the active host slots ``sub``; returns
        the per-slot REC debit totals and the touched mask, after writing
        the updated runtime/fraction/busy columns back to ``world``."""
        self.sync(world)
        S = len(sub)
        P = _bucket(S)
        n_cols = self._shape[1]
        # occupied queue depth, bucketed: rows >= K are all-False q_running
        # for the active slots, so the device pass skips them just as the
        # NumPy K-loop does
        K = min(_bucket(int(world.q_count[sub].max()), lo=1), self._shape[0])
        idx = np.full(P, n_cols, dtype=np.int64)  # out-of-range pad → drop
        idx[:S] = sub
        lane = np.zeros(P, dtype=bool)
        lane[:S] = True
        dts_p = np.zeros(P)
        dts_p[:S] = dts
        idx_j = jnp.asarray(idx)

        m, run2, frac2, eff = _k_advance1(
            K, self.q_total, self.q_runtime, self.q_frac, self.q_running,
            idx_j, jnp.asarray(lane), jnp.asarray(dts_p),
        )
        binc, winc = _k_products(K, self.q_cpu, self.q_weight, idx_j, eff)
        busy_sub, debit = _k_fold(m, binc, winc, _k_gather_busy(self.busy, idx_j))
        self.q_runtime, self.q_frac, self.busy = _k_scatter(
            self.q_runtime, self.q_frac, self.busy, idx_j, run2, frac2, busy_sub,
        )

        m_np = np.asarray(m)[:, :S]
        world.q_runtime[:K, sub] = np.asarray(run2)[:, :S]
        world.q_frac[:K, sub] = np.asarray(frac2)[:, :S]
        world.busy[sub] = np.asarray(busy_sub)[:S]
        return np.asarray(debit)[:S].copy(), m_np.any(axis=0)

    def completed_mask(self, world, idx: np.ndarray,
                       counts: np.ndarray) -> np.ndarray:
        """Completion mask over the device accrual matrix for slots ``idx``
        (rows ≥ each host's queue count masked out), downloaded as bool."""
        self.sync(world)
        S = len(idx)
        P = _bucket(S)
        n_cols = self._shape[1]
        ip = np.full(P, n_cols - 1, dtype=np.int64)
        ip[:S] = idx
        cp = np.zeros(P, dtype=np.int64)  # pad lanes: count 0 → all rows masked
        cp[:S] = counts
        out = _k_completed(
            self.q_running, self.q_runtime, self.q_total,
            jnp.asarray(ip), jnp.asarray(cp),
        )
        return np.asarray(out)[:, :S]


# ----------------------------------------------------------------------
# Pallas quorum_compare digest routing (core/batch_validate, backend="jax")
# ----------------------------------------------------------------------


def quorum_group_codes(mat, rtol: float, atol: float) -> np.ndarray:
    """Group codes for a homogeneous (n, d) float payload matrix via the
    ``kernels/quorum_compare`` Pallas kernel (compiled on the chip,
    interpret mode on the CPU backend only). ``mat`` holds one job's
    results: codes are consistent within a job only, and the engine never
    compares them across jobs (its quorum grouping keys on ``(job, code)``).
    It is a matrix on the device, as ``fuzzy_digest_jax`` stages it, or a
    host matrix, which is put on the device once, here: no comparison
    uploads a row.

    Greedy first-match grouping: row i joins the first group whose
    representative it agrees with (kernel verdict ``n_bad == 0`` under the
    comparator's tolerances), else it founds a new group. Under the digest
    contract (replicas either agree well within tolerance or disagree far
    outside it) this partition equals the scalar comparator's greedy
    pairwise grouping. The kernel compares in f32 — another reason the
    far-from-boundary contract is load-bearing. NaN-carrying rows match
    nothing (kernel predicate is False for NaN, which would read as
    agreement) and get unique sentinels, mirroring ``_fuzzy_digest_*``;
    the chip flags them, and the flags are read back in one sync.
    """
    from ..kernels.quorum_compare.ops import quorum_compare
    from .validator import _nan_sentinel

    n = mat.shape[0]
    codes = np.zeros(n, dtype=np.int64)
    reps: List[int] = []
    with tracing.span("boinc.validate.stack"):
        if not isinstance(mat, jax.Array):
            mat = jax.device_put(mat)
        nan_rows = np.asarray(_k_nan_rows(mat))
    for i in range(n):
        if nan_rows[i]:
            codes[i] = _nan_sentinel()
            continue
        assigned = False
        for g, r in enumerate(reps):
            with tracing.span("boinc.validate.pair"):
                # the call's host side: slicing both rows out of the
                # device matrix, and the launch. The kernel is waited for
                # in int(n_bad).
                with tracing.span("boinc.validate.upload"):
                    n_bad, _ = quorum_compare(mat[i], mat[r], rtol=rtol, atol=atol)
                agree = int(n_bad) == 0
            if agree:
                codes[i] = g
                assigned = True
                break
        if not assigned:
            reps.append(i)
            codes[i] = len(reps) - 1
    return codes


def _device_matrix(outputs: Sequence):
    """One job's payloads as an (n, size) matrix on the device, or None
    unless they are homogeneous floats (``validator._homogeneous``). Each
    payload is put on the device once, as it is, in a
    ``boinc.validate.put`` span; the chip flattens and stacks them. No
    host copy of a payload is made."""
    from .validator import _homogeneous

    if not (_homogeneous(outputs) and outputs[0].dtype.kind == "f"):
        return None
    rows = []
    for o in outputs:
        with tracing.span("boinc.validate.put"):
            rows.append(jax.device_put(o))
    return _k_stack_rows(*rows)


def fuzzy_digest_jax(base, rtol: float, atol: float):
    """Wrap a fuzzy comparator's digest hook: homogeneous float tensor
    payloads route through the Pallas kernel grouping, one job at a time,
    each job's payloads put on the device once; everything else (plain
    floats, mixed payloads) falls through to ``base`` in one call.

    ``job_off`` gives the row offsets of each job's results in ``outputs``
    (``job_off[k]:job_off[k + 1]`` is job k); without it ``outputs`` is one
    job. The hook is marked ``pairwise`` so the engine passes them."""

    def fn(outputs: Sequence, job_off: Optional[Sequence[int]] = None) -> np.ndarray:
        if len(outputs) < 2 or not isinstance(outputs[0], np.ndarray):
            return base(outputs)
        bounds = (0, len(outputs)) if job_off is None else job_off
        codes = np.zeros(len(outputs), dtype=np.int64)
        rest: List[int] = []
        for a, b in zip(bounds[:-1], bounds[1:]):
            with tracing.span("boinc.validate.stack"):
                mat = _device_matrix(outputs[a:b])
            if mat is None:
                rest.extend(range(a, b))
                continue
            with tracing.span("boinc.validate.group", rows=b - a):
                codes[a:b] = quorum_group_codes(mat, rtol, atol)
        if rest:
            codes[rest] = base([outputs[k] for k in rest])
        return codes

    fn.pairwise = True
    return fn
