"""Jitted wrapper: flattens arbitrary payload pytrees and reports the
out-of-tolerance fraction + L2 distance — the fuzzy comparator the grid
runtime's validator uses on gradient/logit replicas."""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import quorum_compare_kernel

_LANES = 256


@functools.partial(jax.jit, static_argnames=("rtol", "atol", "interpret"))
def quorum_compare(
    a: jax.Array,
    b: jax.Array,
    *,
    rtol: float = 1e-5,
    atol: float = 1e-8,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (n_bad, sum_sq_diff) over flattened inputs, compared in f32
    (the kernel contract). ``interpret=None`` runs the Pallas interpreter
    on the CPU backend only; on any other backend the kernel is compiled."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    af = a.reshape(-1).astype(jnp.float32)
    bf = b.reshape(-1).astype(jnp.float32)
    n = af.shape[0]
    pad = (-n) % _LANES
    if pad:
        af = jnp.pad(af, (0, pad))
        bf = jnp.pad(bf, (0, pad))
    rows = af.shape[0] // _LANES
    af = af.reshape(rows, _LANES)
    bf = bf.reshape(rows, _LANES)
    br = min(1024, rows)
    rpad = (-rows) % br
    if rpad:
        af = jnp.pad(af, ((0, rpad), (0, 0)))
        bf = jnp.pad(bf, ((0, rpad), (0, 0)))
    return quorum_compare_kernel(
        af, bf, rtol=rtol, atol=atol, block_rows=br, interpret=interpret
    )


def tree_quorum_agree(
    tree_a: Any,
    tree_b: Any,
    *,
    rtol: float = 1e-4,
    atol: float = 1e-6,
    max_bad_fraction: float = 0.0,
    interpret: Optional[bool] = None,
) -> bool:
    """Pytree-level fuzzy agreement — the validator comparator (§3.4)."""
    la = jax.tree_util.tree_leaves(tree_a)
    lb = jax.tree_util.tree_leaves(tree_b)
    if len(la) != len(lb):
        return False
    bad = 0.0
    total = 0
    for xa, xb in zip(la, lb):
        xa = jnp.asarray(xa)
        xb = jnp.asarray(xb)
        if xa.shape != xb.shape:
            return False
        nb, _ = quorum_compare(xa, xb, rtol=rtol, atol=atol, interpret=interpret)
        bad += float(nb)
        total += xa.size
    if total == 0:
        return True
    return (bad / total) <= max_bad_fraction
