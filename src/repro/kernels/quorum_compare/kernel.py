"""Fuzzy quorum comparison as a Pallas TPU kernel.

This is the hardware adaptation of the paper's validator hot loop (§3.4):
at gradient scale, deciding whether two replicas' results "agree within
tolerances" is a bandwidth-bound reduction over billions of elements. The
kernel counts out-of-tolerance elements (|a-b| > atol + rtol*|b|) per block
and accumulates into two SMEM scalars — one pass over both operands, no
giant bool intermediates in HBM.

Mosaic accepts only 32-bit types, so the caller passes f32 operands and
the ``pallas_call`` is traced with x64 off (index maps and grid indices
stay int32) even when the surrounding process runs with x64 on.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quorum_kernel(a_ref, b_ref, count_ref, sq_ref, *, rtol: float, atol: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        count_ref[0, 0] = jnp.float32(0.0)
        sq_ref[0, 0] = jnp.float32(0.0)

    a = a_ref[...]
    b = b_ref[...]
    diff = jnp.abs(a - b)
    bad = diff > (atol + rtol * jnp.abs(b))
    count_ref[0, 0] += jnp.sum(bad.astype(jnp.float32))
    sq_ref[0, 0] += jnp.sum(diff * diff)


def quorum_compare_kernel(
    a: jax.Array,  # (rows, d) float32 — flattened payload
    b: jax.Array,
    *,
    rtol: float = 1e-5,
    atol: float = 1e-8,
    block_rows: int = 1024,
    interpret: bool = False,
):
    rows, d = a.shape
    assert rows % block_rows == 0
    assert a.dtype == b.dtype == jnp.float32, (a.dtype, b.dtype)
    kernel = functools.partial(_quorum_kernel, rtol=rtol, atol=atol)
    kwargs: dict[str, Any] = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        )
    with jax.enable_x64(False):
        count, sq = pl.pallas_call(
            kernel,
            grid=(rows // block_rows,),
            in_specs=[
                pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
                pl.BlockSpec((block_rows, d), lambda r: (r, 0)),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, 1), jnp.float32),
                jax.ShapeDtypeStruct((1, 1), jnp.float32),
            ],
            interpret=interpret,
            name="quorum_compare",
            **kwargs,
        )(a, b)
    return count[0, 0], sq[0, 0]
