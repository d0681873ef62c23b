"""Shared device probe and tiling helper for the Pallas kernels.

Every kernel entry point auto-selects ``interpret`` mode when the caller
passes ``None``: compiled Mosaic on TPU, the Pallas interpreter on the
CPU backend (tests, CI).  Any other backend is an error rather than a
silent interpreter run, so a run that meant to use an accelerator never
times the interpreter by accident.  The probe is cached because
``jax.default_backend()`` walks the backend registry on every call.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# largest row tile a kernel grid step works on: keeps every in-kernel
# uint32 temporary of a 224/256-wide image row block well inside VMEM
MAX_ROW_TILE = 64


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """False on TPU (compiled Mosaic), True on CPU (interpreter).

    Cached for the process lifetime: the default backend cannot change
    after the first JAX computation anyway.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and interpret on CPU; the "
        f"default JAX backend is {backend!r}")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> the cached probe; explicit flags pass through."""
    return default_interpret() if interpret is None else bool(interpret)


def row_tile(n_rows: int, align: int) -> int:
    """Rows per grid step: the largest divisor of ``n_rows`` that is a
    multiple of ``align`` (the dtype's sublane tiling: 8 for 32-bit, 32
    for 8-bit) and at most :data:`MAX_ROW_TILE`; the whole extent when
    none exists (a block equal to the full dimension is always legal)."""
    for rows in range(MAX_ROW_TILE - MAX_ROW_TILE % align, 0, -align):
        if n_rows % rows == 0:
            return rows
    return n_rows


def row_block_iota(out_ref):
    """(row, lane) int32 indices of this grid step's ``(1, rows, lanes)``
    output block, for a grid of (sample, row tile)."""
    rows, lanes = out_ref.shape[1:]
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) \
        + pl.program_id(1) * rows
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)
    return row, lane
