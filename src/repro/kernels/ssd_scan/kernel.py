"""Mamba2 SSD chunk scan as a Pallas TPU kernel.

TPU adaptation of the CUDA SSD kernels (arXiv:2405.21060): the sequential
chunk recurrence maps onto the innermost grid axis — grid =
``(batch, heads, n_chunks)`` — with the inter-chunk SSM state ``(hp, N)``
of one head carried in VMEM scratch across grid steps (TPU grids are
sequential; no inter-block synchronization is needed, unlike the
stream-K-style CUDA decomposition).  Intra-chunk work is dense
(Q x Q) MXU matmuls under a causal decay mask.

Every operand is laid out head-major and chunked, ``(..., Q, width)``,
so each block's last two dimensions equal the array's: the layout rule
Mosaic enforces holds for any head count, chunk and state width.  The
per-step ``dt`` and ``dt * A`` vectors come in twice, as ``(Q, 2)``
columns and ``(2, Q)`` rows, so the kernel never transposes a vector;
the cumulative log decay is a masked reduction over a (Q x Q) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref, hout_ref,
                h_ref, *, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)     # (Q, hp)
    dtc = dtc_ref[0, 0, 0]                     # (Q, 2): dt, dt*A columns
    dtr = dtr_ref[0, 0, 0]                     # (2, Q): the same as rows
    dt_col, a_col = dtc[:, 0:1], dtc[:, 1:2]
    dt_row, a_row = dtr[0:1, :], dtr[1:2, :]
    Bm = b_ref[0, 0, 0].astype(jnp.float32)    # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)    # (Q, N)
    h = h_ref[...]                             # (hp, N) f32

    Q = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = rows >= cols
    # cumulative log decay la[i] = sum_{j<=i} dt_j A, as column and row
    la_col = jnp.sum(jnp.where(tri, a_row, 0.0), axis=1, keepdims=True)
    la_row = jnp.sum(jnp.where(rows <= cols, a_col, 0.0), axis=0,
                     keepdims=True)
    la_last = la_col[Q - 1:Q, :]               # (1, 1)

    # intra-chunk: masked (Q x Q) — mask the exponent so the unused
    # upper triangle never overflows
    G = _dot(Cm, Bm, ((1,), (1,)))             # C B^T: (Q, Q)
    M = G * jnp.exp(jnp.where(tri, la_col - la_row, -jnp.inf)) * dt_row
    y = _dot(M, x, ((1,), (0,)))               # (Q, hp)

    # inter-chunk contribution from the carried state
    y += _dot(Cm * jnp.exp(la_col), h, ((1,), (1,)))

    # state update
    decay_out = jnp.exp(la_last - la_col) * dt_col            # (Q, 1)
    h_ref[...] = (jnp.exp(la_last) * h
                  + _dot(x * decay_out, Bm, ((0,), (0,))))    # (hp, N)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...]


def ssd_scan_kernel(x, dtc, dtr, B, C, *, interpret: bool | None = None):
    """x: (Bs, nh, nc, Q, hp); dtc: (Bs, nh, nc, Q, 2) holding dt and
    dt*A; dtr: (Bs, nh, nc, 2, Q), the same transposed; B/C:
    (Bs, g, nc, Q, N), shared by the nh // g heads of a group.
    Returns (y with x's shape, h_final (Bs, nh, hp, N) f32).
    ``interpret=None`` auto-detects the backend (compiled on TPU,
    interpret elsewhere)."""
    if interpret is None:
        interpret = default_interpret()
    Bs, nh, nc, Q, hp = x.shape
    g, N = B.shape[1], B.shape[-1]
    rep = nh // g

    kernel = functools.partial(_ssd_kernel, n_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=(Bs, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, hp), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 2), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 2, Q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N),
                         lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N),
                         lambda b, h, c: (b, h // rep, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, hp), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, hp, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((Bs, nh, hp, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hp, N), jnp.float32)],
        interpret=interpret,
    )(x, dtc, dtr, B, C)
