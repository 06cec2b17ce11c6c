"""Blockwise online-softmax attention (FlashAttention) as Pallas TPU
kernels — forward *and* backward.

TPU adaptation (vs the CUDA original): the (q-block x kv-block) tile walk
is expressed as a 3-D sequential grid — the innermost axis revisits the
same output block, so running statistics / accumulators live in VMEM
scratch that persists across grid steps (TPU grids are sequential, unlike
CUDA thread blocks).  Block shapes are multiples of (128, 128) at
production sizes so the score/value products map directly onto the
128x128 MXU; GQA is handled by an index-map that maps each query-head
block onto its kv-head group, so no repeated-KV materialization happens
in HBM.

Backward follows the FlashAttention-2 decomposition: the forward keeps
only the per-row logsumexp ``L = m + log l`` as a residual, the backward
recomputes the score tiles and uses

    P   = exp(S - L)
    dV  = P^T dO
    dP  = dO V^T
    dS  = P * (dP - D),   D = rowsum(dO * O)
    dQ  = scale * dS K        (accumulated over kv blocks)
    dK  = scale * dS^T Q      (accumulated over q blocks)

split into two kernels so each output block is owned by exactly one
innermost accumulation loop: ``dq`` iterates kv blocks innermost,
``dk/dv`` iterates q blocks innermost.  dK/dV are produced per *query*
head; the wrapper sums over the GQA group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import default_interpret

NEG_INF = -1e30


def _tile_mask(iq, ik, *, block_q, block_k, causal, window, sk, shape):
    """(bq, bk) bool mask for score tile (iq, ik): kv padding + causal +
    sliding window, from absolute positions."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = k_pos < sk                                  # kv padding
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


# --------------------------------------------------------------- forward
def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                     l_ref, *, scale: float, causal: bool, window, sq: int,
                     sk: int, block_q: int, block_k: int, n_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    mask = _tile_mask(iq, ik, block_q=block_q, block_k=block_k,
                      causal=causal, window=window, sk=sk, shape=s.shape)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                             # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # logsumexp residual for the backward pass, kept as a (bq, 1)
        # column: the block's last dim then equals the array's, which is
        # the layout Mosaic accepts for a per-row vector
        lse_ref[0] = m_ref[...] + jnp.log(l)


def flash_attention_fwd_kernel(q, k, v, *, causal: bool, window, sq: int,
                               sk: int, block_q: int, block_k: int,
                               interpret: bool | None = None):
    """q: (BH, Sq_pad, hd); k/v: (BKH, Sk_pad, hd).  Sq_pad % block_q == 0,
    Sk_pad % block_k == 0.  BH % BKH == 0 (GQA).  Returns (out, lse) with
    lse: (BH, Sq_pad, 1) f32.

    ``interpret=None`` auto-detects: compiled on TPU, interpret elsewhere.
    """
    if interpret is None:
        interpret = default_interpret()
    BH, sq_pad, hd = q.shape
    BKH, sk_pad, _ = k.shape
    n_rep = BH // BKH
    nq = sq_pad // block_q
    nk = sk_pad // block_k
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _attn_fwd_kernel, scale=scale, causal=causal, window=window,
        sq=sq, sk=sk, block_q=block_q, block_k=block_k, n_kv=nk)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // n_rep, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // n_rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, sq_pad, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, sq_pad, 1), jnp.float32),
        ],
        scratch_shapes=_scratch(block_q, hd),
        interpret=interpret,
    )(q, k, v)


def _scratch(block_q, hd):
    from jax.experimental.pallas import tpu as pltpu
    return [
        pltpu.VMEM((block_q, hd), jnp.float32),   # acc
        pltpu.VMEM((block_q, 1), jnp.float32),    # running max
        pltpu.VMEM((block_q, 1), jnp.float32),    # normalizer
    ]


# -------------------------------------------------------------- backward
def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, dq_acc, *, scale: float, causal: bool,
                        window, sk: int, block_q: int, block_k: int,
                        n_kv: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    mask = _tile_mask(iq, ik, block_q=block_q, block_k=block_k,
                      causal=causal, window=window, sk=sk, shape=s.shape)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0])                        # (bq, bk)

    do = do_ref[0].astype(jnp.float32)                 # (bq, hd)
    dp = jax.lax.dot_general(                          # dO V^T: (bq, bk)
        do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())))
    ds = p * (dp - delta_ref[0])                       # (bq, bk)
    dq_acc[...] += jax.lax.dot_general(                # dS K: (bq, hd)
        ds, k, (((1,), (0,)), ((), ()))) * scale

    @pl.when(ik == n_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                         causal: bool, window, sk: int, block_q: int,
                         block_k: int, n_q: int):
    ik = pl.program_id(1)          # kv block owns the output
    iq = pl.program_id(2)          # innermost: accumulate over q blocks

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    mask = _tile_mask(iq, ik, block_q=block_q, block_k=block_k,
                      causal=causal, window=window, sk=sk, shape=s.shape)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0])                        # (bq, bk)

    do = do_ref[0].astype(jnp.float32)                 # (bq, hd)
    dv_acc[...] += jax.lax.dot_general(                # P^T dO: (bk, hd)
        p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())))
    ds = p * (dp - delta_ref[0])                       # (bq, bk)
    dk_acc[...] += jax.lax.dot_general(                # dS^T Q: (bk, hd)
        ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ()))) * scale

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd_kernel(q, k, v, do, lse, delta, *, causal: bool,
                               window, sk: int, block_q: int, block_k: int,
                               interpret: bool | None = None):
    """Backward pass.  q/do: (BH, Sq_pad, hd); k/v: (BKH, Sk_pad, hd);
    lse/delta: (BH, Sq_pad, 1) f32 (delta = rowsum(dO * O)).

    Returns (dq (BH, Sq_pad, hd), dk, dv (BH, Sk_pad, hd)) — dk/dv at
    *query*-head granularity; the caller reduces over the GQA group.
    All three are f32 (they are gradient accumulators).
    """
    if interpret is None:
        interpret = default_interpret()
    BH, sq_pad, hd = q.shape
    BKH, sk_pad, _ = k.shape
    n_rep = BH // BKH
    nq = sq_pad // block_q
    nk = sk_pad // block_k
    scale = 1.0 / (hd ** 0.5)
    from jax.experimental.pallas import tpu as pltpu

    dq_kernel = functools.partial(
        _attn_bwd_dq_kernel, scale=scale, causal=causal, window=window,
        sk=sk, block_q=block_q, block_k=block_k, n_kv=nk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // n_rep, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // n_rep, j, 0)),
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, sq_pad, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _attn_bwd_dkv_kernel, scale=scale, causal=causal, window=window,
        sk=sk, block_q=block_q, block_k=block_k, n_q=nq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b // n_rep, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b // n_rep, j, 0)),
            pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, sk_pad, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, sk_pad, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
