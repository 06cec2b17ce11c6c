"""Public jit'd wrapper: layout handling (B,S,H,hd) -> (B*H,S,hd), padding
to block multiples, GQA head grouping, block-size selection — and the
``jax.custom_vjp`` that makes the Pallas path trainable: forward runs the
Pallas forward kernel (keeping the per-row logsumexp as the only
residual), backward runs the FlashAttention-2 backward kernels and
reduces dK/dV over the GQA group."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from repro.kernels.flash_attention.kernel import (flash_attention_bwd_kernel,
                                                  flash_attention_fwd_kernel)


def _pick_block(s: int, preferred: int = 256) -> int:
    for b in (preferred, 128, 64, 32, 16, 8):
        if s % b == 0 or s > b:
            return b
    return s


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool | None = None):
    """q: (B, Sq, H, hd); k/v: (B, Sk, Kh, hd) -> (B, Sq, H, hd).

    Differentiable: ``jax.grad`` through this op runs the Pallas backward
    kernels (see ``kernel.py``), so the Pallas path serves training as
    well as prefill.

    ``interpret`` selects the Pallas execution mode: ``None`` (default)
    auto-detects the backend — compiled on TPU, interpret mode (kernel
    body on CPU, for validation) everywhere else.  Pass an explicit bool
    to override.
    """
    if interpret is None:
        interpret = default_interpret()
    return _flash_attention(q, k, v, causal, window, block_q, block_k,
                            interpret)


def _layout(q, k, v, block_q, block_k):
    """(B,S,H,hd) -> padded (B*H, S_pad, hd) layout + geometry."""
    B, Sq, H, hd = q.shape
    Sk, Kh = k.shape[1], k.shape[2]
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    sq_pad = -(-Sq // block_q) * block_q
    sk_pad = -(-Sk // block_k) * block_k

    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * Kh, Sk, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * Kh, Sk, hd)
    qf = jnp.pad(qf, ((0, 0), (0, sq_pad - Sq), (0, 0)))
    kf = jnp.pad(kf, ((0, 0), (0, sk_pad - Sk), (0, 0)))
    vf = jnp.pad(vf, ((0, 0), (0, sk_pad - Sk), (0, 0)))
    return qf, kf, vf, (B, Sq, Sk, H, Kh, hd, block_q, block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_vjp(q, k, v, causal, window, block_q, block_k,
                         interpret):
    out, _ = _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    qf, kf, vf, geom = _layout(q, k, v, block_q, block_k)
    B, Sq, Sk, H, Kh, hd, bq, bk = geom
    outf, lse = flash_attention_fwd_kernel(
        qf, kf, vf, causal=causal, window=window, sq=Sq, sk=Sk,
        block_q=bq, block_k=bk, interpret=interpret)
    out = jnp.moveaxis(outf[:, :Sq].reshape(B, H, Sq, hd), 1, 2)
    # residual is `out`, not `outf`: downstream autodiff keeps `out`
    # alive anyway (it feeds the wo matmul), so no duplicate
    # activation-sized buffer survives to the backward pass
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    qf, kf, vf, geom = _layout(q, k, v, block_q, block_k)
    B, Sq, Sk, H, Kh, hd, bq, bk = geom
    sq_pad = lse.shape[1]

    def to_padded(x):
        xf = jnp.moveaxis(x, 2, 1).reshape(B * H, Sq, hd)
        return jnp.pad(xf, ((0, 0), (0, sq_pad - Sq), (0, 0)))

    gf = to_padded(g)
    # D = rowsum(dO * O): padded rows have dO = 0, so D = 0 there
    delta = jnp.sum(gf.astype(jnp.float32)
                    * to_padded(out).astype(jnp.float32), axis=-1,
                    keepdims=True)
    dqf, dkf, dvf = flash_attention_bwd_kernel(
        qf, kf, vf, gf, lse, delta, causal=causal, window=window, sk=Sk,
        block_q=bq, block_k=bk, interpret=interpret)

    dq = jnp.moveaxis(dqf[:, :Sq].reshape(B, H, Sq, hd), 1, 2)
    # dk/dv come back per query head: reduce over the GQA group
    n_rep = H // Kh
    dk = dkf[:, :Sk].reshape(B, Kh, n_rep, Sk, hd).sum(axis=2)
    dv = dvf[:, :Sk].reshape(B, Kh, n_rep, Sk, hd).sum(axis=2)
    dk = jnp.moveaxis(dk, 1, 2)
    dv = jnp.moveaxis(dv, 1, 2)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash_attention_vjp.defvjp(_flash_fwd, _flash_bwd)
_flash_attention = jax.jit(_flash_attention_vjp,
                           static_argnums=(3, 4, 5, 6, 7))
