"""Public wrapper: chunking, group->head expansion, padding — and the
``jax.custom_vjp`` that makes the Pallas path trainable.

The backward pass differentiates a mathematically-equivalent pure-jnp
chunked formulation (recompute-from-inputs, the FlashAttention residual
strategy): the kernel's intra/inter-chunk decomposition is re-expressed
as a ``lax.scan`` whose autodiff *is* the SSD backward recurrence.  This
keeps one source of truth for the backward math on every backend; a
hand-fused Pallas backward kernel can later swap in behind the same
``defvjp`` without touching callers."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import default_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128,
             interpret: bool | None = None, return_state: bool = False):
    """SSD selective scan.  x: (Bs,S,nh,hp); dt: (Bs,S,nh); A: (nh,);
    B/C: (Bs,S,g,N) group-shared.  Returns y: (Bs,S,nh,hp), or
    ``(y, h_final (Bs,nh,hp,N) f32)`` with ``return_state=True``.

    Differentiable (``jax.grad`` through either output form).
    ``interpret=None`` auto-detects: compiled on TPU, interpret elsewhere.
    """
    if interpret is None:
        interpret = default_interpret()
    y, h = _ssd_scan(x, dt, A, B, C, chunk, interpret)
    return (y, h) if return_state else y


def _chunk_geometry(S: int, chunk: int):
    Q = min(chunk, S)
    pad = (-S) % Q
    return Q, pad


def _pad_chunk(x, dt, B, C, Q, pad):
    if pad:
        # pad dt with zeros => exp(0*A)=1 decay, zero input: harmless
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x, dt, B, C


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_scan_vjp(x, dt, A, B, C, chunk, interpret):
    return _ssd_fwd_impl(x, dt, A, B, C, chunk, interpret)


def _ssd_fwd_impl(x, dt, A, B, C, chunk, interpret):
    Bs, S, nh, hp = x.shape
    g, N = B.shape[2], B.shape[3]
    Q, pad = _chunk_geometry(S, chunk)
    x, dt, B, C = _pad_chunk(x, dt, B, C, Q, pad)
    nc = (S + pad) // Q

    def chunked(a, width):
        # (Bs, Sp, heads, width) -> head-major (Bs, heads, nc, Q, width)
        return jnp.moveaxis(a, 2, 1).reshape(Bs, a.shape[2], nc, Q, width)

    dtf = dt.astype(jnp.float32)
    dtc = chunked(jnp.stack([dtf, dtf * A.astype(jnp.float32)], -1), 2)
    dtr = jnp.swapaxes(dtc, -1, -2)
    y, h = ssd_scan_kernel(chunked(x, hp), dtc, dtr, chunked(B, N),
                           chunked(C, N), interpret=interpret)
    y = jnp.moveaxis(y.reshape(Bs, nh, nc * Q, hp), 1, 2)
    return y[:, :S], h


def _ssd_jnp_equiv(x, dt, A, B, C, chunk):
    """Pure-jnp chunked SSD, matching the kernel math term for term
    (f32 compute, masked-exponent intra-chunk matmuls, carried state).
    Autodiff of this function is the backward pass of the Pallas op."""
    Bs, S, nh, hp = x.shape
    g, N = B.shape[2], B.shape[3]
    rep = nh // g
    in_dtype = x.dtype
    Q, pad = _chunk_geometry(S, chunk)
    x, dt, B, C = _pad_chunk(x, dt, B, C, Q, pad)
    Sp = S + pad
    nc = Sp // Q

    xf = x.astype(jnp.float32).reshape(Bs, nc, Q, nh, hp)
    dtc = dt.astype(jnp.float32).reshape(Bs, nc, Q, nh)
    Bc = jnp.repeat(B, rep, axis=2).astype(jnp.float32).reshape(
        Bs, nc, Q, nh, N)
    Cc = jnp.repeat(C, rep, axis=2).astype(jnp.float32).reshape(
        Bs, nc, Q, nh, N)
    xf, dtc, Bc, Cc = (jnp.moveaxis(a, 1, 0) for a in (xf, dtc, Bc, Cc))
    Af = A.astype(jnp.float32)

    def chunk_step(h, inp):
        xq, dtq, Bq, Cq = inp                      # (Bs,Q,nh,hp) etc.
        la = jnp.cumsum(dtq * Af, axis=1)          # (Bs,Q,nh)
        la_last = la[:, -1, :]                     # (Bs,nh)
        G = jnp.einsum("bihn,bjhn->bijh", Cq, Bq)  # (Bs,Q,Q,nh)
        # mask the EXPONENT, not the product (upper triangle overflows)
        diff = la[:, :, None, :] - la[:, None, :, :]
        tri = jnp.tril(jnp.ones((xq.shape[1], xq.shape[1]), bool))
        diff = jnp.where(tri[None, :, :, None], diff, -jnp.inf)
        M = G * jnp.exp(diff)
        y = jnp.einsum("bijh,bjh,bjhp->bihp", M, dtq, xq)
        y += jnp.einsum("bihn,bhpn->bihp", Cq * jnp.exp(la)[..., None], h)
        decay_out = jnp.exp(la_last[:, None, :] - la) * dtq
        h_new = jnp.exp(la_last)[:, :, None, None] * h + jnp.einsum(
            "bjhp,bjhn->bhpn", xq * decay_out[..., None], Bq)
        return h_new, y

    h0 = jnp.zeros((Bs, nh, hp, N), jnp.float32)
    h_final, yc = jax.lax.scan(chunk_step, h0, (xf, dtc, Bc, Cc))
    y = jnp.moveaxis(yc, 0, 1).reshape(Bs, Sp, nh, hp)[:, :S]
    return y.astype(in_dtype), h_final


def _ssd_fwd(x, dt, A, B, C, chunk, interpret):
    y, h = _ssd_fwd_impl(x, dt, A, B, C, chunk, interpret)
    return (y, h), (x, dt, A, B, C)


def _ssd_bwd(chunk, interpret, res, cts):
    x, dt, A, B, C = res
    _, vjp_fn = jax.vjp(
        lambda x, dt, A, B, C: _ssd_jnp_equiv(x, dt, A, B, C, chunk),
        x, dt, A, B, C)
    return vjp_fn(cts)


_ssd_scan_vjp.defvjp(_ssd_fwd, _ssd_bwd)
_ssd_scan = jax.jit(_ssd_scan_vjp, static_argnums=(5, 6))
