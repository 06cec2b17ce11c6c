"""Plain reference of the dense decoder the benchmark's configurations
describe, in straightforward ``jax.numpy``, written from the equations and
importing nothing of the program.

* Every matrix product runs in float32 at ``Precision.HIGHEST`` (on a TPU a
  float32 product is otherwise done in bfloat16).  ``mode="fp8"`` instead
  rounds both operands of every product, forward and backward, to
  float8 e4m3 with a per-tensor scale: that is the control, the step below
  the bfloat16 the configurations state.
* Training is taken layer by layer from Python: the forward keeps only each
  layer's input and the backward runs one layer's VJP at a time, so three
  AdamW steps of a 1.6B-parameter model fit one 16 GB chip.  Between steps
  the weights and AdamW's moments are kept in the bfloat16 the
  configurations state for them; each step is computed in float32.
* Serving is checked by logits over whole sequences, one sequence per call.

The equations are those of the program's decoder (pre-norm residual blocks,
rotary embedding on the whole head with the two-halves convention, causal
grouped-query attention scaled by 1/sqrt(head_dim), a SiLU-gated MLP, no
biases in the projections); configuration files record where those differ
from the published model.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from model_conf import Dims
from weights import make_params

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ------------------------------------------------------------ products
def _quant(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


@functools.lru_cache(maxsize=None)
def _fp8_einsum(spec: str):
    def plain(a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    @jax.custom_vjp
    def f(a, b):
        return plain(_quant(a), _quant(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(plain, _quant(a), _quant(b))
        return vjp(_quant(g))

    f.defvjp(fwd, bwd)
    return f


def einsum(spec: str, a, b, mode: str):
    if mode == "f32":
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=HIGHEST)
    if mode == "fp8":
        return _fp8_einsum(spec)(a.astype(jnp.float32), b.astype(jnp.float32))
    raise ValueError(f"unknown reference mode {mode!r}")


# -------------------------------------------------------------- blocks
def norm(p, x, dm: Dims):
    if dm.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + dm.norm_eps)
        return y * p["scale"] + p["bias"]
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + dm.norm_eps) * p["scale"]


def rope(x, positions, theta: float):
    """x: (B, S, H, hd); rotate pairs (i, i + hd/2) by position * freq_i."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention(q, k, v, dm: Dims, mode: str, q_chunk: int):
    """Causal grouped-query attention with an exact softmax per row.
    Queries go in chunks of ``q_chunk`` rows, each recomputed in the
    backward pass, so no (heads, S, S) tensor is ever whole."""
    B, S, H, hd = q.shape
    rep = H // dm.kv_heads
    c = min(q_chunk, S)
    n = -(-S // c)
    qp = jnp.pad(q, ((0, 0), (0, n * c - S), (0, 0), (0, 0)))
    qc = qp.reshape(B, n, c, dm.kv_heads, rep, hd).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(S)

    def one(args):
        i, qi = args
        s = einsum("bqgrd,bkgd->bgrqk", qi, k, mode) / jnp.sqrt(float(hd))
        qpos = i * c + jnp.arange(c)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return einsum("bgrqk,bkgd->bqgrd", p, v, mode)

    out = jax.lax.map(jax.checkpoint(one), (jnp.arange(n), qc))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, n * c, H, hd)
    return out[:, :S]


def layer_forward(lp, x, dm: Dims, mode: str, q_chunk: int = 1024):
    """One pre-norm block.  lp: one layer's tree; x: (B, S, d) float32."""
    f = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    lp = f(lp)
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = norm(lp["norm1"], x, dm)
    a = lp["attn"]
    q = einsum("bsd,de->bse", h, a["wq"]["w"], mode).reshape(
        B, S, dm.heads, dm.head_dim)
    k = einsum("bsd,de->bse", h, a["wk"]["w"], mode).reshape(
        B, S, dm.kv_heads, dm.head_dim)
    v = einsum("bsd,de->bse", h, a["wv"]["w"], mode).reshape(
        B, S, dm.kv_heads, dm.head_dim)
    q, k = rope(q, pos, dm.rope_theta), rope(k, pos, dm.rope_theta)
    o = attention(q, k, v, dm, mode, q_chunk).reshape(B, S, dm.q_dim)
    x = x + einsum("bse,ed->bsd", o, a["wo"]["w"], mode)
    h = norm(lp["norm2"], x, dm)
    m = lp["mlp"]
    g = jax.nn.silu(einsum("bsd,df->bsf", h, m["gate"]["w"], mode))
    u = einsum("bsd,df->bsf", h, m["up"]["w"], mode)
    return x + einsum("bsf,fd->bsd", g * u, m["down"]["w"], mode)


def head_matrix(top, dm: Dims):
    return top["embed"]["w"].T if dm.tied else top["head"]["w"]


# ----------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnums=(2, 3))
def _logits(params, tokens, dm: Dims, mode: str):
    x = jnp.take(params["embed"]["w"], tokens, axis=0).astype(jnp.float32)

    def body(x, lp):
        return layer_forward(lp, x, dm, mode), None

    x, _ = jax.lax.scan(body, x, params["periods"]["slot0"])
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), params["final_norm"])
    h = norm(fn, x, dm)
    w = head_matrix(params, dm).astype(jnp.float32)
    return einsum("bsd,dv->bsv", h, w, mode)[0]


def sequence_logits(params, tokens: np.ndarray, dm: Dims, mode: str,
                    pad_to: int) -> jax.Array:
    """Logits (pad_to, vocab) of one sequence, right-padded to ``pad_to``
    (causal, so the padding changes no position before it)."""
    toks = np.zeros((1, pad_to), np.int32)
    toks[0, :len(tokens)] = tokens
    return _logits(params, jnp.asarray(toks), dm, mode)


@jax.jit
def _served_gaps(ref_logits, pos, served, other_logits):
    """Per compared position: how far the served token's reference logit
    lies below the reference's best, and how far the token another
    computation puts first lies below it."""
    rows = ref_logits[pos]
    best = rows.max(axis=-1)
    served_gap = best - jnp.take_along_axis(rows, served[:, None], axis=1)[:, 0]
    other_first = jnp.argmax(other_logits[pos], axis=-1)
    other_gap = best - jnp.take_along_axis(rows, other_first[:, None],
                                           axis=1)[:, 0]
    return served_gap, other_gap


def served_logit_gaps(dm: Dims, seed: int, samples: Sequence, pad_to: int,
                      control: bool = False) -> Dict[str, float]:
    """samples: (prompt, served tokens) pairs.  Returns the widest gap of a
    served token below the reference's best, the number of tokens compared
    and, with ``control``, the widest gap of the token that the float8
    reference puts first at the same positions."""
    params = make_params(dm, seed)
    widest, widest_ctrl, n = 0.0, 0.0, 0
    for prompt, served in samples:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        pos = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = sequence_logits(params, seq, dm, "f32", pad_to)
        other = (sequence_logits(params, seq, dm, "fp8", pad_to)
                 if control else ref)
        gap, ctrl_gap = _served_gaps(ref, pos, jnp.asarray(served), other)
        widest = max(widest, float(gap.max()))
        widest_ctrl = max(widest_ctrl, float(ctrl_gap.max()))
        n += len(served)
    out = {"logit_gap": widest, "tokens": n}
    if control:
        out["control_logit_gap"] = widest_ctrl
    return out


# ----------------------------------------------------------- training
def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_fwd(lp, x, dm: Dims, mode: str):
    return layer_forward(lp, x, dm, mode)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_bwd(lp, x, dx_out, dm: Dims, mode: str):
    """The layer's input gradient and its weights' float32 gradient."""
    _, vjp = jax.vjp(lambda p, h: layer_forward(p, h, dm, mode), _f32(lp), x)
    g, dx = vjp(dx_out)
    return dx, g


def _head_loss(fn, w, x, tokens, dm: Dims, mode: str, chunk: int,
               positions: str):
    """Mean next-token cross entropy: the logits at positions 0..S-2
    against tokens 1..S-1.  fn: final norm; w: (d, vocab) output matrix.
    ``positions="first_half"`` plants a fault: the second half of the
    positions is left out and the mean taken over the rest."""
    h = norm(fn, x, dm)[:, :-1]
    ls = tokens[:, 1:]
    B, S1, d = h.shape
    mask = jnp.ones((B, S1), jnp.float32)
    if positions == "first_half":
        mask = jnp.where(jnp.arange(S1)[None, :] < S1 // 2, 1.0, 0.0) * mask
    n = -(-S1 // chunk)
    pad = n * chunk - S1
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0))).reshape(B, n, chunk, d)
    lp = jnp.pad(ls, ((0, 0), (0, pad))).reshape(B, n, chunk)
    mp = jnp.pad(mask, ((0, 0), (0, pad))).reshape(B, n, chunk)

    def one(args):
        hc, lc, mc = args
        logits = einsum("bcd,dv->bcv", hc, w, mode)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * mc)

    tot = jax.lax.map(jax.checkpoint(one),
                      (hp.transpose(1, 0, 2, 3), lp.transpose(1, 0, 2),
                       mp.transpose(1, 0, 2)))
    return jnp.sum(tot) / jnp.sum(mask)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _head_grad(fn, w, x, tokens, dm: Dims, mode: str, chunk: int,
               positions: str):
    return jax.value_and_grad(_head_loss, argnums=(0, 1, 2))(
        _f32(fn), w.astype(jnp.float32), x, tokens, dm, mode, chunk,
        positions)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed_grad(tokens, dx, vocab: int):
    d = dx.shape[-1]
    return jnp.zeros((vocab, d), jnp.float32).at[tokens.reshape(-1)].add(
        dx.reshape(-1, d))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(p, m, v, g, lr, t, b1=0.9, b2=0.999, eps=1e-8, wd=0.01):
    """One AdamW step in float32 from state kept in its stored dtype, and
    the new state stored back in that dtype (the configuration's bfloat16
    weights and moments)."""
    def leaf(p, m, v, g):
        pf = p.astype(jnp.float32)
        m1 = b1 * m.astype(jnp.float32) + (1 - b1) * g
        v1 = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        mhat = m1 / (1 - b1 ** t)
        vhat = v1 / (1 - b2 ** t)
        p1 = pf - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pf)
        return p1.astype(p.dtype), m1.astype(m.dtype), v1.astype(v.dtype)

    out = jax.tree.map(leaf, p, m, v, g)
    is3 = lambda x: isinstance(x, tuple)
    return tuple(jax.tree.map(lambda x, i=i: x[i], out, is_leaf=is3)
                 for i in range(3))


def _leaf_norms(tree, prefix: str, out: Dict[str, float]):
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = prefix + jax.tree_util.keystr(path)
        out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
            jnp.asarray(a, jnp.float32)))))


def train_readings(dm: Dims, seed: int, batches: Sequence, *, lr: float,
                   mode: str = "f32", positions: str = "all",
                   loss_chunk: int = 512) -> Dict[str, object]:
    """Follow the first ``len(batches)`` AdamW steps from the seeded
    weights.  batches: the token ids of each step, int32 (B, S), in order;
    each position is trained on the token after it.

    Weights and moments are kept between steps in the bfloat16 that the
    configuration states for them; every step is computed in float32.
    Returns the loss of each step, the norm of every parameter slice's
    first gradient (``grad``), and the norm of every slice's change after
    the last step (``change``).  A slice is one layer's share of a stacked
    weight, or a whole unstacked weight."""
    p0 = make_params(dm, seed)
    layers = [jax.tree.map(lambda a, i=i: a[i], p0["periods"]["slot0"])
              for i in range(dm.layers)]
    top = {k: v for k, v in p0.items() if k != "periods"}
    del p0
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    m_l, v_l = [zeros(lp) for lp in layers], [zeros(lp) for lp in layers]
    m_t, v_t = zeros(top), zeros(top)
    losses: List[float] = []
    grad_norms: Dict[str, float] = {}
    lr = jnp.float32(lr)
    for t, tokens in enumerate(batches, start=1):
        tf = jnp.float32(t)
        tokens = jnp.asarray(tokens, jnp.int32)
        x = jnp.take(top["embed"]["w"], tokens, axis=0).astype(jnp.float32)
        stash = []
        for lp in layers:
            stash.append(x)
            x = _layer_fwd(lp, x, dm, mode)
        w = head_matrix(top, dm)
        loss, (g_fn, g_w, dx) = _head_grad(top["final_norm"], w, x, tokens,
                                           dm, mode, loss_chunk, positions)
        losses.append(float(loss))
        del x, w
        for i in reversed(range(dm.layers)):
            dx, g = _layer_bwd(layers[i], stash[i], dx, dm, mode)
            stash[i] = None
            if t == 1:
                _leaf_norms(g, f"layer{i}", grad_norms)
            layers[i], m_l[i], v_l[i] = _adamw(layers[i], m_l[i], v_l[i], g,
                                               lr, tf)
            del g
        g_emb = _embed_grad(tokens, dx, dm.vocab)
        g_top = {"embed": {"w": g_emb + g_w.T if dm.tied else g_emb},
                 "final_norm": g_fn}
        if not dm.tied:
            g_top["head"] = {"w": g_w}
        del g_emb, g_w, g_fn, dx
        if t == 1:
            _leaf_norms(g_top, "top", grad_norms)
        top, m_t, v_t = _adamw(top, m_t, v_t, g_top, lr, tf)
        del g_top
    del m_l, v_l, m_t, v_t
    p0 = make_params(dm, seed)
    change: Dict[str, float] = {}
    for i in range(dm.layers):
        d = jax.tree.map(lambda a, b, i=i: a.astype(jnp.float32)
                         - b[i].astype(jnp.float32),
                         layers[i], p0["periods"]["slot0"])
        _leaf_norms(d, f"layer{i}", change)
    d = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                     top, {k: v for k, v in p0.items() if k != "periods"})
    _leaf_norms(d, "top", change)
    return {"losses": losses, "grad": grad_norms, "change": change}


def slice_norms(tree, dm: Dims) -> Dict[str, float]:
    """Norms of a program-layout tree (stacked layers) under the slice
    names :func:`train_readings` uses."""
    out: Dict[str, float] = {}
    sq = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)), axis=tuple(range(1, a.ndim)))))
    for path, a in jax.tree_util.tree_flatten_with_path(
            tree["periods"]["slot0"])[0]:
        norms = np.asarray(sq(a))
        key = jax.tree_util.keystr(path)
        for i in range(dm.layers):
            out[f"layer{i}{key}"] = float(norms[i])
    _leaf_norms({k: v for k, v in tree.items() if k != "periods"}, "top", out)
    return out


def gap_of_norms(prog: Dict[str, float], ref: Dict[str, float],
                 skip: Optional[set] = None) -> Dict[str, object]:
    """Per slice, |program norm - reference norm| over the larger of that
    slice's reference norm and the median slice's; returns the worst
    slice's gap and the median slice's gap."""
    names = [k for k in ref if not (skip and k in skip)]
    med = float(np.median([ref[k] for k in names]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in names}
    where = max(gaps, key=gaps.get)
    return {"gap": gaps[where], "slice": where,
            "median_gap": float(np.median(list(gaps.values())))}


def rounding_only(ref_grad: Dict[str, float]) -> set:
    """Slices whose reference gradient is nought to rounding: under a
    thousandth of the median slice's.  Their change under AdamW is
    round-off alone, so the change comparison leaves them out."""
    med = float(np.median(list(ref_grad.values())))
    return {k for k, v in ref_grad.items() if v < 1e-3 * med}
