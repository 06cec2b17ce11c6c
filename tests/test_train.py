"""Training behaviour: loss decreases on learnable synthetic data;
microbatch gradient accumulation is exact; checkpoints roundtrip; the
compiled step donates its state; mixed precision and kernel backends
train correctly."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import export_to_s3, load_checkpoint, save_checkpoint
from repro.configs import get_reduced
from repro.core import S3Store
from repro.data.tokens import lm_batch_iterator
from repro.models import init_params, train_loss
from repro.optim import get_optimizer, warmup_cosine
from repro.train import (get_precision, init_train_state, make_eval_step,
                         make_train_step)


def test_loss_decreases_on_markov_tokens():
    cfg = dataclasses.replace(get_reduced("stablelm-1.6b"), vocab=128)
    state = init_train_state(jax.random.PRNGKey(0), cfg,
                             get_optimizer("adamw"))
    step_fn = make_train_step(
        cfg, get_optimizer("adamw"),
        lr_schedule=warmup_cosine(3e-3, 60, warmup_steps=10))
    it = lm_batch_iterator(cfg.vocab, batch=8, seq=64, seed=0)
    losses = []
    for i in range(60):
        toks, labels = next(it)
        state, metrics = step_fn(state, {"tokens": jnp.asarray(toks),
                                         "labels": jnp.asarray(labels)})
        losses.append(float(metrics["loss"]))
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.2, (first, last)


def test_microbatch_accumulation_matches_full_batch():
    cfg = get_reduced("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    batch = {"tokens": toks}

    g_full = jax.grad(lambda p: train_loss(p, cfg, batch, remat=False))(params)

    def acc_grads(n):
        total = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        for i in range(n):
            mb = {"tokens": toks[i * (8 // n):(i + 1) * (8 // n)]}
            g = jax.grad(lambda p: train_loss(p, cfg, mb, remat=False))(params)
            total = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 total, g)
        return jax.tree.map(lambda x: x / n, total)

    g_acc = acc_grads(4)
    flat_f = jnp.concatenate([x.ravel().astype(jnp.float32)
                              for x in jax.tree.leaves(g_full)])
    flat_a = jnp.concatenate([x.ravel() for x in jax.tree.leaves(g_acc)])
    np.testing.assert_allclose(np.asarray(flat_a), np.asarray(flat_f),
                               atol=1e-5, rtol=1e-4)


def test_remat_does_not_change_loss_or_grads():
    cfg = get_reduced("glm4-9b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64),
                                          0, cfg.vocab)}
    l1, g1 = jax.value_and_grad(
        lambda p: train_loss(p, cfg, batch, remat=False))(params)
    l2, g2 = jax.value_and_grad(
        lambda p: train_loss(p, cfg, batch, remat=True))(params)
    assert float(jnp.abs(l1 - l2)) < 1e-5
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-5, rtol=1e-4)


def _small_batch(cfg, batch=4, seq=32, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq),
                              0, cfg.vocab)
    return {"tokens": toks, "labels": toks}


def test_train_step_donates_state_buffers():
    """The jitted train step consumes its input TrainState: the donated
    buffers are deleted, so no second copy of params/opt state exists."""
    cfg = get_reduced("stablelm-1.6b")
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    step_fn = make_train_step(cfg)
    new_state, metrics = step_fn(state, _small_batch(cfg))
    for leaf in jax.tree.leaves(state):
        assert leaf.is_deleted()
    for leaf in jax.tree.leaves(new_state):
        assert not leaf.is_deleted()
    # and the step is usable again with the new state
    new_state, _ = step_fn(new_state, _small_batch(cfg))
    assert int(new_state.step) == 2
    # opt-out keeps the input alive
    state2 = init_train_state(jax.random.PRNGKey(0), cfg)
    undonated = make_train_step(cfg, donate=False)
    undonated(state2, _small_batch(cfg))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(state2))


def test_eval_step_jit_identical_before_after_change():
    """Compiling the eval path must not change the loss.  The bitwise
    contract is jit-vs-jit: the seed's eval (bare function a caller
    would wrap in jax.jit) and the now-built-in jit produce the same
    program, hence bitwise-identical losses — and the jitted loss is
    deterministic across calls.  Eager (op-by-op) execution is only
    float-equal, not bitwise: XLA fusion reorders the reductions."""
    cfg = get_reduced("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _small_batch(cfg)
    seed_style = jax.jit(make_eval_step(cfg, jit_compile=False))
    new_style = make_eval_step(cfg)
    assert float(seed_style(params, batch)) == float(new_style(params, batch))
    assert float(new_style(params, batch)) == float(new_style(params, batch))
    eager = train_loss(params, cfg, batch, remat=False)
    np.testing.assert_allclose(float(new_style(params, batch)), float(eager),
                               rtol=1e-6)


def test_bf16_precision_policy_trains():
    """bf16 policy: master params and optimizer state stay f32 (the
    checkpointable state is unchanged), loss is f32 and close to the f32
    policy's, and the loss still decreases."""
    cfg = dataclasses.replace(get_reduced("stablelm-1.6b"), vocab=128)
    opt = get_optimizer("adamw")
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    f32_loss = float(train_loss(state.params, cfg, _small_batch(cfg)))
    bf16_loss = float(train_loss(state.params, cfg, _small_batch(cfg),
                                 compute_dtype="bfloat16"))
    assert bf16_loss == pytest.approx(f32_loss, rel=2e-2)

    step_fn = make_train_step(
        cfg, opt, precision="bf16",
        lr_schedule=warmup_cosine(3e-3, 40, warmup_steps=5))
    it = lm_batch_iterator(cfg.vocab, batch=8, seq=64, seed=0)
    losses = []
    for _ in range(40):
        toks, labels = next(it)
        state, metrics = step_fn(state, {"tokens": jnp.asarray(toks),
                                         "labels": jnp.asarray(labels)})
        losses.append(float(metrics["loss"]))
        assert metrics["loss"].dtype == jnp.float32
    for leaf in jax.tree.leaves(state.params) + jax.tree.leaves(
            state.opt_state):
        assert leaf.dtype == jnp.float32       # master state stays f32
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2


def test_precision_policy_resolution():
    p = get_precision("bf16")
    assert p.compute_dtype == "bfloat16" and p.param_dtype == "float32"
    assert p.grad_dtype == "float32" and p.casts_compute
    assert get_precision(None).name == "f32"
    assert get_precision(p) is p
    with pytest.raises(ValueError, match="unknown precision"):
        get_precision("fp8")


def test_grad_clip_fused_with_norm_metric():
    """grad_clip bounds the applied update without changing the reported
    grad_norm (the metric is the pre-clip norm from the same reduction)."""
    cfg = dataclasses.replace(get_reduced("stablelm-1.6b"), vocab=128)
    opt = get_optimizer("sgd")                  # update == -lr * grads
    batch = _small_batch(cfg)
    clip = 1e-3
    lr = 1.0

    unclipped = make_train_step(cfg, opt, lr_schedule=lambda s: lr,
                                donate=False)
    clipped = make_train_step(cfg, opt, lr_schedule=lambda s: lr,
                              grad_clip=clip, donate=False)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    _, m0 = unclipped(state, batch)
    new_state, m1 = clipped(state, batch)
    assert float(m0["grad_norm"]) == float(m1["grad_norm"])  # same reduction
    assert float(m1["grad_norm"]) > clip       # clip actually engaged
    upd = jnp.sqrt(sum(
        jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
        for a, b in zip(jax.tree.leaves(new_state.params),
                        jax.tree.leaves(state.params))))
    assert float(upd) <= lr * clip * (1 + 1e-4)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "mamba2-2.7b"])
def test_pallas_backend_trains_equivalently(arch):
    """One full train step (value_and_grad + update) through the Pallas
    kernel backends matches the jnp backends within f32 tolerance."""
    cfg = get_reduced(arch)
    batch = _small_batch(cfg, batch=2, seq=64)
    states = {}
    for be in ("jnp", "pallas"):
        c = dataclasses.replace(cfg, attention_backend=be, mixer_backend=be)
        state = init_train_state(jax.random.PRNGKey(0), c)
        step_fn = make_train_step(c, donate=False)
        states[be] = step_fn(state, batch)
    (s_jnp, m_jnp), (s_pl, m_pl) = states["jnp"], states["pallas"]
    assert float(m_jnp["loss"]) == pytest.approx(float(m_pl["loss"]),
                                                 abs=1e-5)
    assert float(m_jnp["grad_norm"]) == pytest.approx(
        float(m_pl["grad_norm"]), rel=1e-4)
    # AdamW's first step moves each weight by lr * g / (|g| + eps): the
    # update is normalized per element.  The kernels sum their products
    # in another f32 order than the jnp lowerings, so the gradients of a
    # leaf differ by |dg| <= delta = 16 * eps_f32 * max|g| (the measured
    # spread stays under 8 * eps_f32 * max|g|).  Where |g| >= 10 * delta
    # the updates differ by at most lr * delta / |g| <= 1e-5 = atol.  An
    # element whose gradient lies under 10 * delta, at the leaf's noise
    # floor, may take any update in [-lr, lr] on either backend: it is
    # held to 2 * lr.
    lr = 1e-4                                   # make_train_step default
    c_jnp = dataclasses.replace(cfg, attention_backend="jnp",
                                mixer_backend="jnp")
    grads = jax.grad(lambda p: train_loss(p, c_jnp, batch))(
        init_train_state(jax.random.PRNGKey(0), c_jnp).params)
    eps32 = float(np.finfo(np.float32).eps)
    for a, b, g in zip(jax.tree.leaves(s_jnp.params),
                       jax.tree.leaves(s_pl.params), jax.tree.leaves(grads)):
        g = np.abs(np.asarray(g, np.float32))
        delta = 16 * eps32 * g.max()
        atol = np.where(g >= 10 * delta, 1e-5, 2 * lr)
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        bad = np.abs(a - b) > atol + 1e-4 * np.abs(b)
        assert not bad.any(), (f"{bad.sum()} of {bad.size} elements "
                               f"differ by {np.abs(a - b)[bad].max()}")


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_reduced("mamba2-2.7b")
    params = init_params(jax.random.PRNGKey(3), cfg)
    d = save_checkpoint(tmp_path / "ck", params, step=17,
                        metadata={"arch": cfg.name})
    restored, step = load_checkpoint(d, like=params)
    assert step == 17
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # shape mismatch must raise
    bad = jax.tree.map(lambda x: x, params)
    bad["embed"]["w"] = jnp.zeros((3, 3))
    with pytest.raises((ValueError, KeyError)):
        load_checkpoint(d, like=bad)


def test_checkpoint_s3_export(tmp_path):
    cfg = get_reduced("stablelm-1.6b")
    params = init_params(jax.random.PRNGKey(3), cfg)
    d = save_checkpoint(tmp_path / "ck", params, step=1)
    s3 = S3Store(tmp_path)
    n = export_to_s3(d, s3, "models/stablelm-run0")
    assert n >= 2  # manifest + at least one shard
    assert s3.exists("models/stablelm-run0/manifest.json")
