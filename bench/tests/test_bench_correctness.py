"""The check that decides ``correct``, on the CPU at a size a test run can
hold: the plain reference agrees with the program, the float8 control does
not, and a run whose timed path is broken underneath comes out not
correct, once for each fault the cells can have.

These runs skip the harness's look for a chip and use a tiny model of the
configurations' shape (and tiny-size limits, set from these same sizes);
the limits of the cells themselves are set from chip runs (PERF.md)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference as R
from model_conf import dims

TINY = {"name": "tiny", "source": "test", "hidden_size": 256,
        "intermediate_size": 512, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 512, "max_position_embeddings": 256,
        "tie_word_embeddings": True, "norm": "rmsnorm", "norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "program": {"arch": "tiny", "param_dtype": "bfloat16",
                    "optimizer": "adamw", "attention_backend": "auto"}}
TINY_TRAIN = dict(TINY, tie_word_embeddings=False, norm="layernorm",
                  norm_eps=1e-5)
TRAIN_MIX = {"kind": "train", "batch": 2, "seq": 64, "lr": 3e-4,
             "trace_seconds": 1}
SERVE_MIX = {"kind": "serve", "arrivals": "poisson", "rate_rps": 16,
             "prompt": {"median": 24, "sigma": 0.8, "min": 4, "max": 100},
             "output": {"median": 12, "sigma": 0.8, "min": 4, "max": 28},
             "slots": 4, "tail_s": 2, "drain_s": 30, "trace_seconds": 1}
# tiny-size limits, above what sound runs of this size read on the CPU
# (train grad gaps 0.0021-0.0029, serve logit gap 0) and below the float8
# control at SEED (grad gap 0.014; logit gap 0.0041, where a short sample
# of a model this small may flip no first token at all)
TRAIN_LIMITS = {"loss_gap": 0.01, "grad_gap": 0.006, "grad_gap_median": 0.006,
                "change_gap": 0.4}
SERVE_LIMITS = {"logit_gap": 0.002}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 17


def _run(cell, fault=None, trace=0, control=None):
    train = "train" in cell
    args = harness.parse(["--workload", cell, "--seed", str(SEED),
                          "--seconds", "1", "--trace", str(trace)])
    over = {"config": TINY_TRAIN if train else TINY,
            "traffic": TRAIN_MIX if train else SERVE_MIX,
            "limits": TRAIN_LIMITS if train else SERVE_LIMITS, "peak": PEAK}
    return harness.run_cell(args, t_start=time.perf_counter(),
                            require_chip=False, fault=fault, overrides=over,
                            control=control)


TRAIN = "stablelm-1.6b.train-s4096"
SERVE = "granite-3-2b.serve-chat"


# ------------------------------------------------------------- faults
def _train_state_unchanged(step):
    def f(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return f


def _train_half_batch(step):
    def f(state, batch):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return step(state, half)
    return f


def _wrap_decode(change):
    def fault(sched):
        orig = sched._decode

        def f(p, state, last, pos, *rest):
            keep_state = jax.tree.map(jnp.copy, state)
            keep_last = jnp.copy(last)
            new_state, tok, npos, eos = orig(p, state, last, pos, *rest)
            return change(keep_state, keep_last, new_state, tok, npos, eos)
        sched._decode = f
    return fault


_serve_state_unchanged = _wrap_decode(
    lambda old, last, new, tok, pos, eos: (old, tok, pos, eos))
_serve_half_batch = _wrap_decode(
    lambda old, last, new, tok, pos, eos:
    (new, tok.at[1::2].set(0), pos, eos))
_serve_token_altered = _wrap_decode(
    lambda old, last, new, tok, pos, eos:
    (new, tok.at[0].set((tok[0] + 1) % TINY["vocab_size"]), pos, eos))


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}


@pytest.mark.parametrize("cell,fault", [
    (TRAIN, _train_state_unchanged),
    (TRAIN, _train_half_batch),
    (SERVE, _serve_state_unchanged),
    (SERVE, _serve_half_batch),
    (SERVE, _serve_token_altered),
], ids=["train-state-unchanged", "train-half-batch",
        "serve-state-unchanged", "serve-half-batch", "serve-token-altered"])
def test_broken_timed_path_is_not_correct(cell, fault):
    line = _run(cell, fault)
    assert not line["correct"], line["checks"]


# ------------------------------------------------------------ control
def _control_fails(cell, control):
    """The control, put in the program's place by the driver, fails the
    run's own check, while the same run's program passes it."""
    line = _run(cell, control=control)
    assert not line["correct"], line["checks"]
    program = line["notes"]["program"]
    limits = TRAIN_LIMITS if cell == TRAIN else SERVE_LIMITS
    assert all(program[k] <= limits[k] for k in line["checks"]), program


def test_train_control_reads_above_the_program():
    _control_fails(TRAIN, "fp8")


def test_train_half_positions_reads_above_the_program():
    _control_fails(TRAIN, "half_positions")


def test_serve_control_reads_above_the_program():
    _control_fails(SERVE, "fp8")


def test_reference_trains_each_position_on_the_next_token():
    """The reference's loss is that of the logits at positions 0..S-2
    against tokens 1..S-1, worked out here from its forward pass."""
    dm = dims(TINY_TRAIN)
    toks = np.tile(np.array([7, 9, 11], np.int32), 22)[None, :64]
    loss = R.train_readings(dm, SEED, [toks], lr=3e-4)["losses"][0]
    logits = np.asarray(R.sequence_logits(R.make_params(dm, SEED), toks[0],
                                          dm, "f32", 64), np.float64)
    top = logits.max(-1)
    lse = np.log(np.exp(logits - top[:, None]).sum(-1)) + top
    want = np.mean(lse[:-1] - logits[np.arange(63), toks[0, 1:]])
    assert abs(loss - want) < 1e-4 * want, (loss, want)
