"""Preemption-resilient training subsystem: atomic CheckpointManager
(rotation, torn-checkpoint fallback), seekable data streams, TrainLoop
kill/resume bitwise determinism, orchestrator retry-resume semantics,
and checkpoint-aware ClusterSim preemption accounting."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointError, CheckpointManager,
                              list_checkpoints, load_checkpoint,
                              save_checkpoint)
from repro.core import ClusterSim, JobSpec, JobState, Orchestrator, \
    PersistentVolume, Resources
from repro.data.tokens import SeekableTokenBatches, lm_batch_iterator
from repro.data.inputs import SeekableSyntheticBatches
from repro.train import TrainLoop, TrainState
from repro.train.loop import Preemption


# A toy quadratic "trainer" so manager/loop mechanics are tested without
# model compile time: params -> scalar loss, SGD update.
def _toy_state(value=1.0):
    params = {"w": jnp.full((4,), value, jnp.float32)}
    return TrainState(params, (), jnp.zeros((), jnp.int32))


def _toy_step(state, batch):
    w = state.params["w"]
    new_w = w - 0.1 * (w - batch["target"])
    loss = jnp.mean((w - batch["target"]) ** 2)
    metrics = {"loss": loss, "lr": jnp.float32(0.1),
               "grad_norm": jnp.linalg.norm(w - batch["target"])}
    return TrainState({"w": new_w}, (), state.step + 1), metrics


class _ToyData:
    """Seekable deterministic stream: batch i is a pure function of i."""

    def __init__(self):
        self.step = 0

    def next_batch(self):
        b = {"target": jnp.full((4,), float(self.step % 3), jnp.float32)}
        self.step += 1
        return b

    def cursor(self):
        return {"step": self.step}

    def seek(self, cursor):
        self.step = int(cursor["step"])


# ------------------------------------------------------ CheckpointManager
def test_manager_atomic_layout_and_rotation(tmp_path):
    mgr = CheckpointManager(tmp_path / "ck", keep_last=2, every_steps=1,
                            async_saves=False)
    state = _toy_state()
    for step in (1, 2, 3, 4):
        mgr.save(state, step, extra={"data_cursor": {"step": step}})
    steps = [s for s, _ in list_checkpoints(tmp_path / "ck")]
    assert steps == [3, 4]                       # keep-last-2 rotation
    # no tmp debris after publication
    assert not [p for p in (tmp_path / "ck").iterdir()
                if p.name.startswith(".tmp")]
    restored = mgr.restore_latest(like=state)
    assert restored is not None
    tree, step, extra = restored
    assert step == 4 and extra["data_cursor"] == {"step": 4}


def test_manager_async_saves_and_stats(tmp_path):
    mgr = CheckpointManager(tmp_path / "ck", keep_last=3, every_steps=2,
                            async_saves=True)
    state = _toy_state()
    assert not mgr.maybe_save(state, 1)          # off-cadence
    assert mgr.maybe_save(state, 2)
    assert mgr.maybe_save(state, 4)
    mgr.wait()
    assert [s for s, _ in list_checkpoints(tmp_path / "ck")] == [2, 4]
    st = mgr.stats()
    assert st["saves"] == 2 and st["async"]
    mgr.close()


def test_manager_falls_back_past_torn_checkpoint(tmp_path):
    mgr = CheckpointManager(tmp_path / "ck", keep_last=3, async_saves=False)
    state = _toy_state(1.0)
    mgr.save(state, 5)
    mgr.save(_toy_state(9.0), 10)
    # tear the newest: truncate its manifest mid-write
    newest = tmp_path / "ck" / "step_00000010" / "manifest.json"
    newest.write_text(newest.read_text()[: len(newest.read_text()) // 2])
    tree, step, _ = mgr.restore_latest(like=state)
    assert step == 5                              # fell back
    np.testing.assert_array_equal(np.asarray(tree.params["w"]),
                                  np.full((4,), 1.0, np.float32))
    assert mgr.restore_skipped and "step_00000010" in mgr.restore_skipped[0]


def test_manager_restore_latest_empty_dir(tmp_path):
    mgr = CheckpointManager(tmp_path / "nothing-here")
    assert mgr.restore_latest(like=_toy_state()) is None
    assert mgr.latest_step() is None


# ------------------------------------------------------------ io hardening
def test_load_checkpoint_casts_dtype_only_mismatch(tmp_path):
    params = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
    d = save_checkpoint(tmp_path / "ck", params, step=1)
    like = {"w": jnp.zeros((2, 3), jnp.float16)}
    tree, step = load_checkpoint(d, like=like)
    assert tree["w"].dtype == jnp.float16        # cast, not crash
    np.testing.assert_allclose(np.asarray(tree["w"], np.float32),
                               np.arange(6, dtype=np.float32).reshape(2, 3))


def test_load_checkpoint_missing_and_truncated_manifest(tmp_path):
    with pytest.raises(CheckpointError, match="no manifest.json"):
        load_checkpoint(tmp_path)                # empty dir
    params = {"w": jnp.ones((2,))}
    d = save_checkpoint(tmp_path / "ck", params, step=1)
    mpath = tmp_path / "ck" / "manifest.json"
    mpath.write_text('{"step": 1, "keys": {"w"')  # truncated json
    with pytest.raises(CheckpointError, match="truncated or corrupt"):
        load_checkpoint(d)


def test_load_checkpoint_torn_final_shard(tmp_path):
    params = {"w": jnp.ones((8,)), "b": jnp.zeros((3,))}
    d = save_checkpoint(tmp_path / "ck", params, step=2)
    shard = sorted((tmp_path / "ck").glob("shard_*.npz"))[-1]
    data = shard.read_bytes()
    shard.write_bytes(data[: len(data) // 2])    # torn mid-write
    with pytest.raises(CheckpointError, match="missing or torn"):
        load_checkpoint(d, like=params)
    shard.unlink()                               # shard gone entirely
    with pytest.raises(CheckpointError, match="missing or torn"):
        load_checkpoint(d, like=params)


# --------------------------------------------------------- seekable data
def test_seekable_token_batches_cursor_is_exact():
    a = SeekableTokenBatches(128, 4, 16, seed=3)
    for _ in range(5):
        a.next_batch()
    cur = json.loads(json.dumps(a.cursor()))     # survives JSON roundtrip
    want = [a.next_batch() for _ in range(3)]
    b = SeekableTokenBatches(128, 4, 16, seed=3)
    b.seek(cur)
    got = [b.next_batch() for _ in range(3)]
    for (t1, l1), (t2, l2) in zip(want, got):
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_array_equal(l1, l2)


def test_lm_batch_iterator_start_step_matches_skipping():
    it = lm_batch_iterator(64, 2, 8, seed=1)
    skipped = [next(it) for _ in range(4)][-1]
    fresh = next(lm_batch_iterator(64, 2, 8, seed=1, start_step=3))
    np.testing.assert_array_equal(skipped[0], fresh[0])
    np.testing.assert_array_equal(skipped[1], fresh[1])


def test_seekable_synthetic_batches_cursor():
    from repro.configs import get_reduced
    cfg = get_reduced("hubert-xlarge")           # audio family: make_batch
    a = SeekableSyntheticBatches(cfg, 2, 8, seed=0)
    for _ in range(3):
        a.next_batch()
    b = SeekableSyntheticBatches(cfg, 2, 8, seed=0)
    b.seek(a.cursor())
    x, y = a.next_batch(), b.next_batch()
    for k in x:
        np.testing.assert_array_equal(np.asarray(x[k]), np.asarray(y[k]))


# ------------------------------------------------- TrainLoop kill/resume
def test_trainloop_preempt_then_resume_bitwise_identical(tmp_path):
    def run(ckpt=None, preempt=None, resume=False):
        loop = TrainLoop(_toy_step, _toy_state(), _ToyData(),
                         checkpointer=ckpt, preempt_at_step=preempt,
                         log_every=0)
        if resume:
            assert loop.resume()
        return loop, loop.run(30)

    _, base = run()
    mgr = CheckpointManager(tmp_path / "ck", every_steps=4, async_saves=True)
    with pytest.raises(Preemption):
        run(ckpt=mgr, preempt=15)
    loop2, res = run(ckpt=CheckpointManager(tmp_path / "ck", every_steps=4),
                     resume=True)
    assert res["resumed_from_step"] == 12        # 15 rounded down to cadence
    assert res["steps"] == 30
    assert res["final_loss"] == base["final_loss"]   # bitwise on CPU
    np.testing.assert_array_equal(
        np.asarray(loop2.state.params["w"]), np.asarray(_run_ref(30)))


def _run_ref(steps):
    loop = TrainLoop(_toy_step, _toy_state(), _ToyData(), log_every=0)
    loop.run(steps)
    return loop.state.params["w"]


def test_trainloop_resumed_loss_curve_matches_uninterrupted_tail(tmp_path):
    base = TrainLoop(_toy_step, _toy_state(), _ToyData(), log_every=0)
    base.run(20)
    mgr = CheckpointManager(tmp_path / "ck", every_steps=5, async_saves=False)
    broken = TrainLoop(_toy_step, _toy_state(), _ToyData(),
                       checkpointer=mgr, preempt_at_step=13, log_every=0)
    with pytest.raises(Preemption):
        broken.run(20)
    resumed = TrainLoop(_toy_step, _toy_state(), _ToyData(),
                        checkpointer=CheckpointManager(tmp_path / "ck"),
                        log_every=0)
    assert resumed.resume()
    res = resumed.run(20)
    assert res["resumed_from_step"] == 10
    # every post-resume loss equals the uninterrupted curve, bitwise
    assert resumed.losses == base.losses[10:]


def test_trainloop_fault_hook_generalizes():
    seen = []

    class Boom(RuntimeError):
        pass

    def hook(i):
        seen.append(i)
        if i == 4:
            raise Boom()

    loop = TrainLoop(_toy_step, _toy_state(), _ToyData(), fault_hook=hook,
                     log_every=0)
    with pytest.raises(Boom):
        loop.run(10)
    assert seen == [0, 1, 2, 3, 4]


def test_real_training_kill_and_resume_bitwise(tmp_path):
    """Acceptance: a reduced-config run killed mid-flight via the fault
    hook and resumed produces the identical final loss and step count."""
    from repro.launch.train import train_main

    kw = dict(steps=10, batch=2, seq=16, log_every=0, seed=0)
    base = train_main("stablelm-1.6b", **kw)
    ck = str(tmp_path / "ck")
    with pytest.raises(Preemption):
        train_main("stablelm-1.6b", checkpoint_dir=ck, checkpoint_every=3,
                   preempt_at_step=7, **kw)
    res = train_main("stablelm-1.6b", checkpoint_dir=ck, checkpoint_every=3,
                     resume=True, **kw)
    assert res["resumed_from_step"] == 6
    assert res["steps"] == base["steps"] == 10
    assert res["final_loss"] == base["final_loss"]   # bitwise on CPU
    assert res["checkpoint"]["saves"] >= 2
    # the full TrainState (params + opt state + step) roundtrips: the
    # checkpoint contains optimizer moment keys, not just params
    from repro.checkpoint.io import read_manifest
    step_dirs = list_checkpoints(ck)
    manifest = read_manifest(step_dirs[-1][1])
    keys = manifest["keys"]
    assert any(k.startswith("opt_state/") for k in keys), list(keys)[:5]
    assert "step" in keys
    assert any(k.startswith("params/") for k in keys)


def test_bf16_policy_kill_and_resume_bitwise(tmp_path):
    """The bf16 mixed-precision policy keeps master params + optimizer
    state f32, so its checkpoints round-trip through CheckpointManager
    exactly like f32 runs: a bf16-computed run killed mid-flight and
    resumed ends bitwise-identical to the uninterrupted bf16 run."""
    from repro.launch.train import train_main

    kw = dict(steps=10, batch=2, seq=16, log_every=0, seed=0,
              precision="bf16")
    base = train_main("stablelm-1.6b", **kw)
    ck = str(tmp_path / "ck")
    with pytest.raises(Preemption):
        train_main("stablelm-1.6b", checkpoint_dir=ck, checkpoint_every=3,
                   preempt_at_step=7, **kw)
    res = train_main("stablelm-1.6b", checkpoint_dir=ck, checkpoint_every=3,
                     resume=True, **kw)
    assert res["resumed_from_step"] == 6
    assert res["final_loss"] == base["final_loss"]   # bitwise on CPU
    # the checkpointed state is the f32 master copy, not bf16 compute
    from repro.checkpoint.io import read_manifest
    manifest = read_manifest(list_checkpoints(ck)[-1][1])
    param_dtypes = {v["dtype"] for k, v in manifest["keys"].items()
                    if k.startswith(("params/", "opt_state/"))}
    assert param_dtypes == {"float32"}


def test_bf16_checkpoint_restores_into_f32_run(tmp_path):
    """Cross-policy restore: a checkpoint written by a bf16-policy run
    restores into an f32-policy run (dtype-cast-on-restore is a no-op —
    the master state is f32 either way) and training continues."""
    from repro.launch.train import train_main

    ck = str(tmp_path / "ck")
    train_main("stablelm-1.6b", steps=4, batch=2, seq=16, log_every=0,
               seed=0, precision="bf16", checkpoint_dir=ck,
               checkpoint_every=2)
    res = train_main("stablelm-1.6b", steps=8, batch=2, seq=16, log_every=0,
                     seed=0, precision="f32", checkpoint_dir=ck,
                     checkpoint_every=2, resume=True)
    assert res["resumed_from_step"] == 4
    assert res["steps"] == 8
    assert np.isfinite(res["final_loss"])


def _bf16_state(arch="stablelm-1.6b"):
    """A TrainState in the full config's precision (params and AdamW
    moments bf16) at reduced widths: the full widths do not fit a CPU
    test, and the dtypes are what the checkpoint path has to carry."""
    import dataclasses
    from repro.configs import get_config, get_reduced
    from repro.train import init_train_state
    cfg = dataclasses.replace(get_reduced(arch),
                              param_dtype=get_config(arch).param_dtype)
    return cfg, init_train_state(jax.random.PRNGKey(0), cfg)


def test_bf16_train_state_roundtrips_bitwise(tmp_path):
    """np.savez stores bf16 leaves as raw bytes; load views them back
    through the manifest dtype, so every leaf returns bit for bit, with
    or without a ``like`` tree."""
    _, state = _bf16_state()
    assert jax.tree.leaves(state.params)[0].dtype == jnp.bfloat16
    d = save_checkpoint(tmp_path / "ck", state, step=3)
    tree, step = load_checkpoint(d, like=state)
    flat, _ = load_checkpoint(d)
    assert step == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert {str(v.dtype) for v in flat.values()} >= {"bfloat16"}


def test_full_config_bf16_checkpoint_restores_through_manager(tmp_path):
    """A bf16 run killed after a cadence checkpoint restores through
    ``CheckpointManager.restore_latest`` and ends bitwise identical to
    the uninterrupted run."""
    from repro.data.tokens import SeekableTokenBatches
    from repro.train import make_train_step

    cfg, _ = _bf16_state()

    class Batches(SeekableTokenBatches):
        def next_batch(self):
            toks, labels = super().next_batch()
            return {"tokens": jnp.asarray(toks),
                    "labels": jnp.asarray(labels)}

    step_fn = make_train_step(cfg)

    def loop(ckpt_dir=None, **kw):
        mgr = (CheckpointManager(ckpt_dir, every_steps=2, async_saves=False)
               if ckpt_dir else None)
        return TrainLoop(step_fn, _bf16_state()[1],
                         Batches(cfg.vocab, 2, 16, 0), checkpointer=mgr,
                         log_every=0, **kw)

    base = loop()
    base.run(6)
    ck = tmp_path / "ck"
    with pytest.raises(Preemption):
        loop(ck, preempt_at_step=5).run(6)
    resumed = loop(ck)
    assert resumed.resume() and resumed.start_step == 4
    assert jax.tree.leaves(resumed.state.params)[0].dtype == jnp.bfloat16
    resumed.run(6)
    assert resumed.losses == base.losses[4:]
    for a, b in zip(jax.tree.leaves(base.state),
                    jax.tree.leaves(resumed.state)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ------------------------------------------- orchestrator resume semantics
def test_orchestrator_retry_resumes_from_checkpoint(tmp_path):
    """A payload that raises at step k then succeeds on retry must end at
    the full target step with attempt history recording
    resumed_from_step >= k - checkpoint_every."""
    from repro.api import RunSpec

    k, every, steps = 5, 2, 8
    ck = str(tmp_path / "ck")
    spec = RunSpec(kind="train", arch="stablelm-1.6b", name="resume-job",
                   overrides={"steps": steps, "batch": 2, "seq": 16,
                              "log_every": 0, "checkpoint_dir": ck,
                              "checkpoint_every": every,
                              "preempt_at_step": k})
    orch = Orchestrator(PersistentVolume(tmp_path))
    orch.submit_runs([spec], attach_payload=True)
    rec = orch.run_local()["resume-job"]
    assert rec.state == JobState.SUCCEEDED and rec.attempts == 2
    result = json.loads(orch.pvc.read_bytes("results/resume-job.json"))
    hist = result["attempt_history"]
    assert hist[0]["outcome"] == "failed" and "Preemption" in hist[0]["error"]
    assert hist[1]["outcome"] == "succeeded"
    assert hist[1]["resumed_from_step"] >= k - every
    assert result["result"]["metrics"]["steps"] == steps


def test_to_job_retry_env_only_for_resumable_kinds():
    from repro.api import RunSpec

    train = RunSpec(kind="train", overrides={"steps": 4}).to_job()
    assert train.retry_env.get("RESUME") == "true"
    assert "resume" in train.retry_env["RUN_OVERRIDE_KEYS"].split(",")
    assert "RESUME" not in train.env             # first attempt: fresh
    serve = RunSpec(kind="serve").to_job()
    assert serve.retry_env == {}


# ------------------------------------- checkpoint-aware cluster simulation
def test_clustersim_checkpointing_strictly_improves_makespan():
    jobs = [JobSpec(name=f"j{i}", duration_h=10.0, retries=10,
                    resources=Resources(gpus=1, cpus=1, memory_gb=4))
            for i in range(40)]
    for seed in (0, 1, 2):
        no = ClusterSim(seed=seed, preemption_rate=0.4).run(jobs)
        ck = ClusterSim(seed=seed, preemption_rate=0.4,
                        checkpoint_every_h=1.0).run(jobs)
        assert all(r.state == JobState.SUCCEEDED for r in ck.records)
        assert ck.makespan_h < no.makespan_h     # strictly lower
        assert ck.lost_gpu_hours < no.lost_gpu_hours
        assert ck.goodput > no.goodput
        # lost work bounded by one checkpoint interval per preemption
        assert ck.lost_gpu_hours <= ck.preemptions * 1.0 + 1e-9


def test_clustersim_no_preemption_unchanged_by_checkpointing():
    jobs = [JobSpec(name=f"j{i}", duration_h=2.0,
                    resources=Resources(gpus=1, cpus=1, memory_gb=4))
            for i in range(8)]
    res = ClusterSim(checkpoint_every_h=0.5).run(jobs)
    assert res.preemptions == 0 and res.lost_gpu_hours == 0.0
    assert res.goodput == 1.0
    assert res.makespan_h == pytest.approx(2.0)


def test_orchestrator_simulate_passes_checkpoint_knob(tmp_path):
    orch = Orchestrator(PersistentVolume(tmp_path))
    for i in range(20):
        orch.submit(JobSpec(name=f"j{i}", duration_h=5.0, retries=10,
                            resources=Resources(gpus=1, cpus=1,
                                                memory_gb=4)))
    no = orch.simulate(preemption_rate=0.5)
    ck = orch.simulate(preemption_rate=0.5, checkpoint_every_h=0.5)
    assert ck.makespan_h < no.makespan_h
