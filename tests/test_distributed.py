"""Data-parallel training subsystem tests.

Three layers, mirroring how the subsystem is built:

* **units** (jax-free or world=1): row sharding, the analytic
  all-reduce traffic model, per-rank argv construction, the Indexed-Job
  manifest rendering, and the seekable-cursor round trip through
  :class:`repro.distributed.data.ShardedBatches`;
* **hermetic executor gang scheduling** over the injectable fake
  spawn: one process per rank sharing a coordinator, whole-gang
  kill+requeue when one rank dies (second attempt resumes), fail-fast
  unschedulable gangs with zero spawns, worker-cap accounting in
  process units, and the ``campaign status`` gang row;
* **system oracle + chaos** (real subprocesses, real SIGKILL): a
  world=2 gang through the campaign executor matches a single-process
  run at the same global batch to documented tolerance, and a
  chaos-killed gang (one rank SIGKILLed mid-run) resumes to final
  params **bitwise identical** to the undisturbed gang.

The world=1 distributed path is asserted *bitwise* equal to the plain
single-process trainer — same step function, same stream, a one-device
mesh — so the tolerance in the cross-world oracle isolates exactly the
``psum`` reassociation of the batch-mean gradient.
"""
import json

import numpy as np
import pytest

from repro.core import (ChaosSpec, JobSpec, JobState, NodeSpec,
                        Orchestrator, PersistentVolume, Resources,
                        replay_events)
from repro.core.executor import EVENTS_REL, format_status
from repro.distributed.data import shard_rows
from repro.distributed.gang import rank_argv
from repro.distributed.trainer import allreduce_bytes_per_step

from test_campaign_exec import FakeProc, fake_spawn


# --------------------------------------------------------------------------
# Units
# --------------------------------------------------------------------------
def test_shard_rows_contiguous_partition():
    batch = {"tokens": np.arange(8 * 3).reshape(8, 3)}
    parts = [shard_rows(batch, r, 4)["tokens"] for r in range(4)]
    assert all(p.shape == (2, 3) for p in parts)
    np.testing.assert_array_equal(np.concatenate(parts),
                                  batch["tokens"])
    with pytest.raises(ValueError):
        shard_rows(batch, 0, 3)          # 8 rows not divisible by 3


def test_allreduce_bytes_analytic_model():
    gb = 1_000_000
    assert allreduce_bytes_per_step(gb, 1) == 0
    assert allreduce_bytes_per_step(gb, 2) == gb          # 2*(1/2)
    assert allreduce_bytes_per_step(gb, 4) == 1_500_000   # 2*(3/4)


def test_rank_argv_appends_dist_flags():
    base = ["python", "-m", "repro.launch", "run", "train", "--steps=3"]
    got = rank_argv(base, 1, "127.0.0.1:555")
    assert got[:len(base)] == base
    assert got[len(base):] == ["--dist_rank=1",
                               "--coordinator=127.0.0.1:555"]
    assert base[-1] == "--steps=3"       # input untouched


def test_rank_envs_bind_one_chip_per_rank_on_a_tpu_host(monkeypatch):
    from repro.distributed import gang
    monkeypatch.setattr(gang, "tpu_chips", lambda: 4)
    base = {"PATH": "/bin", "JAX_PLATFORMS": "tpu"}
    envs = gang.rank_envs(base, 4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    # every rank lists the same addresses; each serves on its own port
    addrs = envs[0]["TPU_PROCESS_ADDRESSES"].split(",")
    assert all(e["TPU_PROCESS_ADDRESSES"] == envs[0]["TPU_PROCESS_ADDRESSES"]
               for e in envs)
    assert addrs == [f"localhost:{e['TPU_PROCESS_PORT']}" for e in envs]
    assert len(set(addrs)) == 4
    assert all(e["PATH"] == "/bin" for e in envs)
    assert "ALLOW_MULTIPLE_LIBTPU_LOAD" not in envs[0]
    assert "TPU_VISIBLE_CHIPS" not in base          # input untouched
    with pytest.raises(ValueError):
        gang.rank_envs(base, 8)                     # more ranks than chips


@pytest.mark.parametrize("chips,platforms", [(0, ""), (4, "cpu")])
def test_rank_envs_leave_cpu_ranks_unbound(monkeypatch, chips, platforms):
    from repro.distributed import gang
    monkeypatch.setattr(gang, "tpu_chips", lambda: chips)
    base = {"JAX_PLATFORMS": platforms} if platforms else {}
    assert gang.rank_envs(base, 2) == [base, base]


def test_gang_manifest_renders_indexed_job():
    job = JobSpec(name="ddp", gang=4)
    spec = job.manifest()["spec"]
    assert spec["completionMode"] == "Indexed"
    assert spec["completions"] == spec["parallelism"] == 4
    assert "completionMode" not in JobSpec(name="solo").manifest()["spec"]


def test_world_size_override_becomes_gang():
    from repro.api import RunSpec
    spec = RunSpec(kind="train", arch="stablelm-1.6b", seed=0,
                   name="ddp", overrides={"world_size": 2, "steps": 2})
    assert spec.to_job().gang == 2
    assert RunSpec(kind="train", arch="stablelm-1.6b", seed=0,
                   name="solo").to_job().gang == 1


def test_sharded_batches_cursor_round_trip():
    """Every rank advances the identical global stream; seeking the
    shared cursor replays identical local shards (world=1 mesh)."""
    from repro.configs import get_reduced
    from repro.data.tokens import SeekableTokenBatches
    from repro.distributed.context import init_distributed
    from repro.distributed.data import ShardedBatches

    ctx = init_distributed(1)
    cfg = get_reduced("stablelm-1.6b")
    inner = SeekableTokenBatches(cfg.vocab, 4, 8, seed=0)
    data = ShardedBatches(
        inner, ctx, to_named=lambda raw: {"tokens": raw[0],
                                          "labels": raw[1]},
        global_rows=4)
    _ = data.next_batch()
    mark = data.cursor()
    want = [np.asarray(data.next_batch()["tokens"]) for _ in range(3)]
    data.seek(mark)
    got = [np.asarray(data.next_batch()["tokens"]) for _ in range(3)]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


# --------------------------------------------------------------------------
# Hermetic gang scheduling (fake spawn — no jax per job)
# --------------------------------------------------------------------------
def _gang_job(name, gang, *, retries=3, cpus=1, priority=0):
    return JobSpec(name=name, gang=gang, retries=retries,
                   priority=priority,
                   resources=Resources(gpus=0, cpus=cpus, memory_gb=1.0),
                   env={"RUN_KIND": "train"})


def test_gang_spawns_one_process_per_rank_shared_coordinator(tmp_path):
    pvc = PersistentVolume(tmp_path)
    orch = Orchestrator(pvc)
    orch.submit(_gang_job("ddp", 2))
    seen = []

    def spawn(job, attempt, argv, env, stdout_fh, stderr_fh):
        seen.append(argv)
        return FakeProc(job, attempt, stdout_fh)

    recs = orch.run_cluster(workers=2, poll_s=0.0, telemetry=False,
                            retry_backoff_base_s=0.0, spawn=spawn)
    assert recs["ddp"].state == JobState.SUCCEEDED
    assert len(seen) == 2
    ranks = sorted(a for argv in seen for a in argv
                   if a.startswith("--dist_rank="))
    assert ranks == ["--dist_rank=0", "--dist_rank=1"]
    coords = {a for argv in seen for a in argv
              if a.startswith("--coordinator=")}
    assert len(coords) == 1              # both ranks share one address


def test_gang_rank_death_requeues_whole_gang_and_resumes(tmp_path):
    """One rank dying kills the gang (the survivor is reaped, not
    orphaned), the whole gang is requeued as preempted, and the retry
    attempt re-spawns EVERY rank with the resume overlay."""
    from repro.api import RunSpec
    pvc = PersistentVolume(tmp_path)
    orch = Orchestrator(pvc)
    # the RunSpec path (not a raw JobSpec): to_job maps world_size to
    # gang AND fills the retry_env resume overlay for train kinds
    orch.submit_runs([RunSpec(
        kind="train", arch="stablelm-1.6b", seed=0, name="ddp",
        overrides={"steps": 4, "world_size": 2,
                   "checkpoint_dir": str(tmp_path / "ck")})])
    attempts = []

    def spawn(job, attempt, argv, env, stdout_fh, stderr_fh):
        rank = next(int(a.split("=")[1]) for a in argv
                    if a.startswith("--dist_rank="))
        attempts.append((attempt, rank, argv))
        import signal as _sig
        rc = -int(_sig.SIGKILL) if (attempt == 1 and rank == 1) else 0
        return FakeProc(job, attempt, stdout_fh, rc=rc)

    recs = orch.run_cluster(workers=2, poll_s=0.0, telemetry=False,
                            retry_backoff_base_s=0.0, spawn=spawn)
    assert recs["ddp"].state == JobState.SUCCEEDED
    assert sorted((a, r) for a, r, _ in attempts) \
        == [(1, 0), (1, 1), (2, 0), (2, 1)]
    for a, _r, argv in attempts:
        assert ("--resume=true" in argv) == (a == 2)
    events = [json.loads(ln) for ln
              in pvc.read_bytes(EVENTS_REL).decode().splitlines()]
    exits = [(e["attempt"], e["rank"], e["returncode"]) for e in events
             if e["event"] == "rank_exited"]
    assert len(exits) == 4               # every rank's exit is logged
    assert any(e["event"] == "preempted" for e in events)
    state = replay_events(events)
    assert state["consistent"], state["violations"]
    assert state["jobs"]["ddp"]["gang"] == 2
    assert state["jobs"]["ddp"]["preemptions"] == 1


def test_unschedulable_gang_fails_fast_without_spawning(tmp_path):
    """A gang that can never be placed — more ranks than worker slots,
    or per-rank requests no inventory satisfies — fails at submit
    validation, before any process starts."""
    pvc = PersistentVolume(tmp_path)
    orch = Orchestrator(pvc)
    orch.submit(_gang_job("too-wide", 4))
    spawned = []

    def spawn(job, attempt, argv, env, stdout_fh, stderr_fh):
        spawned.append(job.name)
        return FakeProc(job, attempt, stdout_fh)

    recs = orch.run_cluster(workers=2, poll_s=0.0, telemetry=False,
                            retry_backoff_base_s=0.0, spawn=spawn)
    assert recs["too-wide"].state == JobState.FAILED
    assert "unschedulable" in recs["too-wide"].error
    assert "gang" in recs["too-wide"].error
    assert spawned == []
    events = [json.loads(ln) for ln
              in pvc.read_bytes(EVENTS_REL).decode().splitlines()]
    assert any(e["event"] == "unschedulable" and e.get("gang") == 4
               for e in events)


def test_gang_counts_against_worker_cap_in_processes(tmp_path):
    """workers=2 with a 2-rank gang plus singletons: never more than 2
    live processes, and everything completes."""
    pvc = PersistentVolume(tmp_path)
    orch = Orchestrator(pvc)
    orch.submit(_gang_job("ddp", 2))
    for i in range(3):
        orch.submit(_gang_job(f"solo{i}", 1))
    tracker = {"active": 0, "max": 0}
    recs = orch.run_cluster(workers=2, poll_s=0.0, telemetry=False,
                            retry_backoff_base_s=0.0,
                            spawn=fake_spawn(tracker=tracker))
    assert tracker["max"] <= 2
    assert all(r.state == JobState.SUCCEEDED for r in recs.values())


def test_status_renders_gang_as_one_row_with_rank_states(tmp_path):
    pvc = PersistentVolume(tmp_path)
    orch = Orchestrator(pvc)
    orch.submit(_gang_job("ddp", 2))
    orch.run_cluster(workers=2, poll_s=0.0, telemetry=False,
                     retry_backoff_base_s=0.0, spawn=fake_spawn())
    state = replay_events(pvc.read_bytes(EVENTS_REL).decode()
                          .splitlines())
    st = state["jobs"]["ddp"]
    assert st["gang"] == 2 and st["gang_id"] == "ddp.g1"
    assert {r["returncode"] for r in st["ranks"].values()} == {0}
    text = format_status(state)
    assert sum(ln.startswith("ddp") for ln in text.splitlines()) == 1
    assert "2[0:0 1:0]" in text


# --------------------------------------------------------------------------
# System: world=1 bitwise identity, world=2 oracle + chaos resume
# --------------------------------------------------------------------------
STEPS, CKPT_EVERY, GLOBAL_BATCH, SEQ = 6, 2, 4, 16


def _final_tree(ckpt_dir):
    from repro.checkpoint import list_checkpoints, load_checkpoint
    ckpts = list_checkpoints(ckpt_dir)
    assert ckpts, f"no published checkpoints under {ckpt_dir}"
    tree, step = load_checkpoint(ckpts[-1][1])
    return tree, int(step)


@pytest.mark.timeout(300)
def test_dist_world1_bitwise_equals_single_process(tmp_path):
    """The distributed trainer at world=1 (one-device mesh, no
    distributed runtime) IS the single-process trainer: identical loss
    scalars and bitwise-identical final checkpoints."""
    from repro.distributed.trainer import dist_train_main
    from repro.launch.train import train_main

    kw = dict(reduced=True, steps=STEPS, batch=GLOBAL_BATCH, seq=SEQ,
              seed=0, log_every=0, checkpoint_every=CKPT_EVERY,
              checkpoint_async=False)
    plain = train_main("stablelm-1.6b",
                       checkpoint_dir=str(tmp_path / "plain"), **kw)
    dist = dist_train_main("stablelm-1.6b", world_size=1,
                           checkpoint_dir=str(tmp_path / "dist"), **kw)
    assert dist["dist"]["allreduce_bytes_per_step"] == 0
    assert dist["first_loss"] == plain["first_loss"]
    assert dist["final_loss"] == plain["final_loss"]
    got, got_step = _final_tree(tmp_path / "dist")
    want, want_step = _final_tree(tmp_path / "plain")
    assert got_step == want_step == STEPS
    assert set(got) == set(want) and len(want) > 0
    for key in sorted(want):
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


def _gang_run(name, *, ckpt_dir, seed=0):
    from repro.api import RunSpec
    return RunSpec(kind="train", arch="stablelm-1.6b", seed=seed,
                   name=name,
                   overrides={"steps": STEPS, "batch": GLOBAL_BATCH,
                              "seq": SEQ, "world_size": 2,
                              "log_every": 0,
                              "checkpoint_every": CKPT_EVERY,
                              "checkpoint_dir": str(ckpt_dir)})


@pytest.mark.timeout(600)
def test_gang_world2_oracle_and_chaos_resume_bitwise(tmp_path):
    """The tentpole's end-to-end contract, in two campaign legs:

    1. a world=2 gang through the executor reproduces the world=1 loss
       trajectory at the same global batch to documented tolerance (the
       only divergence is psum reassociation of the batch mean, ~1e-6);
    2. the same gang with chaos — one rank SIGKILLed mid-run — gang-
       requeues, resumes from the shared checkpoint, and lands final
       params bitwise identical to the undisturbed gang (identical
       world partitioning, so not even reassociation differs).
    """
    from repro.distributed.trainer import dist_train_main

    ref = dist_train_main(
        "stablelm-1.6b", world_size=1, reduced=True, steps=STEPS,
        batch=GLOBAL_BATCH, seq=SEQ, seed=0, log_every=0)

    # ---- leg 1: undisturbed gang campaign -> tolerance oracle
    pvc = PersistentVolume(tmp_path / "campA")
    orch = Orchestrator(pvc)
    orch.submit_runs([_gang_run("ddp-a", ckpt_dir=tmp_path / "ckA")])
    recs = orch.run_cluster(workers=2, retry_backoff_base_s=0.0,
                            telemetry=False)
    assert recs["ddp-a"].state == JobState.SUCCEEDED
    metrics = recs["ddp-a"].result["metrics"]
    assert metrics["dist"]["world_size"] == 2
    assert metrics["dist"]["allreduce_bytes_per_step"] \
        == metrics["dist"]["grad_bytes"]       # 2*(N-1)/N at N=2
    np.testing.assert_allclose(metrics["losses"], ref["losses"],
                               rtol=5e-4, atol=5e-4)

    # ---- leg 2: chaos kills one rank; gang resume is bitwise
    pvc_b = PersistentVolume(tmp_path / "campB")
    orch_b = Orchestrator(pvc_b)
    orch_b.submit_runs([_gang_run("ddp-b", ckpt_dir=tmp_path / "ckB")])
    recs_b = orch_b.run_cluster(
        workers=2, retry_backoff_base_s=0.0, telemetry=False,
        chaos=ChaosSpec(kill_jobs=("ddp-b",), after_checkpoints=1))
    assert recs_b["ddp-b"].state == JobState.SUCCEEDED
    events = [json.loads(ln) for ln
              in pvc_b.read_bytes(EVENTS_REL).decode().splitlines()]
    kills = [e for e in events if e["event"] == "chaos_kill"]
    assert kills and all(e["rank"] == 1 for e in kills)
    state = replay_events(events)
    assert state["consistent"], state["violations"]
    st = state["jobs"]["ddp-b"]
    assert st["gang"] == 2 and st["preemptions"] >= 1
    assert recs_b["ddp-b"].result["metrics"]["resumed_from_step"] \
        is not None

    got, got_step = _final_tree(tmp_path / "ckB")
    want, want_step = _final_tree(tmp_path / "ckA")
    assert got_step == want_step == STEPS
    assert set(got) == set(want) and len(want) > 0
    for key in sorted(want):
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
