"""Training launcher.

``python -m repro.launch.train --arch <id> [--reduced] --steps N``

On this CPU container only reduced configs actually execute; the full
configs are exercised by the dry-run (``repro.launch.dryrun``).  The same
entrypoint is what a Kubernetes job manifest's container command would
invoke on real hardware — env-var overrides mirror the paper's
bash-automation interface.

Training runs through :class:`repro.train.TrainLoop`: step execution and
metrics live there, and with ``--checkpoint-dir`` the **full**
``TrainState`` (params + optimizer state + step) plus the data cursor is
checkpointed atomically on a ``--checkpoint-every`` cadence and at run
end.  ``--resume`` restores the newest valid checkpoint (falling back
past torn ones) so a preempted job continues instead of restarting;
``--preempt-at-step`` injects the kill for tests/CI.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager, export_to_s3
from repro.configs import get_config, get_reduced
from repro.core.artifacts import S3Store
from repro.data.inputs import SeekableSyntheticBatches
from repro.data.tokens import SeekableTokenBatches
from repro.kernels.common import kernel_paths
from repro.launch.runtime import compile_stats, device_report
from repro.optim import get_optimizer, warmup_cosine
from repro.train import TrainLoop, init_train_state, make_train_step


class _LMDictBatches(SeekableTokenBatches):
    """Seekable LM stream yielding model-ready {'tokens','labels'} dicts."""

    def next_batch(self):
        toks, labels = super().next_batch()
        return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}


def train_main(arch: str, *, reduced: bool = True, steps: int = 100,
               batch: int = 8, seq: int = 128, lr: float = 3e-4,
               optimizer: str = None, seed: int = 0,
               checkpoint_dir: str = None, s3_root: str = None,
               log_every: int = 10, checkpoint_every: int = 0,
               checkpoint_keep: int = 3, checkpoint_async: bool = True,
               resume: bool = False, preempt_at_step: int = None,
               precision: str = "f32", grad_clip: float = None,
               microbatches: int = 1,
               attention_backend: str = None,
               mixer_backend: str = None) -> dict:
    cfg = get_reduced(arch) if reduced else get_config(arch)
    backends = {}
    if attention_backend:
        backends["attention_backend"] = attention_backend
    if mixer_backend:
        backends["mixer_backend"] = mixer_backend
    if backends:
        cfg = dataclasses.replace(cfg, **backends)
    opt = get_optimizer(optimizer or cfg.optimizer)
    # jit + donation live in make_train_step: the input TrainState is
    # consumed each step (params/opt_state updated in place)
    step_fn = make_train_step(
        cfg, opt, lr_schedule=warmup_cosine(lr, steps,
                                            warmup_steps=max(steps // 10, 1)),
        precision=precision, grad_clip=grad_clip,
        microbatches=max(1, int(microbatches)))

    text_lm = cfg.family in ("dense", "moe", "ssm", "hybrid")
    data = (_LMDictBatches(cfg.vocab, batch, seq, seed) if text_lm
            else SeekableSyntheticBatches(cfg, batch, seq, seed))

    ckpt = None
    if checkpoint_dir:
        ckpt = CheckpointManager(checkpoint_dir,
                                 keep_last=max(int(checkpoint_keep), 1),
                                 every_steps=int(checkpoint_every),
                                 async_saves=bool(checkpoint_async))
    with compile_stats() as compiled:
        # the loop holds the only reference to the state: resume frees it
        # before the restored one lands on the device
        loop = TrainLoop(step_fn, init_train_state(jax.random.PRNGKey(seed),
                                                   cfg, opt),
                         data, checkpointer=ckpt,
                         preempt_at_step=preempt_at_step, log_every=log_every)
        if resume:
            loop.resume()
        try:
            run = loop.run(steps)
        finally:
            if ckpt is not None:
                ckpt.wait()

    result = {
        "arch": cfg.name, "params": cfg.param_count(),
        **run,
        "device": device_report(), "kernels": kernel_paths(cfg),
        "compile": compiled,
    }
    if steps <= 512:
        # oracle tests compare full trajectories (e.g. an elastically
        # shrunk gang's world=1 continuation vs a pure world=1 run);
        # bounded so long runs don't bloat their reports
        result["losses"] = list(loop.losses)
    if ckpt is not None:
        loop.save_final(extra={"arch": cfg.name,
                               "final_loss": run.get("final_loss")})
        overhead = result.get("checkpoint", {}).get("overhead_frac", 0.0)
        result["checkpoint"] = {**ckpt.stats(), "overhead_frac": overhead}
        ckpt.close()
        if s3_root:
            s3 = S3Store(s3_root)
            n = export_to_s3(checkpoint_dir, s3, f"models/{cfg.name}")
            result["s3_objects"] = n
    return result


def main():
    # thin shim over the repro.api registry (RunSpec in, RunReport out)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=os.environ.get("ARCH", "stablelm-1.6b"))
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int,
                    default=int(os.environ.get("STEPS", 100)))
    ap.add_argument("--batch", type=int,
                    default=int(os.environ.get("BATCH", 8)))
    ap.add_argument("--seq", type=int, default=int(os.environ.get("SEQ", 128)))
    ap.add_argument("--lr", type=float, default=float(os.environ.get("LR", 3e-4)))
    ap.add_argument("--optimizer", default=os.environ.get("OPTIMIZER"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="save the full TrainState every N steps")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid checkpoint before "
                         "training")
    ap.add_argument("--preempt-at-step", type=int, default=None,
                    help="fault hook: raise Preemption before this step")
    ap.add_argument("--s3-root", default=None)
    ap.add_argument("--precision", default=os.environ.get("PRECISION", "f32"),
                    choices=["f32", "bf16"],
                    help="mixed-precision policy: f32 master params + "
                         "optimizer state always; bf16 = bf16 "
                         "compute/activations")
    ap.add_argument("--grad-clip", type=float, default=None,
                    help="clip the global gradient norm to this value")
    ap.add_argument("--attention-backend", default=None,
                    choices=["jnp", "pallas", "auto"],
                    help="attention kernel backend (default: config's, "
                         "'auto' = Pallas on TPU, jnp elsewhere)")
    ap.add_argument("--mixer-backend", default=None,
                    choices=["jnp", "pallas", "auto"],
                    help="SSD mixer kernel backend")
    ap.add_argument("--world-size", type=int, default=1,
                    help=">1: data-parallel gang of N rank processes "
                         "(--batch is the GLOBAL batch)")
    ap.add_argument("--dist-rank", type=int, default=None,
                    help="this process's rank (set by the gang launcher)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of rank 0 (jax.distributed)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation chunks per step")
    args = ap.parse_args()

    from repro.api import RunSpec, run
    overrides = {"full": args.full, "steps": args.steps, "batch": args.batch,
                 "seq": args.seq, "lr": args.lr}
    if args.optimizer:
        overrides["optimizer"] = args.optimizer
    if args.precision != "f32":
        overrides["precision"] = args.precision
    if args.grad_clip is not None:
        overrides["grad_clip"] = args.grad_clip
    if args.attention_backend:
        overrides["attention_backend"] = args.attention_backend
    if args.mixer_backend:
        overrides["mixer_backend"] = args.mixer_backend
    if args.checkpoint_dir:
        overrides["checkpoint_dir"] = args.checkpoint_dir
    if args.checkpoint_every:
        overrides["checkpoint_every"] = args.checkpoint_every
    if args.resume:
        overrides["resume"] = True
    if args.preempt_at_step is not None:
        overrides["preempt_at_step"] = args.preempt_at_step
    if args.s3_root:
        overrides["s3_root"] = args.s3_root
    if args.world_size != 1:
        overrides["world_size"] = args.world_size
    if args.dist_rank is not None:
        overrides["dist_rank"] = args.dist_rank
    if args.coordinator:
        overrides["coordinator"] = args.coordinator
    if args.microbatches != 1:
        overrides["microbatches"] = args.microbatches
    report = run(RunSpec(kind="train", arch=args.arch, seed=args.seed,
                         overrides=overrides))
    print(json.dumps(report.metrics, indent=1))
    if not report.ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
