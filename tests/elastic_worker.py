"""Worker script for the elastic-training e2e tests: trains a tiny
linear model through the
engine's OWN data-iterator chain (DeepSpeedDataLoader → RepeatingLoader
→ DevicePrefetcher), records per-step losses and every PRODUCED batch's
sample indices, checkpoints every step, and optionally hard-kills
itself mid-run on the first attempt (``DS_ELASTIC_RESTART=0``).

The dp width comes from ``DS_ELASTIC_WORLD_SLOTS`` (the supervisor's
export), so a shrunk relaunch automatically re-forms a smaller mesh and
the reshard-on-load checkpoint restore does the rest.

argv: out_dir ckpt_dir total_steps crash_at [default_slots]
  crash_at > 0: os._exit(3) after completing (and checkpointing) step
  crash_at, first attempt only — a hard kill, not a graceful close, so
  prefetched in-flight batches are genuinely abandoned.
"""
import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.parallel import build_mesh  # noqa: E402
from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,  # noqa: E402
                                              RepeatingLoader)
from deepspeed_tpu.runtime.module import TrainModule  # noqa: E402

HIDDEN = 8
GLOBAL_BS = 8
DATASET_N = 48  # 6 batches/epoch: multi-epoch runs exercise reshuffle


class TinyModel(TrainModule):
    def init(self, rng):
        import jax.numpy as jnp
        k1, _ = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (HIDDEN, HIDDEN),
                                       jnp.float32) * 0.1,
                "b": jnp.zeros((HIDDEN,), jnp.float32)}

    def loss_fn(self, params, batch, rng, train=True):
        import jax.numpy as jnp
        x, y = batch
        h = x @ params["w"].astype(x.dtype) + params["b"].astype(x.dtype)
        return jnp.mean((h.astype(jnp.float32)
                         - y.astype(jnp.float32)) ** 2)


def build_dataset():
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((DATASET_N, HIDDEN)).astype(np.float32)
    # feature 0 IS the sample index — the identity channel the
    # sample-exactness assertions read back out of the collate log
    xs[:, 0] = np.arange(DATASET_N, dtype=np.float32)
    return [(xs[i], (0.5 * xs[i]).astype(np.float32))
            for i in range(DATASET_N)]


def main():
    out_dir, ckpt_dir = sys.argv[1], sys.argv[2]
    total_steps = int(sys.argv[3])
    crash_at = int(sys.argv[4])
    default_slots = int(sys.argv[5]) if len(sys.argv) > 5 else 1
    restart = int(os.environ.get("DS_ELASTIC_RESTART", "0"))
    slots = int(os.environ.get("DS_ELASTIC_WORLD_SLOTS", default_slots))
    dp = max(min(slots, len(jax.devices())), 1)

    os.makedirs(out_dir, exist_ok=True)
    samples_log = open(
        os.path.join(out_dir, f"samples_r{restart}.jsonl"), "a")

    def collate(samples):
        xs = np.stack([np.asarray(s[0]) for s in samples])
        ys = np.stack([np.asarray(s[1]) for s in samples])
        # production-order log: prefetched-but-unconsumed batches appear
        # here too — the assertions trim to the consumed count
        samples_log.write(
            json.dumps([int(v) for v in xs[:, 0]]) + "\n")
        samples_log.flush()
        return (xs, ys)

    mesh = build_mesh(dp=dp, devices=jax.devices()[:dp])
    cfg = {
        "train_batch_size": GLOBAL_BS,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10 ** 9,
        # fp32 end to end: the dp4-vs-dp2 trajectory equivalence
        # tolerates only reduction-order noise
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "data_prefetch": {"enabled": True, "depth": 2},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TinyModel(), config=cfg, mesh=mesh)
    engine.training_dataloader = RepeatingLoader(DeepSpeedDataLoader(
        build_dataset(), batch_size=GLOBAL_BS, collate_fn=collate,
        shuffle=True, seed=5))

    path, _ = engine.load_checkpoint(ckpt_dir)  # fallback chain; None=fresh
    start = engine.global_steps
    traj = open(os.path.join(out_dir, f"traj_r{restart}.jsonl"), "a")
    for step in range(start, total_steps):
        loss = float(np.asarray(engine.train_batch()))
        engine.save_checkpoint(ckpt_dir)
        traj.write(json.dumps({"step": step, "loss": loss, "dp": dp})
                   + "\n")
        traj.flush()
        if crash_at and restart == 0 and step + 1 == crash_at:
            os._exit(3)  # hard kill: no close(), prefetched batches die
    engine.close()
    print("ELASTIC_WORKER_DONE", flush=True)


if __name__ == "__main__":
    main()
