"""1-bit Adam convergence-parity artifact.

Trains the same toy regression model with OneBitAdam (freeze_step=15,
error-feedback sign-compressed gradient exchange after the boundary) and
plain Adam on identical data/seeds over an 8-way data-parallel mesh, and
writes both loss curves to ``docs/artifacts/onebit_convergence.json``.

This is the loss-curve evidence behind the reference's "same convergence
as Adam" claim (reference
docs/_posts/2020-09-09-onebit-adam-blog-post.md:85); the regression test
asserting terminal parity is
tests/test_onebit_engine.py::test_onebit_terminal_loss_parity_with_adam.

Run from the repo root:  python examples/onebit_convergence.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

# CPU-mesh artifact by design (8 virtual devices): ask for the CPU
# backend before any backend starts
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from deepspeed_tpu.config import DeepSpeedConfig  # noqa: E402
from deepspeed_tpu.parallel import build_mesh  # noqa: E402
from deepspeed_tpu.runtime.engine import DeepSpeedEngine  # noqa: E402
from simple_model import SimpleModel, base_config, random_batches  # noqa: E402

STEPS, FREEZE, LR = 120, 30, 5e-3


def _run(opt_type: str, extra: dict) -> list:
    cfg_dict = base_config(micro_bs=8, grad_acc=1)
    cfg_dict["optimizer"] = {"type": opt_type,
                             "params": {"lr": LR, **extra}}
    eng = DeepSpeedEngine(
        SimpleModel(hidden_dim=16, nlayers=2),
        DeepSpeedConfig(cfg_dict, world_size=8),
        mesh=build_mesh(dp=8, devices=jax.devices()[:8]))
    return [float(np.asarray(eng.train_batch(b)))
            for b in random_batches(64, 16, num_batches=STEPS, seed=21)]


def main():
    onebit = _run("OneBitAdam", {"freeze_step": FREEZE})
    adam = _run("Adam", {})
    tail = max(1, STEPS // 10)
    out = {
        "task": "SimpleModel regression, dp=8, bf16, lr=%g" % LR,
        "steps": STEPS,
        "freeze_step": FREEZE,
        "onebit_loss": onebit,
        "adam_loss": adam,
        "terminal_tail_mean": {
            "onebit": float(np.mean(onebit[-tail:])),
            "adam": float(np.mean(adam[-tail:])),
        },
        "parity_ratio": float(np.mean(onebit[-tail:])
                              / max(np.mean(adam[-tail:]), 1e-12)),
    }
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "artifacts", "onebit_convergence.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"parity_ratio": out["parity_ratio"],
                      "onebit_terminal": out["terminal_tail_mean"]["onebit"],
                      "adam_terminal": out["terminal_tail_mean"]["adam"]}))


if __name__ == "__main__":
    main()
