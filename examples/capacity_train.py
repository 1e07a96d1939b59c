"""The full ZeRO-Offload capacity stack — train a model far past what
HBM alone holds (the reference's "10x larger models" headline,
docs/_pages/features.md:115 there; this stack reaches past it):

  cpu_offload + offload_impl=xla   fp32 master + Adam moments live in
                                   pinned host memory, Adam runs as an
                                   XLA host computation
  offload_grad_chunks=K            gradients computed in K balanced
                                   groups (K forward recomputes) so
                                   device grad bytes ~ largest group
  param_streaming + stream_scan    ZeRO-Infinity-style: compute copies
                                   of the stacked block params stay in
                                   host memory; the model fetches ONE
                                   layer per scan tick — device param
                                   bytes ~ one layer, past the
                                   2 bytes/param floor

    python examples/capacity_train.py --cpu --steps 5      # smoke
    python examples/capacity_train.py --layers 96          # on TPU

Peak trainable params per chip (plain vs offload vs chunked vs streamed)
has no measurement on the current chip: ROADMAP.md S8 / W7.
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models import GPT2Config, GPT2Model  # noqa: E402
from deepspeed_tpu.parallel import build_mesh  # noqa: E402


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--layers", type=int, default=48)
    parser.add_argument("--d-model", type=int, default=1600)
    parser.add_argument("--seq", type=int, default=1024)
    parser.add_argument("--chunks", type=int, default=4,
                        help="offload_grad_chunks (1 disables)")
    parser.add_argument("--no-stream", action="store_true",
                        help="disable param streaming (chunks only)")
    parser.add_argument("--cpu", action="store_true")
    parser = deepspeed_tpu.add_config_arguments(parser)
    args = parser.parse_args()
    if args.cpu:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")
        # tiny shapes for the smoke: the point here is the code path
        args.layers, args.d_model, args.seq = 3, 64, 64
    import jax

    deepspeed_tpu.init_distributed()
    mesh = build_mesh(devices=jax.devices()[:1])  # capacity is per-chip
    stream = not args.no_stream
    vocab = 4096 if args.cpu else 50257
    model = GPT2Model(GPT2Config(
        vocab_size=vocab, n_positions=args.seq, d_model=args.d_model,
        n_layer=args.layers, n_head=max(4, args.d_model // 64),
        remat="block", scan_layers=True, stream_scan=stream))

    config = args.deepspeed_config or {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 5,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": dict(
            {"stage": 2, "cpu_offload": True, "offload_impl": "xla"},
            **({"offload_grad_chunks": args.chunks}
               if args.chunks > 1 else {}),
            **({"param_streaming": True} if stream else {})),
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config,
                                               mesh=mesh)
    n_params = model.config.num_params
    print(f"{n_params / 1e9:.2f}B params | chunks={args.chunks} "
          f"stream={stream} | mesh={dict(mesh.shape)}")
    rng = np.random.default_rng(0)
    for step in range(args.steps):
        toks = rng.integers(0, vocab,
                            (engine.train_batch_size, args.seq + 1),
                            dtype=np.int32)
        loss = engine.train_batch(toks)
        if (step + 1) % 5 == 0:
            print(f"step {step + 1}: loss {float(np.asarray(loss)):.4f}")


if __name__ == "__main__":
    main()
