"""chip_smoke.py's phases at toy size on the CPU mesh (the first and second
rehearsals of a chip run: control flow, arguments, meshes and sharding
rules), and its refusal to stand a CPU run in for a chip."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Config  # noqa: E402

TOY = GPT2Config(vocab_size=512, n_positions=128, d_model=64, n_layer=2,
                 n_head=4, attn_impl="flash")


def test_train_phase_toy():
    out = chip_smoke.train_phase(TOY, micro_batch=4, seq=64, steps=4,
                                 ref_chunk=2, fall=0.02)
    assert len(out["losses"]) == 4
    assert out["kernels"] == 0          # interpreted here: no Mosaic call


def test_serve_phase_toy():
    out = chip_smoke.serve_phase(TOY, slots=4, page_len=8, max_seq_len=64,
                                 prefill_len=32)
    assert out["tokens"] == 52          # 12 + 8 + 16 + 10 + 6 per wave
    assert out["agree"] >= chip_smoke.AGREE_FLOOR
    assert out["slack"] <= chip_smoke.LOGIT_TOL


def test_zero_dp_phase_toy_four_virtual_devices():
    out = chip_smoke.zero_dp_phase(TOY, jax.devices()[:4], global_batch=8,
                                   seq=64, steps=4)
    assert out["collectives"]["all-gather"] > 0


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_refuses_without_a_tpu(argv):
    """No accelerator: non-zero exit, no result, ``"ok": false`` last."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py", *argv], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


@pytest.mark.parametrize("case", ["env", "checkout", "installed"])
def test_compile_cache_is_placed_from_outside(case, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: nothing is configured in code.
    Unset: the fixed <checkout>/.jax_cache; nothing for an installed
    package, which has no checkout."""
    from deepspeed_tpu.utils import compile_cache
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *kv: updates.append(kv))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    if case == "env":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere")
        want, want_updates = "/somewhere", []
    elif case == "checkout":
        want = os.path.join(REPO, ".jax_cache")
        want_updates = [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setattr(compile_cache, "_ROOT",
                            "/opt/venv/lib/python3/site-packages")
        want, want_updates = None, []
    assert compile_cache.enable_compile_cache() == want
    assert updates == want_updates
