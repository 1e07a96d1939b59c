"""``ds_report`` — environment / op-compatibility report (reference:
deepspeed/env_report.py + bin/ds_report): versions, devices, native-op
build status."""
from __future__ import annotations

import sys


def _device_line(deadline_s: int = 45) -> tuple:
    """Device enumeration with a hard deadline.

    ``jax.devices()`` can block for as long as the accelerator runtime
    does not answer (another process holds the chip, a runtime that is
    down) — and a report tool that hangs is worse than useless when
    diagnosing exactly that situation.  The probe runs in a subprocess
    so a hung backend init cannot take the report down with it; the
    parent never initializes a backend itself.
    """
    import os
    import subprocess
    try:
        deadline_s = int(os.environ.get("DS_REPORT_DEVICE_TIMEOUT",
                                        str(deadline_s)))
    except ValueError:
        # the diagnostic tool must not die on a malformed knob — that is
        # the exact robustness this function exists for
        pass
    code = ("import jax; d = jax.devices(); "
            "print(d[0].platform, len(d), "
            "getattr(d[0], 'device_kind', '?'), sep='|')")
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=deadline_s)
    except subprocess.TimeoutExpired:
        return ("devices", f"UNREACHABLE (no response in {deadline_s}s "
                "— accelerator runtime down or held by another process)")
    if r.returncode != 0:
        tail = (r.stderr or "").strip().splitlines()
        why = tail[-1] if tail else "init failed"
        return ("devices", f"unavailable ({why})")
    try:
        platform, n, kind = r.stdout.strip().split("|")
        return ("devices", f"{n} × {kind} (platform {platform})")
    except ValueError:
        return ("devices", f"unparseable probe output {r.stdout!r}")


def collect_report() -> list:
    lines = []
    lines.append(("python", sys.version.split()[0]))
    for mod in ("jax", "jaxlib", "numpy", "optax", "flax"):
        try:
            m = __import__(mod)
            lines.append((mod, getattr(m, "__version__", "?")))
        except ImportError:
            lines.append((mod, "NOT INSTALLED"))
    lines.append(_device_line())
    from .ops.op_builder import cpu_ops_status
    lines.append(("native host ops", cpu_ops_status()))
    # per-op compatibility matrix (the reference ds_report's main table)
    from .git_version_info import compatible_ops
    for op, ok in sorted(compatible_ops.items()):
        lines.append((f"op {op}", "compatible" if ok else "UNAVAILABLE"))
    from . import __version__
    from .git_version_info import git_hash, git_branch
    lines.append(("deepspeed_tpu", f"{__version__} "
                  f"(git {git_hash}, {git_branch})"))
    return lines


def main():
    print("-" * 60)
    print("deepspeed_tpu environment report")
    print("-" * 60)
    for key, val in collect_report():
        print(f"{key:.<24} {val}")
    print("-" * 60)


if __name__ == "__main__":
    main()
