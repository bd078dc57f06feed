"""Environment / capability report (reference ``deepspeed/env_report.py``,
the ``ds_report`` CLI): what backend is live, which native extensions
built, which kernel paths are active.

Usage::

    python -m shuffle_exchange_tpu.env_report
"""

from __future__ import annotations

import importlib
import os
import sys


def _row(name: str, status: str, note: str = "") -> str:
    return f"{name:<28} {status:<12} {note}"


def collect(probe_devices: bool = True) -> list:
    """Rows of (name, status, note). ``probe_devices=False`` skips backend
    bring-up (a report that must not take the chip from its owner)."""
    rows = []

    for mod in ("jax", "flax", "optax", "orbax.checkpoint", "numpy"):
        try:
            m = importlib.import_module(mod)
            rows.append((mod, "ok", getattr(m, "__version__", "")))
        except Exception as e:  # pragma: no cover
            rows.append((mod, "MISSING", type(e).__name__))

    if probe_devices:
        try:
            import jax

            devs = jax.devices()
            rows.append(("backend", jax.default_backend(),
                         f"{len(devs)} device(s): {devs[0].device_kind}"))
        except Exception as e:
            rows.append(("backend", "ERROR", str(e)[:80]))
    else:
        rows.append(("backend", "skipped", "probe_devices=False"))

    if probe_devices:
        # pallas_enabled() asks the live backend — only safe when probing
        from .ops.dispatch import pallas_enabled

        try:
            on = pallas_enabled()
            rows.append(("pallas kernels", "enabled" if on else "disabled",
                         "" if on else "non-TPU backend or SXT_DISABLE_PALLAS"))
        except Exception as e:  # pragma: no cover
            rows.append(("pallas kernels", "ERROR", str(e)[:80]))
    elif os.environ.get("SXT_DISABLE_PALLAS"):
        rows.append(("pallas kernels", "disabled", "SXT_DISABLE_PALLAS set"))
    else:
        rows.append(("pallas kernels", "auto", "enabled on a TPU backend"))

    try:
        from jax.experimental.pallas.ops.tpu.megablox import gmm  # noqa: F401

        rows.append(("megablox grouped GEMM", "available", ""))
    except Exception:
        rows.append(("megablox grouped GEMM", "unavailable",
                     "MoE ragged path uses lax.ragged_dot"))

    # native (C++) runtime lib (aio + cpu_optim + packbits, csrc/) — built
    # lazily into the build dir; report without triggering a build
    try:
        import glob

        from .ops.native.builder import _build_dir

        sos = glob.glob(os.path.join(_build_dir(), "*.so"))
        rows.append(("native runtime (csrc)", "built" if sos else "not built",
                     sos[0] if sos else "g++ builds it on first use"))
    except Exception as e:  # pragma: no cover
        rows.append(("native runtime (csrc)", "ERROR", str(e)[:80]))
    return rows


def main(argv=None) -> int:
    probe = "--no-device" not in (argv or sys.argv[1:])
    print("shuffle_exchange_tpu environment report")
    print("-" * 72)
    for name, status, note in collect(probe_devices=probe):
        print(_row(name, status, note))
    return 0


if __name__ == "__main__":
    sys.exit(main())
