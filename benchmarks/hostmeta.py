"""Host/backend metadata stamped into every BENCH_*.json.

The paper's speed tables are meaningless without the hardware row ("on an
i7-4770", "Chrome 46 on..."); ours are too. ``stamp(payload)`` attaches a
``host`` block so every machine-readable benchmark artifact records the
jax version, backend, device kind and platform it was measured on — plus
an ``env`` block (:func:`env_block`) capturing the knobs that change
numbers without changing code (``XLA_FLAGS``, x64 mode, the forced host
device count, whether Pallas ran in interpret mode) and the tiled-kernel
autotune cache, so rows from different hosts are comparable and a
regression gate can refuse to compare apples to oranges.
"""
from __future__ import annotations

import os
import platform
import re
import time
from typing import Any, Dict


def env_block() -> Dict[str, Any]:
    """Reproducibility knobs for benchmark comparability.

    ``pallas_interpret`` is the single most important bit: off-TPU the
    pallas rows measure the interpreter emulation, not the hardware.
    """
    import jax
    from repro.kernels import interpret_mode

    xla_flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                  xla_flags)
    return {
        "xla_flags": xla_flags,
        "jax_enable_x64": bool(jax.config.jax_enable_x64),
        "host_platform_device_count": int(m.group(1)) if m else None,
        "pallas_interpret": interpret_mode(),
        "jax_default_prng_impl": str(
            getattr(jax.config, "jax_default_prng_impl", "threefry2x32")),
    }


def host_metadata() -> Dict[str, Any]:
    import jax
    from repro.kernels.ga import autotune

    dev = jax.devices()[0]
    return {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
        "python_version": platform.python_version(),
        "cpu_count": os.cpu_count(),
        "env": env_block(),
        "ga_autotune": autotune.cache_summary(),
    }


def stamp(payload: Dict[str, Any]) -> Dict[str, Any]:
    """Attach the host block (in place) and return ``payload``."""
    payload["host"] = host_metadata()
    return payload
