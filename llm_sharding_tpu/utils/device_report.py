"""What this process runs on, as one JSON-able dict.

The serve daemon publishes it under ``/statz`` (``"device"``): a harness
that must not touch the chip itself — one process per chip — reads the
platform, the device kind and count, the package versions, per-device
memory and the compile-cache directory from the process that holds it.
``chip_smoke.py`` refuses to pass unless ``platform`` is ``"tpu"``.
"""

from __future__ import annotations

import importlib.metadata

#: ``memory_stats()`` keys worth reporting (backends that report nothing —
#: the CPU — yield an entry with the device id only).
_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def _version(dist: str):
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def device_report() -> dict:
    import jax

    devices = jax.devices()
    memory = []
    for d in devices:
        stats = d.memory_stats() or {}
        memory.append(
            {"id": d.id, **{k: stats[k] for k in _MEMORY_KEYS if k in stats}}
        )
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": _version("jaxlib"),
        "libtpu": _version("libtpu"),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "memory": memory,
    }
