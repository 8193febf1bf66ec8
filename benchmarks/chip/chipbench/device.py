"""The chips a run may use, their published peaks, and what JAX reports."""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Sequence

PEAKS_JSON = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(SystemExit):
    """No TPU, or fewer chips than the cell asks for."""


def tpu_devices(n: int) -> list:
    """The first ``n`` TPU devices.  Raises :class:`NoChip` on any other
    platform or with fewer chips: a run never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX's devices are "
                     f"{devs[0].platform if devs else None!r}); this "
                     f"benchmark measures TPU chips only")
    if len(devs) < n:
        raise NoChip(f"{n} TPU chips asked for, {len(devs)} visible")
    return devs[:n]


def peaks(device_kind: str, path: pathlib.Path = PEAKS_JSON) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path.name} "
                       f"(known: {sorted(table)})")
    return {k: float(v) for k, v in table[device_kind].items()}


def compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``), keeping every program, also
    those that compile in under a second, so later runs' set-up is steady."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def describe(devs: Sequence) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes(devs: Sequence) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the backend
    reports nothing)."""
    out = 0
    for d in devs:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


class CompileCounter:
    """Counts XLA backend compilations, to show none happen in a window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
