"""Helpers the drivers share: device memory, host spans, compiles."""

from __future__ import annotations


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest of `devs` (0 where not reported)."""
    peaks = [0]
    for d in devs:
        try:
            st = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — a backend without stats
            st = {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = [0, False]


def compile_count() -> int:
    """Programs this process has compiled or loaded from the persistent
    cache so far (JAX records the event for both); difference it around a
    window, where it should not move."""
    import jax

    if not _compiles[1]:
        def on_event(event, secs, **kw):
            if event == _COMPILE_EVENT:
                _compiles[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        _compiles[1] = True
    return _compiles[0]
