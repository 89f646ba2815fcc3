"""Unified observability: trace spans, metrics, and the wave-stats schema.

``repro.obs`` is the engine's single timing plane.  One
:class:`TraceRecorder` (the ``obs=`` object every subsystem accepts) carries
both the structured span/event stream and a :class:`MetricsRegistry`;
:func:`make_wave_stats` is the one schema every serving pool's
``last_wave_stats`` conforms to.  Everything is opt-in: the default
``obs=None`` keeps every hot path at exactly one attribute test, and a
disabled recorder performs zero clock reads and zero per-event allocations
(see :mod:`repro.obs.trace`).
"""
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_SPAN, TraceRecorder, span
from repro.obs.wave_stats import (
    WAVE_STATS_KEYS, make_wave_stats, record_wave_metrics,
)

__all__ = [
    "MetricsRegistry",
    "NULL_SPAN",
    "TraceRecorder",
    "WAVE_STATS_KEYS",
    "make_wave_stats",
    "record_wave_metrics",
    "span",
]
