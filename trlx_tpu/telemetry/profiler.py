"""Programmatic ``jax.profiler`` windows: one xplane trace per phase.

Tracing a whole run is gigabytes, and the first optimizer steps say
nothing about "what did phase 37 overlap with". ``train.profile_phase: N``
opens the profiler for EXACTLY phase N (one collect→train pair) and
closes it at the phase boundary, yielding one loadable xplane/Perfetto
artifact. ``train.profile_dir`` alone means phase 0. Either way the run
keeps the schedule it would have had: turning the profiler on changes
nothing that it measures.

The trace and the tracer share a clock: every recorded span of
``telemetry/tracer.py`` is also written into the open profiler session
as the host event ``trlx/<span name>``, so the span tree of the profiled
phase lies on the host plane of the xplane, beside the device ops it
caused (the tracer's own ring keeps ``time.monotonic`` stamps; the two
are the same intervals on two clocks, not one shared wall-clock).

The stop fence (``block_until_ready``) sits at a phase boundary that
already synchronizes (the phase's stats were fetched), so the window
adds no new device syncs to the steady-state loop.
"""

from __future__ import annotations

from typing import Any, Optional


class PhaseProfiler:
    """Start/stop a ``jax.profiler`` trace around one phase.

    Drive with :meth:`on_phase_start` (before the phase's collection
    dispatches) and :meth:`on_phase_end` (after the phase's updates are
    consumed). Idempotent and crash-safe: :meth:`close` from a
    ``finally`` stops a still-open trace so an exception mid-phase
    cannot leak a running profiler into the next run."""

    def __init__(self, profile_dir: Optional[str], target_phase: Optional[int]):
        self.profile_dir = profile_dir or "profiles"
        # a directory alone asks for phase 0; neither asks for nothing
        self.target = (
            0 if target_phase is None and profile_dir else target_phase
        )
        self.active = False
        self.done = False

    @property
    def enabled(self) -> bool:
        return self.target is not None

    def on_phase_start(self, phase_index: int) -> None:
        if not self.enabled or self.active or self.done:
            return
        if phase_index != self.target:
            return
        import jax

        jax.profiler.start_trace(self.profile_dir)
        self.active = True

    def on_phase_end(self, sync: Any = None) -> None:
        """Close the window if one is open. ``sync`` (e.g. the train
        state's params) is blocked on first so in-flight device work of
        the profiled phase lands inside the trace — this boundary is
        already a sync point in every caller."""
        if not self.active:
            return
        import jax

        if sync is not None:
            jax.block_until_ready(sync)
        jax.profiler.stop_trace()
        self.active = False
        self.done = True  # exactly one window per run

    def close(self) -> None:
        if not self.active:
            return
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        self.active = False
