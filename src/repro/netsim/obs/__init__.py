"""Observability layer for the netsim engine (docs/observability.md).

Three pillars, all opt-in and bit-identical to the pre-obs engine when
off:

  * **event rings** (`events`): a bounded per-scenario ring of discrete,
    timestamped events carried through the scan under
    ``trace_mode="window"`` + ``NetConfig.event_ring_slots > 0``
  * **timeline export** (`timeline`): window/event/trace data -> Chrome
    trace-event JSON for Perfetto UI / ``chrome://tracing``
  * **launch profiling + manifests** (`profile`): AOT compile/execute
    wall-clock split, XLA memory figures, and JSONL run manifests
    summarized by ``tools/obs_report.py``
"""
from .events import (EVENT_KINDS, EventRing, decode_events,
                     engine_event_candidates, event_count, init_event_ring,
                     kind_name, push_events, unroll_window)
from .profile import (MANIFEST_VERSION, git_rev, memory_figures,
                      profiled_traced_batch, read_manifest, write_manifest)
from .timeline import (export_timeline, timeline_cell,
                       timeline_from_traces, timeline_from_window)

__all__ = [
    "EVENT_KINDS", "EventRing", "decode_events", "engine_event_candidates",
    "event_count", "init_event_ring", "kind_name", "push_events",
    "unroll_window",
    "MANIFEST_VERSION", "git_rev", "memory_figures",
    "profiled_traced_batch", "read_manifest", "write_manifest",
    "export_timeline", "timeline_cell", "timeline_from_traces",
    "timeline_from_window",
]
