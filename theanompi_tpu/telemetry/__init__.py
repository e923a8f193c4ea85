"""Unified telemetry layer (ISSUE 1): structured spans, collective byte
accounting, live training metrics.

Two fragments existed before this package — the Recorder's host splits
and the bounded ``jax.profiler`` window — neither of which emitted
structured events.  This package is the common substrate:

- :mod:`~theanompi_tpu.telemetry.spans` — the process's always-on span
  ring (ISSUE 25): every hot loop opens its spans there, once; a record
  has an id, a parent, ``perf_counter`` stamps and tags, and is a
  ``jax.profiler.TraceAnnotation`` as well, so a profiler trace carries
  the program's spans on the device's clock.  ``jit.build`` instants say
  which span built a program;
- :class:`~theanompi_tpu.telemetry.core.Telemetry` — per-rank JSONL event
  sink (spans / counters / gauges, monotonic timestamps, rank+host tags,
  bounded rotation) with a metrics registry flushed at ``print_freq``;
  it subscribes to the ring and writes each closed span;
- :mod:`~theanompi_tpu.telemetry.chrome_trace` — export to the Chrome
  trace-event format so host-side spans render in Perfetto alongside the
  ``profile_dir`` device traces;
- :mod:`~theanompi_tpu.telemetry.aggregate` — rank-0 merge + cross-rank
  step-skew / straggler summary for the multihost path;
- :mod:`~theanompi_tpu.telemetry.health` — streaming health detectors
  (hang, straggler skew, loss spike/NaN, throughput regression,
  checkpoint stall, serving SLO) publishing typed verdicts to
  ``HEALTH.json`` (ISSUE 13);
- :mod:`~theanompi_tpu.telemetry.flight_recorder` — bounded in-memory
  event ring dumped as ``blackbox.json`` on crash/SIGTERM;
- :mod:`~theanompi_tpu.telemetry.profile` — streaming step-time
  attribution (data/compute/comm/validate/checkpoint/host for training,
  queue-wait/prefill/decode/rollout-swap for serving) publishing
  ``attr.*`` gauges, per-device HBM watermarks, and ``ATTRIB.json``
  (ISSUE 16);
- :mod:`~theanompi_tpu.telemetry.ledger` — the append-only
  ``TMPROF_LEDGER.jsonl`` cross-run perf trajectory with typed regression
  verdicts (ISSUE 16);
- :mod:`~theanompi_tpu.telemetry.cli` / ``.prof`` — the ``tmhealth`` and
  ``tmprof`` CLIs (``python -m theanompi_tpu.telemetry``).

The sink is off by default: the trainer holds ``telemetry=None`` unless a
directory was configured (``telemetry_dir`` rule config /
``--telemetry-dir`` launcher flag), and a disabled run constructs no
``Telemetry`` and writes nothing: no sink, no file, no thread, no
profiler.  The ring itself always records, as the ``Recorder``'s
histories and the scheduler's latency lists always have.
"""

from theanompi_tpu.telemetry.core import Telemetry
from theanompi_tpu.telemetry.flight_recorder import (
    FlightRecorder,
    read_blackbox,
)
from theanompi_tpu.telemetry.health import (
    HealthConfig,
    HealthMonitor,
    hung_verdict,
    read_health,
    replay_events,
)
from theanompi_tpu.telemetry.ledger import (
    PerfLedger,
    check_ledger,
    read_ledger,
)
from theanompi_tpu.telemetry.metrics import (
    MetricsRegistry,
    device_memory_stats,
    mfu,
    peak_flops,
    per_device_memory_stats,
    step_flops_estimate,
)
from theanompi_tpu.telemetry.profile import (
    StepAttributor,
    attribute_events,
    parse_profile_window,
    read_attrib,
)
from theanompi_tpu.telemetry.sink import (
    EventSink,
    read_events,
    sink_files,
    tail_events,
)
from theanompi_tpu.telemetry.spans import Span

__all__ = [
    "EventSink",
    "FlightRecorder",
    "HealthConfig",
    "HealthMonitor",
    "MetricsRegistry",
    "PerfLedger",
    "Span",
    "StepAttributor",
    "Telemetry",
    "attribute_events",
    "check_ledger",
    "device_memory_stats",
    "hung_verdict",
    "mfu",
    "parse_profile_window",
    "peak_flops",
    "per_device_memory_stats",
    "read_attrib",
    "read_blackbox",
    "read_events",
    "read_health",
    "read_ledger",
    "replay_events",
    "sink_files",
    "step_flops_estimate",
    "tail_events",
]
