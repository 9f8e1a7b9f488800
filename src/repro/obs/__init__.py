"""repro.obs — the federation telemetry subsystem (ISSUE 7).

Structured per-round observability for every training path in the repo:

  schema     typed RoundRecord events, NaN-safe JSONL round-trip, the
             shared row->record construction path, histogram geometry
  sinks      pluggable record consumers: JSONL file, in-memory ring
             buffer, null, tee
  profiling  the device program's ``fed.*`` stage scopes (predict /
             select / gather / local SGD / upload transform / aggregate),
             their map from compiled HLO, the scan loop's ``fed.host.*``
             host spans, and trace capture
  report     markdown straggler/health report renderer
             (CLI: scripts/fl_report.py)

The server (repro.core.server) emits every executed round through a sink;
on the scan driver the underlying metrics ride the block's single existing
stats pull (host_syncs_per_round is unchanged by telemetry), and with
telemetry off the round programs are bitwise identical to untelemetered
ones (tests/test_telemetry.py).
"""
from repro.obs.schema import (HISTORY_KEYS, LOSS_HIST_BINS, LOSS_HIST_MAX,
                              WORKLOAD_HIST_BINS, RoundRecord, SchemaError,
                              histogram_counts, read_jsonl,
                              record_from_row, records_from_block_stats)
from repro.obs.sinks import (JsonlSink, NullSink, RingBufferSink, Sink,
                             TeeSink)
from repro.obs.profiling import (HOST_PHASES, STAGE_AGGREGATE,
                                 STAGE_GATHER, STAGE_LOCAL_SGD,
                                 STAGE_PREDICT, STAGE_SELECT, STAGE_UPLOAD,
                                 host_span, stage, stage_map, trace_if)
from repro.obs.report import client_reliability, render_report

__all__ = [
    "HISTORY_KEYS", "LOSS_HIST_BINS", "LOSS_HIST_MAX", "WORKLOAD_HIST_BINS",
    "RoundRecord", "SchemaError", "histogram_counts", "read_jsonl",
    "record_from_row", "records_from_block_stats",
    "JsonlSink", "NullSink", "RingBufferSink", "Sink", "TeeSink",
    "HOST_PHASES", "STAGE_AGGREGATE", "STAGE_GATHER", "STAGE_LOCAL_SGD",
    "STAGE_PREDICT", "STAGE_SELECT", "STAGE_UPLOAD",
    "host_span", "stage", "stage_map", "trace_if",
    "client_reliability", "render_report",
]
