"""Unified telemetry — structured run events, zero-sync device
counters, TLC-style progress heartbeats, and per-stage reports.

Two halves:

- :mod:`pulsar_tlaplus_tpu.obs.telemetry` — the emission side every
  engine (and the fpset) writes into: a versioned JSONL event stream,
  the progress heartbeat thread, and the RTT probe.
- :mod:`pulsar_tlaplus_tpu.obs.report` — the aggregation side:
  turns a stream back into the BASELINE.md per-stage table and the
  BENCH_* artifact keys, RTT-corrected.

Round 12 adds the flight deck on top of both:

- :mod:`pulsar_tlaplus_tpu.obs.trace` — streams -> Perfetto trace
  JSON (levels, ckpt stalls, daemon job slices + context switches);
- :mod:`pulsar_tlaplus_tpu.obs.metrics` — Prometheus text exposition
  from a live scheduler (the service ``metrics`` verb) or a stream
  tail, identically named either way;
- :mod:`pulsar_tlaplus_tpu.obs.top` — the ``cli.py top`` dashboard
  renderer (job table, rate sparklines, status line).
"""
