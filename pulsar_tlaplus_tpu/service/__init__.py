"""Checking-as-a-service — the resident multi-tenant checker daemon.

The one-shot CLI pays the full compile warmup per verdict; a CI fleet
submitting Pulsar spec revisions cannot.  This package composes the
ingredients the repo already has — capacity-tier prewarm over JAX's
persistent compilation cache, checkpoint
frames + preemption-safe shutdown, JSONL telemetry with run_ids — into
a long-lived service:

- :mod:`jobs` — the job model: one queued check (spec + .cfg constant
  bindings + state/time budget) with its own directory, checkpoint
  frame, telemetry stream, and result record.
- :mod:`protocol` — the local-socket JSONL wire protocol
  (submit/status/result/cancel/watch/ping/shutdown).
- :mod:`scheduler` — the warmed-checker pool and the FIFO +
  budget-slice scheduler that time-slices the single device between
  jobs by suspending a running job at a checkpoint-frame boundary
  (the engine's cooperative ``suspend_hook``) and resuming the next.
- :mod:`server` — the daemon (``cli.py serve``): socket accept loop,
  graceful SIGTERM shutdown (frame every active job, persist the
  queue), ``serve --recover`` resume.
- :mod:`client` — the thin client (``cli.py submit/status/watch``).

State layout under ``state_dir``::

    serve.sock            the listening unix socket
    service.jsonl         daemon telemetry stream (job_* events, v4)
    queue.json            persisted queue (atomic; survives restarts)
    jobs/<job_id>/
        frame.npz         the job's checkpoint frames (per-job isolation)
        events.jsonl      the job's engine telemetry (one run_id/slice)
        result.json       the final result record

See docs/service.md for the protocol and the scheduler state machine.
"""
