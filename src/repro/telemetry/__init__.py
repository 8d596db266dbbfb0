"""Telemetry subsystem — the sensor layer of the probe-driven control plane.

One training run produces one coherent, schema-versioned ``events.jsonl``:
scalar metrics (loss, grad norms, per-family captured energy / projector
drift / bias residual / rank), discrete events (health, recovery, fault
injection, rank-policy decisions, checkpoint save/verify/GC, audit
summaries), host-side timing spans (steady step vs refresh boundary vs rank
migration vs checkpoint save) and closing counters.

Three layers:

  * :mod:`repro.telemetry.bus` — the structured record bus: typed records
    with pluggable sinks (stdout pretty-printer, append-only JSONL with a
    versioned schema, in-memory ring for tests).  Every former ad-hoc
    ``print()`` emitter in the trainer routes through it, so console output
    and ``events.jsonl`` can never disagree.
  * :mod:`repro.telemetry.instrument` — host-side gatherers over the live
    optimizer state: per-family probe metrics (captured-energy fraction,
    projector drift, sampled bias residual — stored in-jit by
    ``lowrank(telemetry=True)``), layerwise-unbias gamma-slot sampling
    distribution, and the runtime launch-count cross-check against the
    closed-form model of :mod:`repro.analysis.launch_model`.
  * :mod:`repro.telemetry.report` — the run-report/diff CLI:
    ``python -m repro.telemetry.report RUN_DIR [--diff OTHER]``.

The in-jit half lives in ``repro.core.combinators.lowrank(telemetry=True)``
(riding the spectrum-probe mechanism — zero extra state leaves when off,
loss-trajectory bit-exact when on).  What tracing costs on the chip is
kept in ``PERF.md`` beside the benchmark (``chipbench/``); the in-jit
probes' own step time is not measured there yet.

The bus's spans are also ``jax.profiler`` annotations, so a profile of a
run (``--profile-steps A:B``) puts the trainer's host phases (``step``,
``feed``, ``dispatch``, ``block``, ``loss``, ``ckpt_save``) on the clock of
the device ops; the compiled step names its own stages with
``jax.named_scope`` (``launch.steps.make_train_step``).
"""
from .bus import (
    SCHEMA_VERSION,
    JsonlSink,
    MemorySink,
    StdoutSink,
    Telemetry,
    TelemetryConfig,
)
from .instrument import (
    GammaSlotTracker,
    lowrank_family_metrics,
)

__all__ = [
    "SCHEMA_VERSION",
    "Telemetry",
    "TelemetryConfig",
    "JsonlSink",
    "StdoutSink",
    "MemorySink",
    "GammaSlotTracker",
    "lowrank_family_metrics",
]
