"""Unified observability layer: span tracing, metrics, calibration.

Three pieces, all host-side and dependency-free (no JAX imports at
module level), cheap enough to leave on in production smokes:

``repro.obs.trace``
    Structured span tracer.  ``with trace.span("gen.round", wave=i):``
    records a nestable, exception-safe span on the current thread;
    spans export as Chrome-trace/Perfetto JSON (``export_chrome``) or a
    human summary table (``report``).  Enabled spans are also
    ``jax.profiler.TraceAnnotation``s under their names, so a JAX
    profiler trace (``.xplane.pb``) holds them as host events beside the
    device's operations.  Disabled spans cost one attribute read.
    ``REPRO_TRACE=<path>`` enables tracing at import and dumps the trace
    at interpreter exit.

``repro.obs.metrics``
    Process-wide registry of counters / gauges / histograms replacing
    the ad-hoc stats dicts: ``metrics.counter("gen.tokens").inc(n)``,
    ``metrics.histogram("gen.ttft_s").observe(dt)``.  ``snapshot()``
    returns a JSON-able dict (histograms fold to count/sum/mean/
    p50/p95/p99/max).  ``REPRO_METRICS=<path>`` dumps a snapshot at
    interpreter exit.

``repro.obs.calibrate``
    Fits per-device-class ``CostModel`` scale factors from a measured
    ``Event`` timeline (HetRL §4.1 profiling, closing the wall-clock
    gap the ROADMAP flags), plus a ``DivergenceMonitor`` that turns
    sustained measured/predicted drift per task into the reactive
    elasticity signal.

Span naming scheme (dotted, category = first segment):

    engine.iteration       one Engine.run_iteration call, all of it
    engine.stage           one workflow stage dispatch
    task.<name>            one task executor (task.generation, ...)
    engine.sync            post-train weight sync to the GEN replica
    engine.swap            Engine.apply_plan plan transition
    rl.advantages          KL-penalised rewards, advantages and their
                           host reads, once per trained iteration
    gen.round              one genserve host round (admit+decode)
    gen.admit              one-shot admission program
    gen.install            chunked-admission install (+ prefix match)
    gen.mixed              mixed wave-step scan (decode + prefill)
    gen.decode             pure decode chunk scan
    elastic.poll           ElasticController drift reaction
    elastic.reschedule     scheduler warm-start search
    elastic.checkpoint     pre-swap checkpoint write
    train.step             one step of the LM launcher's training loop

Metric naming scheme (dotted namespaces):

    gen.*       tokens, ttft_s, queue_wait_s, queue_depth,
                wave_occupancy, prefix_hits / prefix_tokens,
                sjf_skips, sjf_aged_admissions
    pagepool.*  utilization, leaked_pages
    engine.*    iter_wall_s, sync_s, plan_epoch, staleness, swaps
    elastic.*   reschedule_s, checkpoint_bytes, drift_events
    calib.*     scale.<device-class>, sync_scale, local_tflops,
                local_hbm_gbps

``REPRO_OBS_STRICT=1`` upgrades observability warnings (page-pool
refcount leaks at decoder teardown) to hard errors.
"""
from __future__ import annotations

__all__ = ["metrics", "trace", "calibrate"]

# submodules import on first attribute access, keeping ``python -m
# repro.obs.trace`` free of the runpy double-import warning
_SUBMODULES = ("metrics", "trace", "calibrate")


def __getattr__(name: str):
    if name in _SUBMODULES:
        import importlib
        return importlib.import_module(f"repro.obs.{name}")
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
