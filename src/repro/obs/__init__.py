"""Chip telemetry: jit-safe counters, phase timing, and run reports.

The source paper's contribution is *verification methodology* — automated
monitoring of the circuits under simulation and emulation (§3). This
package is that methodology applied to the machine model itself: every
silent runtime decision of the emulation stack (sparse-vs-dense gate,
event-stream overflow fallback, VM saturation, specializer cache churn)
becomes an observable counter, every phase a measurable span, and every
run a structured report.

Three layers:

``repro.obs.trace``
    The layer scopes (``LAYER_SCOPES``, ``scope``) that name each layer's
    ops in the compiled program, and a jit-safe ``Telemetry`` pytree of
    counters carried through the training scan. ``None`` means OFF and compiles to *nothing*: every
    update helper is the identity on ``None``, so the telemetry-off
    program graph is byte-identical to the pre-telemetry one, and
    telemetry on/off is bit-identical in spikes/weights (the counters
    only read the existing dataflow).

``repro.obs.timing``
    Host-side timing: ``block_until_ready``-bracketed spans
    (``PhaseTimer``), the ``jax.profiler`` trace hook, and
    specializer-cache snapshots with eviction-storm detection.

``repro.obs.report``
    Structured run reports (JSON + markdown) merging counters, timings,
    cache stats, config, and git SHA.
"""
from repro.obs.trace import Telemetry, init_telemetry, summary  # noqa: F401
