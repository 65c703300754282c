"""dstpu-telemetry: unified runtime telemetry.

One subsystem replacing four disconnected fragments (utils/timer,
utils/comms_logging, profiling/flops_profiler, monitor) with a coherent
observability layer: span/trace recording (trace.py), derived metrics —
step percentiles, tokens/sec, MFU, goodput, overlap efficiency
(metrics.py), memory watermarks + compiled-HLO analysis (memory.py), and
a stall watchdog (watchdog.py), behind the facade in telemetry.py.

Hard contract: **a TraceMe per span when off, nothing else** — the disabled
path is :data:`NULL_TELEMETRY` (nothing recorded; a span is only a
``jax.profiler.TraceAnnotation``) and nothing is ever injected into traced
code (no host callbacks, no syncs in span hooks); enforced by the
``telemetry-hot-path-sync`` lint rule and the ``telemetry-off-parity``
Layer-B audit. See docs/OBSERVABILITY.md.
"""

from .config import TelemetryConfig, telemetry_enabled  # noqa: F401
from .telemetry import (NULL_TELEMETRY, JsonlMetricsSink, NullTelemetry,  # noqa: F401
                        Telemetry, build_telemetry, get_telemetry,
                        maybe_enable_from_env, reset_telemetry, set_telemetry)
from .trace import (PHASE_BWD, PHASE_CHECKPOINT, PHASE_DATA,  # noqa: F401
                    PHASE_FWD, PHASE_GATHER, PHASE_OPTIMIZER, PHASE_OTHER,
                    PHASE_SCATTER, PHASE_SERVING, PHASE_SETUP, PHASE_STEP,
                    TraceRecorder)
from . import setup_spans  # noqa: F401,E402  (registers JAX's listeners, once)
