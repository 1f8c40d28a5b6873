from bcfl_tpu.metrics.metrics import (  # noqa: F401
    ResourceMonitor,
    RoundRecord,
    RunMetrics,
    model_size_gb,
)
from bcfl_tpu.metrics.tracing import StepClock, scope, trace  # noqa: F401
