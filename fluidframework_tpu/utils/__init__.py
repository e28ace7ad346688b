"""Base utils (SURVEY §1.1): telemetry logger, spans, metrics,
wire-trace consumption, kernel-contract registry.
"""

from .contracts import (  # noqa: F401
    KernelContract,
    kernel_contract,
    register_kernel_contract,
    registered_contracts,
)
from .telemetry import (  # noqa: F401
    HOP_ACK,
    HOP_ADMIT,
    HOP_DELI,
    HOP_EXECUTE,
    HOP_FANOUT,
    HOP_ORDER,
    HOP_PIPELINE,
    HOP_RELAY,
    HOP_SHED,
    HOP_STAGE,
    HOP_SUBMIT,
    HOPS,
    BufferSink,
    Counters,
    TelemetryLogger,
    TraceAggregator,
    count_unknown_hops,
    hop_pair_name,
    percentile,
)
