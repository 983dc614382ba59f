"""Device executor: process-wide continuous cross-job batching.

See service.py for the design.  Importing this package does NOT import
jax — control-plane processes can hold an ExecutorConfig (and the
overload / circuit-breaker error types for retry classification) without
pulling in the device stack.
"""

from .accumulator import (
    AccumulatorConfig,
    AccumulatorError,
    AccumulatorUnavailable,
    DeviceAccumulatorStore,
    ResidentRef,
    StaleAccumulatorDelta,
)
from .service import (
    KIND_COMBINE,
    KIND_POPLAR_INIT,
    KIND_PREP_INIT,
    CircuitBreaker,
    CircuitOpenError,
    DeviceExecutor,
    ExecutorConfig,
    ExecutorOverloadedError,
    bucket_label,
    get_global_executor,
    narrow_arrival,
    peek_global_executor,
    reset_global_executor,
    shape_label,
    withdraw_arrival,
)

__all__ = [
    "AccumulatorConfig",
    "AccumulatorError",
    "AccumulatorUnavailable",
    "CircuitBreaker",
    "CircuitOpenError",
    "DeviceAccumulatorStore",
    "DeviceExecutor",
    "ExecutorConfig",
    "ExecutorOverloadedError",
    "KIND_COMBINE",
    "KIND_POPLAR_INIT",
    "KIND_PREP_INIT",
    "ResidentRef",
    "StaleAccumulatorDelta",
    "bucket_label",
    "get_global_executor",
    "narrow_arrival",
    "peek_global_executor",
    "reset_global_executor",
    "shape_label",
    "withdraw_arrival",
]
