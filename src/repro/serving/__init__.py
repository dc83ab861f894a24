"""repro.serving — FaaS-for-models gateway and the LLM-as-FaaS workload."""
from .request import (RequestSpec, kv_bytes, preemption_penalty_ms,
                      service_ms)
from .gateway import (GatewayResult, SlotCFS, SlotHybridScheduler,
                      requests_from_trace, run_gateway, run_gateway_fleet)
from .llm import (LLMSpec, approx_param_bytes, llm_requests, llm_workload,
                  request_chunks)

__all__ = [
    "RequestSpec", "kv_bytes", "preemption_penalty_ms", "service_ms",
    "GatewayResult", "SlotCFS", "SlotHybridScheduler",
    "requests_from_trace", "run_gateway", "run_gateway_fleet",
    "LLMSpec", "approx_param_bytes", "llm_requests", "llm_workload",
    "request_chunks",
]
