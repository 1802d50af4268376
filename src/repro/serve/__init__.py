from ..faults import FaultPlan, InjectedFailure
from .engine import (ArrivalTrace, CircuitBreaker, ProxyRequest,
                     ServeReport, ServingEngine, burst_trace, poisson_trace,
                     serve)
from .serve_step import generate, make_decode_step, make_prefill_step
