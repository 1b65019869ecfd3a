"""Service tier: scheduler, request model, response handling, tracing."""

from xllm_service_tpu.service.ordered_streams import HopThreads, Strand
from xllm_service_tpu.service.request import (
    RequestTracer,
    ServiceRequest,
    make_service_request_id,
)
from xllm_service_tpu.service.response_handler import ClientStream, ResponseHandler
from xllm_service_tpu.service.scheduler import Scheduler

__all__ = [
    "HopThreads",
    "Strand",
    "RequestTracer",
    "ServiceRequest",
    "make_service_request_id",
    "ClientStream",
    "ResponseHandler",
    "Scheduler",
]
