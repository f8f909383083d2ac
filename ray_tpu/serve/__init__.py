"""ray_tpu.serve — model serving (reference: python/ray/serve).

Deployments are replicated actors; handles route with power-of-two-
choices; @serve.batch keeps TPU batches full; a stdlib HTTP proxy
provides ingress.
"""

from ray_tpu.serve import slo
from ray_tpu.serve.batching import batch
from ray_tpu.serve.grpc_proxy import (
    grpc_proxy_stats,
    start_grpc_proxy,
    stop_grpc_proxy,
)
from ray_tpu.serve.multiplex import get_multiplexed_model_id, multiplexed
from ray_tpu.serve.controller import (
    delete,
    get_app_handle,
    profile_start,
    profile_stop,
    run,
    shutdown,
    status,
)
from ray_tpu.serve.deployment import (
    Application,
    Deployment,
    DeploymentHandle,
    DeploymentResponse,
    deployment,
)
from ray_tpu.serve.http_proxy import (
    http_proxy_stats,
    start_http_proxy,
    stop_http_proxy,
)
from ray_tpu.serve.slo import (
    DeadlineExceededError,
    OverloadedError,
    ReplicasUnavailableError,
    request_deadline,
)

__all__ = [
    "Application",
    "DeadlineExceededError",
    "Deployment",
    "DeploymentHandle",
    "DeploymentResponse",
    "OverloadedError",
    "ReplicasUnavailableError",
    "batch",
    "delete",
    "deployment",
    "get_app_handle",
    "get_multiplexed_model_id",
    "grpc_proxy_stats",
    "http_proxy_stats",
    "multiplexed",
    "profile_start",
    "profile_stop",
    "request_deadline",
    "run",
    "shutdown",
    "slo",
    "start_grpc_proxy",
    "start_http_proxy",
    "status",
    "stop_grpc_proxy",
    "stop_http_proxy",
]
