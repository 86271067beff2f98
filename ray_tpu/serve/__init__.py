"""ray_tpu.serve: model serving on actors (reference: Ray Serve).

Deployments default to the colocated posture (every replica prefills
and decodes). Pass ``role="prefill"`` / ``role="decode"`` to
``serve.deployment`` to split the two phases onto separate fleets with
KV pages handed off over the object plane — see docs/SERVING.md
"Disaggregated prefill/decode"."""

from ray_tpu.serve.api import (  # noqa: F401
    delete,
    get_deployment_handle,
    http_addresses,
    proxy_status,
    run,
    shutdown,
    start_http,
    status,
    stop_http,
    timelines,
)
from ray_tpu.serve.metrics import slo_summary  # noqa: F401
from ray_tpu.serve.batching import batch  # noqa: F401
from ray_tpu.serve.decode import (  # noqa: F401
    DecodeEngine,
    LlamaDecodeDeployment,
)
from ray_tpu.serve.build import deploy_config  # noqa: F401
from ray_tpu.serve.deployment import (  # noqa: F401
    AutoscalingConfig,
    Deployment,
    DeploymentHandle,
    deployment,
)
from ray_tpu.serve.replica import (  # noqa: F401
    get_multiplexed_model_id,
    multiplexed,
    request_deadline_s,
)
from ray_tpu.core.errors import (  # noqa: F401 — request-lifecycle outcomes
    DeadlineExceededError,
    OverloadedError,
    RequestCancelledError,
)
