"""torchft_tpu — TPU-native per-step fault tolerance for replicated JAX training.

A ground-up rebuild of the capabilities of torchft (zhengchenyu/torchft) for
TPU: a C++ coordination core (Lighthouse quorum server + per-replica-group
Manager), a reconfigurable dynamic-membership collective layer over DCN,
live peer-to-peer checkpoint healing of pytree state, and training-loop
adapters (FT-DDP, LocalSGD, DiLoCo) — designed JAX-first: inner parallelism
(FSDP/TP/SP within a slice) is pjit sharding over ICI and stays static; the
elastic replica dimension lives above jit so membership changes never re-jit.

Public API surface mirrors reference torchft/__init__.py:7-34: the Manager,
the Optimizer wrapper, FT-DDP, the elastic data sampler, and the concrete
ProcessGroup backends are importable from the package root.
"""

from torchft_tpu.data import DistributedSampler, StatefulDistributedSampler
from torchft_tpu.ddp import DistributedDataParallel, PureDistributedDataParallel
from torchft_tpu.local_sgd import DiLoCo, LocalSGD
from torchft_tpu.manager import Manager, WorldSizeMode
from torchft_tpu.optim import OptimizerWrapper
from torchft_tpu.parallel.process_group import (
    ErrorSwallowingProcessGroupWrapper,
    ManagedProcessGroup,
    NotParticipatingError,
    ProcessGroup,
    ProcessGroupDummy,
    ProcessGroupTCP,
)

# Reference name: torchft.Optimizer (torchft/optim.py re-exported at root).
Optimizer = OptimizerWrapper

# Telemetry from env, at import (reference wires its OTEL pipeline at
# import, torchft/__init__.py:20-22 + otel.py:42-86): OTLP logs + metrics
# + traces gated on TORCHFT_USE_OTEL; the Prometheus scrape server gated
# on TORCHFT_METRICS_PORT.
from torchft_tpu.utils.metrics import (
    maybe_export_from_env as _metrics_export_install,
    maybe_serve_from_env as _metrics_serve_install,
)
from torchft_tpu.utils.otel import maybe_install_from_env as _otel_install
from torchft_tpu.utils.tracing import maybe_install_from_env as _traces_install

_otel_install()
_metrics_export_install()
_traces_install()
_metrics_serve_install()
del _otel_install, _metrics_export_install, _traces_install, _metrics_serve_install

__all__ = [
    "DiLoCo",
    "DistributedDataParallel",
    "DistributedSampler",
    "ErrorSwallowingProcessGroupWrapper",
    "LocalSGD",
    "ManagedProcessGroup",
    "Manager",
    "NotParticipatingError",
    "Optimizer",
    "OptimizerWrapper",
    "ProcessGroup",
    "ProcessGroupDummy",
    "ProcessGroupTCP",
    "PureDistributedDataParallel",
    "StatefulDistributedSampler",
    "WorldSizeMode",
]

__version__ = "0.1.0"
