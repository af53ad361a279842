"""Multi-host (multi-process) wiring for one replica group.

A real TPU slice beyond v5e-8 spans several hosts (a v5e-16 is 4 hosts);
one replica *group* is then N processes forming ONE jax multi-controller
runtime: ``jax.distributed.initialize`` builds the global device mesh,
XLA's SPMD partitioner runs the inner parallelism (dp/fsdp/tp/...) over
ICI with every process feeding its addressable shards, and the
fault-tolerance layer sits above it — one ``Manager`` per process with
``group_rank = process index``, sharing the group's store for the
manager-address handoff (the reference does the same with TCPStore:
torchft/manager.py:277-325; multi-process worker wiring:
torchft/fsdp_test.py:96-120).

Division of labor (this framework's core design):
- intra-group, inter-host: XLA collectives over ICI/DCN via the jit mesh —
  static, compiled, membership never changes mid-job;
- inter-group: the elastic ``ProcessGroupTCP`` ring driven by the Manager —
  reconfigured per quorum, groups join/leave freely.

Testable without TPUs: the CPU backend supports multi-process meshes (Gloo
collectives); see examples/train_multihost.py and
tests/test_multihost_integ.py.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np


def initialize_multihost(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    platform: "Optional[str]" = None,
    cpu_devices_per_process: "Optional[int]" = None,
) -> None:
    """Join this process to the replica group's jax runtime.

    Must run before any other jax device use.  ``platform``/
    ``cpu_devices_per_process`` force the CPU backend with N virtual
    devices — the no-TPU test and demo configuration; leave both ``None``
    to run on the host's own accelerator.
    """
    import jax

    if platform is not None:
        jax.config.update("jax_platforms", platform)
    if cpu_devices_per_process is not None:
        jax.config.update("jax_num_cpu_devices", cpu_devices_per_process)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def host_sharded_array(
    global_shape: "tuple",
    sharding: Any,
    fill: "Callable[[Any], np.ndarray]",
) -> Any:
    """Build a global array from per-process local shards.

    ``fill(index)`` returns the numpy data for one addressable shard
    (``index`` is the global-slice tuple for that shard).  Thin veneer
    over ``jax.make_array_from_callback`` — named here so trainers read
    as 'each host contributes its slice of the global batch'.
    """
    import jax

    return jax.make_array_from_callback(global_shape, sharding, fill)
