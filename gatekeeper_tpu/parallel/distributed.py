"""Multi-host (DCN) wiring for the evaluation plane.

Reference: the upstream scales audit/webhook horizontally with sharded pods
(--operation + status.byPod aggregation); the TPU-native equivalent is a
multi-controller JAX runtime — one process per host, a GLOBAL device mesh,
and XLA collectives riding ICI within a slice and DCN across hosts.

``init_distributed`` boots the JAX distributed runtime (coordinator
rendezvous; Gloo collectives back the CPU path used by tests, real TPU
slices use their native interconnect).  After it returns, ``jax.devices()``
is global and ``make_mesh()`` / ``ShardedEvaluator`` span hosts unchanged:
object batches shard over the global 'data' axis, each host feeding the
same flattened batch and XLA keeping every collective on the fastest link.

Validated by tests/test_multihost.py: two processes x 4 virtual devices
each form one 8-device mesh and produce identical sweep verdicts.
"""

from __future__ import annotations

import os
from typing import Optional


def init_distributed(coordinator_address: str, num_processes: int,
                     process_id: int,
                     local_device_count: Optional[int] = None) -> None:
    """Join the multi-process JAX runtime.  Must run before any JAX
    computation; with ``local_device_count`` the CPU backend is given
    that many virtual devices (the test path, run under
    ``JAX_PLATFORMS=cpu`` — real TPU hosts discover their chips)."""
    if local_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        want = f"--xla_force_host_platform_device_count={local_device_count}"
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + want).strip()
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def process_info() -> tuple:
    """(process_id, num_processes, local_devices, global_devices)."""
    import jax

    return (jax.process_index(), jax.process_count(),
            len(jax.local_devices()), len(jax.devices()))
