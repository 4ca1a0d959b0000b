"""Device mesh / topology module — the distributed-communication backend.

SURVEY.md §2.10: the reference has *no* distributed backend (all communication
is HTTPS to SaaS APIs); the TPU-native equivalent is XLA collectives over ICI
expressed through ``jax.sharding.Mesh`` + ``NamedSharding``. This module is
the single place device topology is defined:

- ``data`` axis — batches independent sequences / eval cases (DP).
- ``pipe`` axis — pipeline stages: the scan-stacked layer dimension is
  partitioned across this axis and activations flow stage-to-stage via
  ``ppermute`` (``parallel/pipeline.py``).
- ``seq`` axis — shards the sequence dimension for long-context ring
  attention (``parallel/ring_attention.py``); K/V shards rotate around this
  axis's ICI ring via ``ppermute``.
- ``model`` axis — shards attention heads, MLP, vocab (Megatron TP); psum /
  all-gather reductions ride ICI inside compiled programs.

Multi-host (DCN) scale-out uses the same axis names over
``jax.distributed``-initialized global device lists.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
PIPE_AXIS = "pipe"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"

AXIS_ORDER = (DATA_AXIS, PIPE_AXIS, SEQ_AXIS, MODEL_AXIS)


def build_mesh(
    data: int = 1,
    model: int = 1,
    seq: int = 1,
    pipe: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (data, pipe, seq, model) mesh over the first N needed devices.

    On TPU, a mesh over the whole device set comes from
    ``mesh_utils.create_device_mesh``, which picks an ICI-friendly physical
    layout (the ``seq``/``pipe`` axes land on rings so ppermute hops are
    nearest-neighbor) — and whose refusal of a shape is an error, not a
    reason to serve collectives over an arbitrary device order. Subsets
    (a fleet replica's slice) and the CPU's virtual devices, which have no
    topology, take the devices in order.
    """
    devices = list(devices if devices is not None else jax.devices())
    shape = (data, pipe, seq, model)
    need = data * pipe * seq * model
    if need > len(devices):
        raise ValueError(
            f"mesh {'x'.join(map(str, shape))} needs {need} devices, have {len(devices)}")
    if need == len(devices) and devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        arr = np.asarray(devices[:need]).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def single_device_mesh() -> Mesh:
    return build_mesh(1, 1)


def replica_device_slices(
    dp: int,
    per_replica: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> list[Optional[list[jax.Device]]]:
    """Disjoint device slices along the dp axis for an engine fleet.

    Each of the ``dp`` replicas owns ``per_replica`` consecutive devices
    (the replica-internal axes — model/seq — stay within a slice, so their
    high-frequency collectives ride ICI while replicas never communicate
    inside compiled programs at all). When the host has fewer devices than
    the fleet needs, every entry is ``None``: replicas share the default
    device — the CPU tier-1 virtual-fleet case when the platform exposes a
    single device.
    """
    devices = list(devices if devices is not None else jax.devices())
    if dp < 1 or per_replica < 1:
        raise ValueError("dp and per_replica must be >= 1")
    if len(devices) < dp * per_replica:
        return [None] * dp
    return [devices[i * per_replica:(i + 1) * per_replica]
            for i in range(dp)]


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
