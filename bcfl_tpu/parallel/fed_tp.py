"""Federated rounds over a 2-D ``clients x tp`` mesh.

The 1-D programs in :mod:`bcfl_tpu.fed.client_step` give every client one
device (or a stacked share of one). For models too large for a single chip —
the BASELINE.json Llama LoRA config — each client instead spans ``tp`` chips:

- the frozen base params carry megatron tensor-parallel shardings
  (:func:`bcfl_tpu.models.tp_param_specs`) over the ``tp`` axis and are
  shared by every client (replicated over ``clients``),
- the per-client LoRA adapter stacks carry a leading client dim sharded over
  ``clients`` (adapters are small; they stay replicated over ``tp``),
- batches are sharded over ``clients`` like the 1-D path.

Under GSPMD this composition needs NO separate round implementation: the 1-D
program bodies run unchanged on the 2-D mesh, and XLA inserts the tp
collectives inside each client's forward/backward plus the cross-client
all-reduce from the sharding annotations alone. So this module is a thin
veneer over :func:`bcfl_tpu.fed.client_step.build_programs` — which means the
clients x tp path has FULL parity with the 1-D programs (masked weighted
mean, gossip, split-phase ledger flow, multi-round fusion), not a demo mean.
The product route is ``FedConfig(tp=...)`` -> :class:`bcfl_tpu.fed.engine.
FedEngine`; these helpers serve library users composing programs directly.

This is the TPU-native composition of the reference's two axes of scale
(many clients x a big model), neither of which the reference itself has
(single process, encoder-size models — SURVEY.md §2.4-2.5).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bcfl_tpu.core.mesh import CLIENT_AXIS, ClientMesh

Tree = Any


def as_client_mesh(mesh: Mesh, num_clients: Optional[int] = None) -> ClientMesh:
    """Wrap a raw 2-D ``(clients, tp)`` Mesh (e.g. from
    :func:`bcfl_tpu.core.mesh.fed_tp_mesh`) as a :class:`ClientMesh`."""
    shards = mesh.shape[CLIENT_AXIS]
    num_clients = shards if num_clients is None else num_clients
    if num_clients % shards:
        raise ValueError(
            f"num_clients {num_clients} must be a multiple of the mesh's "
            f"{shards} client shards")
    return ClientMesh(mesh=mesh, num_clients=num_clients,
                      per_device=num_clients // shards,
                      tp=mesh.shape.get("tp", 1))


def build_fed_tp_programs(model, mesh: Mesh, num_clients: Optional[int] = None,
                          **kw):
    """Full :class:`~bcfl_tpu.fed.client_step.FedPrograms` set on a
    clients x tp mesh — every 1-D program (server/gossip rounds, fused
    multi-round variants, split-phase ledger flow, eval) at parity.
    ``kw`` forwards to :func:`~bcfl_tpu.fed.client_step.build_programs` —
    including ``aggregator``/``aggregator_trim``: the Byzantine-robust rules
    (ROBUSTNESS.md) are the same GSPMD bodies on the 2-D mesh, so a
    tp-sharded model gets trimmed-mean/median/krum aggregation with no
    separate implementation (order statistics reduce over the clients axis;
    XLA keeps the tp sharding inside each client's update)."""
    from bcfl_tpu.fed.client_step import build_programs

    return build_programs(model, as_client_mesh(mesh, num_clients), **kw)


def build_fed_tp_round(
    model,
    mesh: Mesh,
    frozen_specs: Optional[Tree] = None,
    optimizer: str = "adamw",
    learning_rate: float = 5e-5,
) -> Callable:
    """Compile ONE clients x tp federated round (compat shim over
    :func:`build_fed_tp_programs`).

    Returns ``round_fn(stacked_adapters, frozen, batches, rngs, mask=None)
    -> (stacked_adapters, stats [C, 3])``: each client trains from its own
    adapters, then every participating client adopts the mask-weighted mean
    (all-ones default reproduces the FedAvg consensus — all clients start the
    next round from the average), masked clients keep their own state.

    ``frozen_specs``, when given, is applied to the frozen tree on each call
    (``device_put`` — a no-op for an already tp-sharded committed tree),
    preserving the old contract that a host-resident base gets megatron-
    sharded rather than silently replicated onto every device.
    """
    progs = build_fed_tp_programs(
        model, mesh, optimizer=optimizer, learning_rate=learning_rate,
        gossip_steps=0)
    C = mesh.shape[CLIENT_AXIS]
    frozen_sh = (None if frozen_specs is None else jax.tree.map(
        lambda s: NamedSharding(mesh, s), frozen_specs))

    def round_fn(stacked, frozen, batches, rngs, mask=None):
        if mask is None:
            mask = jnp.ones((C,), jnp.float32)
        if frozen_sh is not None:
            frozen = jax.device_put(frozen, frozen_sh)
        return progs.gossip_round(stacked, frozen, batches, mask, rngs)

    return round_fn


def stack_adapters(mesh: Mesh, adapters: Tree, num_clients: int) -> Tree:
    """Broadcast one adapter tree to a client-stacked, client-sharded tree."""
    cl = NamedSharding(mesh, P(CLIENT_AXIS))
    return jax.device_put(
        jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (num_clients,) + x.shape),
            adapters),
        cl)
