"""Device-placement backends: where the serving engine's arrays live.

The engine (`serve/engine.py`) is device-agnostic: every host→device
boundary crossing — initial parameter/cache placement, the device-resident
tick state, the per-tick host staging uploads, and program compilation —
goes through ONE of these backends. The engine never calls ``jnp.asarray``
or ``jax.device_put`` itself, so the same tick loop serves three fabrics:

* :class:`DefaultBackend` — the process default device, exactly the
  pre-refactor behaviour (uncommitted ``jnp.asarray`` staging). The gated
  single-device steady-state hot path runs through this backend, so it must
  stay free of per-tick overhead (C3 parity: the cluster layer must not tax
  the engine it grew out of).
* :class:`DeviceBackend` — pins an engine to one explicit device: the
  split-mode fabric, one independent replica per mesh device. Everything,
  including the per-tick host staging, lands directly on that device —
  replicas never serialize through the process default device.
* :class:`ShardedBackend` — tensor-parallel placement over a
  :class:`~repro.dist.sharding.MeshInfo` ``model`` axis: params via
  ``param_shardings`` (attention heads partitioned), the head-major ``[L,B,KV,S,hd]``
  KV cache via ``serve_cache_shardings``, tick state and host staging
  replicated. Dispatch programs are plain ``jax.jit`` — GSPMD partitions
  them from the operand shardings (merge-mode serving).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import (
    MeshInfo,
    param_shardings,
    replicated,
    serve_cache_shardings,
    serve_state_shardings,
)


class PlacementBackend:
    """Default placement: the process default device, uncommitted arrays.

    Subclasses override the four placement hooks; ``jit`` is shared (a
    dispatch program's placement follows its committed operands, so pinning
    or sharding the params/cache/state is sufficient).
    """

    def put_params(self, model, params) -> Any:
        """Place the model parameters (called once at engine construction)."""
        return params

    def put_cache(self, model, cache) -> Any:
        """Place the decode cache pytree (once; donated thereafter)."""
        return cache

    def put_state(self, x) -> Any:
        """Place a device-resident tick-state array (tokens/lengths/PRNG)."""
        return x

    def put_host(self, x) -> Any:
        """Upload a freshly-built host staging array (per-tick path)."""
        return jnp.asarray(x)

    def jit(self, fn, **kwargs) -> Any:
        return jax.jit(fn, **kwargs)

    def describe(self) -> str:
        return "default-device"


# the pre-refactor engine behaviour, importable by name
DefaultBackend = PlacementBackend


class DeviceBackend(PlacementBackend):
    """Pin one engine to one explicit device (a split-mode replica)."""

    def __init__(self, device) -> None:
        self.device = device

    def put_params(self, model, params) -> Any:
        return jax.device_put(params, self.device)

    def put_cache(self, model, cache) -> Any:
        return jax.device_put(cache, self.device)

    def put_state(self, x) -> Any:
        return jax.device_put(x, self.device)

    def put_host(self, x) -> Any:
        # staging lands DIRECTLY on the replica's device: uncommitted
        # jnp.asarray would place it on the process default device and pay
        # an extra hop (and serialize all replicas through device 0) on a
        # real multi-device fabric
        return jax.device_put(x, self.device)

    def describe(self) -> str:
        return f"device:{self.device.id}"


class ShardedBackend(PlacementBackend):
    """Tensor-parallel placement over ``mesh_info`` (merge-mode serving).

    Params shard per ``spec_for_param`` (attention heads on the ``model``
    axis), the KV cache per ``serve_cache_shardings`` (KV heads, head_dim
    fallback), and everything per-slot/host-built replicates — the tick
    loop's descriptors and override lanes are control state, identical on
    every shard, exactly like the paper's merged fabric running under ONE
    controller.
    """

    def __init__(self, mesh_info: MeshInfo) -> None:
        self.mesh_info = mesh_info

    def put_params(self, model, params) -> Any:
        return jax.device_put(params, param_shardings(params, self.mesh_info))

    def put_cache(self, model, cache) -> Any:
        return jax.device_put(
            cache,
            serve_cache_shardings(jax.eval_shape(lambda: cache), self.mesh_info),
        )

    def put_state(self, x) -> Any:
        return jax.device_put(x, serve_state_shardings(x, self.mesh_info))

    def put_host(self, x) -> Any:
        return jax.device_put(jnp.asarray(x), replicated(self.mesh_info))

    def describe(self) -> str:
        mi = self.mesh_info
        return f"sharded:model={mi.model_size},devices={mi.n_devices}"


def resolve_backend(backend: Optional[PlacementBackend]) -> PlacementBackend:
    return backend if backend is not None else DefaultBackend()
