"""Canonical fused training step (ref: the reference's Fleet training loop —
forward/backward/allreduce/optimizer as separate phases; here ONE jitted,
donated XLA program: grads, collectives, optimizer update and LR schedule all
fuse, params stay resident in HBM in their sharded layout).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.module import Module, combine, partition_trainable, value_and_grad
from paddle_tpu.distributed.mesh import HybridMesh
from paddle_tpu.distributed.sharded import partition_specs, shard_module
from paddle_tpu.observability.compile import instrumented_jit


@jax.tree_util.register_pytree_node_class
class TrainState:
    """(model, opt_state, rng) bundle that flattens as one pytree.
    ``layout`` is static: the sharding of every leaf of a state that
    ``init_state`` laid out over a mesh (None otherwise). It travels with
    the state so the step can hand the new state back in the same layout."""

    def __init__(self, model, opt_state, rng=None, layout=None):
        self.model = model
        self.opt_state = opt_state
        self.rng = rng
        self.layout = layout

    def tree_flatten(self):
        return (self.model, self.opt_state, self.rng), self.layout

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, layout=aux)

    def updated(self, model, opt_state, rng) -> "TrainState":
        """The state a step returns, held to this state's layout. Left to
        itself XLA re-shards small replicated leaves on the way out (norm
        weights and their slots, over fsdp), and a state that comes back
        laid out differently from the one the step was compiled for
        compiles it a second time."""
        new = TrainState(model, opt_state, rng, self.layout)
        if self.layout is None:
            return new
        leaves, treedef = jax.tree_util.tree_flatten(new)
        return treedef.unflatten(
            [jax.lax.with_sharding_constraint(l, s)
             for l, s in zip(leaves, self.layout, strict=True)])

    @property
    def step(self):
        return self.opt_state["step"]


def make_train_step(loss_fn: Callable, optimizer, mesh: Optional[HybridMesh] = None,
                    donate: bool = True, with_rng: bool = False):
    """loss_fn(model, *batch[, rng]) -> scalar. Returns jitted
    step(state, *batch) -> (state, loss)."""

    def step(state: TrainState, *batch):
        if with_rng:
            rng, sub = jax.random.split(state.rng)
            loss, grads = value_and_grad(loss_fn)(state.model, *batch, sub)
        else:
            rng = state.rng
            loss, grads = value_and_grad(loss_fn)(state.model, *batch)
        model, opt_state = optimizer.step(state.model, grads, state.opt_state)
        return state.updated(model, opt_state, rng), loss

    return instrumented_jit(step, name="train.step",
                            donate_argnums=(0,) if donate else ())


def init_state(model: Module, optimizer, mesh: Optional[HybridMesh] = None,
               seed: int = 0) -> TrainState:
    if mesh is not None:
        model = shard_module(model, mesh)
    state = TrainState(model, optimizer.init(model), jax.random.PRNGKey(seed))
    if mesh is None:
        return state
    # slots inherit param shardings (tree_map over sharded params); what has
    # no parameter to inherit from (step counter, learning rate, rng key) is
    # replicated over the mesh. The layout is then recorded in the state, so
    # that the step returns it as it got it and is compiled once.
    on_mesh = set(mesh.mesh.devices.flat)
    state = jax.tree_util.tree_map(
        lambda l: jax.device_put(l, mesh.replicated())
        if isinstance(l, jax.Array) and l.sharding.device_set != on_mesh
        else l, state)
    state.layout = tuple(l.sharding for l in jax.tree_util.tree_leaves(state))
    return state
