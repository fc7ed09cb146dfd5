"""Sharded train-step compilation: model + optax + ShardingStrategy -> pjit.

The TPU-native core of the Train layer: where the reference wraps a torch
module in DDP/FSDP (train/torch/train_loop_utils.py:158 prepare_model), here
a loss function and a strategy compile into ONE XLA program whose collectives
(reduce-scatter/all-gather for fsdp, all-reduce for dp, all-to-all for ep)
are inserted by GSPMD along the mesh axes. Buffer donation keeps params/opt
state in place across steps (HBM), and batch shardings put the host->device
transfer on the right devices.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel import memory
from ray_tpu.parallel.sharding import ShardingStrategy, strategy_from_name


@dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: Any  # scalar int32 array


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt_state, s.step), None),
    lambda _, c: TrainState(*c),
)


def init_train_state(init_fn: Callable[[], Any], optimizer,
                     mesh: Mesh, strategy: "ShardingStrategy | str"):
    """Initialize params + opt state directly into their shardings.

    init_fn runs under jit with sharded outputs, so even a model too big for
    one device initializes without materializing replicated copies.
    """
    if isinstance(strategy, str):
        strategy = strategy_from_name(strategy)
    with mesh:
        sample = jax.eval_shape(init_fn)
        param_sh = strategy.param_shardings(mesh, sample)
        params = jax.jit(init_fn, out_shardings=param_sh)()
        opt_state = jax.jit(
            optimizer.init,
            in_shardings=(param_sh,),
            out_shardings=_opt_state_shardings(optimizer, sample, param_sh,
                                               mesh),
        )(params)
        # Placed as the step's out_shardings place it: a counter that
        # changed sharding between the first call and the second made jit
        # trace and compile the whole step a second time.
        step = jax.device_put(jnp.zeros((), jnp.int32),
                              NamedSharding(mesh, P()))
    return TrainState(params, opt_state, step)


def _opt_state_shardings(optimizer, sample_params, param_shardings, mesh):
    """Shard optimizer moments like their parameters (ZeRO partitioning of
    optimizer state falls out of the fsdp param sharding)."""
    state_shape = jax.eval_shape(optimizer.init, sample_params)
    flat_param = [
        (tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path), sh)
        for path, sh in jax.tree_util.tree_flatten_with_path(param_shardings)[0]
    ]

    def assign(path, leaf):
        # Moments live under e.g. (0, 'mu', <param path...>): match a param
        # whose full path is a suffix of this leaf's path.
        key = tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for pkey, sh in flat_param:
            if len(key) >= len(pkey) and key[-len(pkey):] == pkey:
                return sh
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(assign, state_shape)


def make_train_step(loss_fn: Callable, optimizer, mesh: Mesh,
                    strategy: "ShardingStrategy | str",
                    sample_params: Any = None,
                    donate: bool = True,
                    accum_steps: int = 0):
    """Build the jitted sharded train step.

    loss_fn(params, batch) -> scalar. Returns the jitted step(state, batch)
    -> (state, metrics), compiled with GSPMD shardings from the strategy.
    donate=True hands the input state's buffers to the output state, so
    params and optimizer state are updated in place in HBM; the caller
    must not touch the state it passed in afterwards.

    accum_steps > 0: gradient accumulation INSIDE the compiled program —
    every batch leaf carries a leading [accum_steps] dim and a lax.scan
    runs that many microbatch fwd+bwd passes before ONE optimizer update
    (a large effective batch in one executable launch).
    """
    if isinstance(strategy, str):
        strategy = strategy_from_name(strategy)

    def _grads(params, batch):
        # (the region's own row in a trace is what no scope of the model
        # holds: residual adds, casts of the gradients)
        with jax.named_scope("loss_and_grad"):
            return jax.value_and_grad(loss_fn)(params, batch)

    def _step(state: TrainState, batch):
        with memory.told(_budget(state)):
            return _traced_step(state, batch)

    def _budget(state: TrainState) -> memory.Budget:
        """What a device holds across the step, for a model that spends
        spare memory on less recomputation (parallel/memory.py): the state
        as its shardings cut it (whole where the caller gave no
        sample_params), the accumulated gradient beside it."""
        whole = memory.tree_bytes(state.params)
        held = (whole if state_sh is None
                else memory.tree_bytes(state.params, state_sh.params))
        opt = memory.tree_bytes(
            state.opt_state, None if state_sh is None else state_sh.opt_state)
        return memory.Budget(
            limit=memory.device_limit(mesh.devices.flat),
            state=held + opt + (held if accum_steps else 0),
            share=held / max(whole, 1))

    def _traced_step(state: TrainState, batch):
        if accum_steps:
            def micro(carry, mb):
                loss_sum, gacc = carry
                loss, g = _grads(state.params, mb)
                with jax.named_scope("grad_accum"):
                    gacc = jax.tree_util.tree_map(
                        lambda a, gi: a + gi.astype(a.dtype), gacc, g)
                return (loss_sum + loss.astype(jnp.float32), gacc), None
            gzero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (loss_sum, gsum), _ = jax.lax.scan(
                micro, (jnp.float32(0.0), gzero), batch)
            inv = 1.0 / accum_steps
            with jax.named_scope("grad_accum"):
                grads = jax.tree_util.tree_map(lambda g: g * inv, gsum)
            loss = loss_sum * inv
        else:
            loss, grads = _grads(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        return (TrainState(params, opt_state, state.step + 1),
                {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                 "step": state.step + 1})

    bspec = strategy.batch_spec
    if accum_steps:
        bspec = P(*((None,) + tuple(bspec)))
    batch_sh = NamedSharding(mesh, bspec)
    kwargs = {}
    state_sh = None
    if donate:
        kwargs["donate_argnums"] = (0,)
    if sample_params is not None:
        param_sh = strategy.param_shardings(mesh, sample_params)
        opt_sh = _opt_state_shardings(optimizer, sample_params, param_sh, mesh)
        state_sh = TrainState(param_sh, opt_sh,
                              NamedSharding(mesh, P()))
        kwargs["in_shardings"] = (state_sh, batch_sh)
        kwargs["out_shardings"] = (state_sh, NamedSharding(mesh, P()))
    # NOTE to callers: do NOT wrap calls in `with mesh:` — an active Mesh
    # context bypasses the C++ jit dispatch fast path and re-enters Python
    # tracing machinery per call. Explicit NamedShardings make the context
    # unnecessary; program-level mesh use (shard_map in the flash, pipeline
    # and ring paths) closes over the mesh object directly.
    return jax.jit(_step, **kwargs)


def make_eval_step(loss_fn: Callable, mesh: Mesh,
                   strategy: "ShardingStrategy | str",
                   sample_params: Any = None):
    """Jitted eval step with the strategy's batch/param shardings applied,
    so eval reuses the training layout instead of re-laying-out (replicating)
    a sharded model."""
    if isinstance(strategy, str):
        strategy = strategy_from_name(strategy)

    batch_sh = NamedSharding(mesh, strategy.batch_spec)
    kwargs = {}
    if sample_params is not None:
        param_sh = strategy.param_shardings(mesh, sample_params)
        kwargs["in_shardings"] = (param_sh, batch_sh)
        kwargs["out_shardings"] = NamedSharding(mesh, P())

    def _eval(params, batch):
        return loss_fn(params, batch).astype(jnp.float32)

    # No `with mesh:` on the hot path — see make_train_step.
    return jax.jit(_eval, **kwargs)
