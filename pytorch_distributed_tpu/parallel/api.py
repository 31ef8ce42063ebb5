"""Parallel train step: the pjit/NamedSharding ("automatic") path.

The single-device train step (train/trainer.py) is already a pure function;
making it DDP or FSDP is *only* a matter of sharding annotations — XLA's SPMD
partitioner inserts the same collectives torch issues imperatively:

  DDP        → gradient all-reduce (reference DDP reducer; here: psum placed
               at the accumulation boundary because grads of sharded-batch
               loss feed a replicated weight update)
  FSDP full  → all_gather(params) before use + reduce_scatter(grads)
               (reference train_fsdp.py:50-52)
  FSDP grad_op → reduce_scatter(grads) + sharded update + all_gather(params)

The loss the step returns is already the global mean over the sharded batch —
the explicit ``dist.all_reduce(loss, AVG)`` of reference
distributed_trainer.py:131-154 is subsumed by SPMD semantics.

An explicit `shard_map` twin of this path (collectives written by hand, for
teaching/trace parity) lives in parallel/explicit.py.
"""

from __future__ import annotations


import jax
from jax.sharding import Mesh, NamedSharding

import optax

from pytorch_distributed_tpu.config import MeshConfig, ModelConfig
from pytorch_distributed_tpu.models import ModelApi
from pytorch_distributed_tpu.parallel.mesh import (
    batch_partition_spec,
    make_batch_put,
)
from pytorch_distributed_tpu.parallel.sharding import (
    param_partition_specs,
    state_shardings,
)
from pytorch_distributed_tpu.train.state import TrainState
from pytorch_distributed_tpu.train.trainer import make_train_step


def make_parallel_train_step(
    model: ModelApi,
    model_cfg: ModelConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    mesh_cfg: MeshConfig,
    state: TrainState,
    *,
    accum_dtype: str = "float32",
    guard=None,
):
    """Returns (train_step, batch_put) for a sharded TrainState.

    ``train_step`` has the same (state, batch, key) -> (state, metrics)
    signature as the single-device step; ``batch_put`` places a host
    [A, B_global, T] batch onto the mesh with the batch sharding (B split
    over data×fsdp axes, T over seq).
    """
    shardings = state_shardings(state, mesh, mesh_cfg)
    batch_spec = batch_partition_spec(mesh_cfg)  # P(None, batch_axes, seq)
    # Logits [B, T, V]: batch/seq sharded like the inputs, vocab replicated.
    logits_sharding = NamedSharding(
        mesh, jax.sharding.PartitionSpec(batch_spec[1], batch_spec[2], None)
    )
    # Gradients follow the ZeRO level, not the param placement: under
    # shard_grad_op params are replicated but grads reduce-scatter onto the
    # optimizer-state shards.
    grad_shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        param_partition_specs(state.params, mesh_cfg, for_grads=True),
    )
    base_step = make_train_step(
        model,
        model_cfg,
        tx,
        jit=False,
        logits_sharding=logits_sharding,
        grad_shardings=grad_shardings,
        accum_dtype=accum_dtype,
        guard=guard,
    )

    def step_under_mesh(state, batch, dropout_key):
        # Traced under the mesh so an op GSPMD cannot partition (the
        # Pallas flash custom call, ops/pallas_flash.py) can find it and
        # shard_map itself over the axes that shard its operands.
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return base_step(state, batch, dropout_key)

    batch_sharding = NamedSharding(mesh, batch_spec)
    metrics_sharding = NamedSharding(mesh, jax.sharding.PartitionSpec())

    metrics_shardings = {"loss": metrics_sharding, "grad_norm": metrics_sharding}
    if guard is not None:
        metrics_shardings["anomaly"] = metrics_sharding
    step = jax.jit(
        step_under_mesh,
        in_shardings=(
            shardings,
            {"inputs": batch_sharding, "targets": batch_sharding},
            None,
        ),
        out_shardings=(shardings, metrics_shardings),
        donate_argnums=(0,),
    )

    return step, make_batch_put(mesh, mesh_cfg)
