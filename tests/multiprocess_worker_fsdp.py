"""FSDP (ZeRO stage 2) + tensor-parallel worker for the multi-process
launcher tests (reference pattern: test_dist_base.py:668 — the same
script runs at world=1 and world=N and the parent compares losses).

Launched via paddle_tpu.distributed.launch (which wires the PADDLE_* env
contract and jax.distributed) or directly for the single-process
reference run.  Requires XLA_FLAGS=--xla_force_host_platform_device_count=2
and JAX_PLATFORMS=cpu in the environment.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu import distributed as dist
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.sharding import (ColumnParallelLinear,
                                             RowParallelLinear)
from paddle_tpu.parallel.train_step import TrainStep

rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
world = jax.process_count()
ndev = jax.device_count()
assert ndev == 2 * world, (ndev, world)


class MSE(nn.Layer):
    def forward(self, p, l):
        return paddle.mean((p - l) ** 2)


rng = np.random.RandomState(0)
x_global = rng.rand(16, 8).astype("float32")
w_true = rng.rand(8, 1).astype("float32")
y_global = x_global @ w_true
per = 16 // world
x_local = x_global[rank * per:(rank + 1) * per]
y_local = y_global[rank * per:(rank + 1) * per]

# ---- FSDP: ZeRO stage 2 over every device (optimizer state sharded,
# grads reduce-scattered by XLA); cross-process when world > 1 ----------
mesh = dist.build_mesh(sharding=ndev)
dist.set_mesh(mesh)
paddle.seed(0)
net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
strategy = DistributedStrategy()
strategy.sharding = True
strategy.sharding_configs.update({"stage": 2})
step = TrainStep(net, optimizer.Adam(learning_rate=0.05,
                                     parameters=net.parameters()),
                 loss_fn=MSE(), strategy=strategy, mesh=mesh)
losses = []
for _ in range(5):
    loss = step.step([x_local], [y_local])
    losses.append(float(loss.numpy()))
print(f"RESULT fsdp {rank} " + ",".join(f"{v:.6f}" for v in losses),
      flush=True)
assert losses[-1] < losses[0]

# ---- TP: Megatron column->row parallel over every device; the mp
# collectives (partial-sum allreduce) cross processes when world > 1.
# Data axes are size 1, so every process feeds the identical full batch.
mesh_tp = dist.build_mesh(mp=ndev)
dist.set_mesh(mesh_tp)
paddle.seed(0)
tp_net = nn.Sequential(
    ColumnParallelLinear(8, 16, gather_output=False),
    nn.Tanh(),
    RowParallelLinear(16, 1, input_is_parallel=True))
tp_step = TrainStep(tp_net, optimizer.SGD(learning_rate=0.1,
                                          parameters=tp_net.parameters()),
                    loss_fn=MSE(), mesh=mesh_tp)
tp_losses = []
for _ in range(5):
    loss = tp_step.step([x_global], [y_global])
    tp_losses.append(float(loss.numpy()))
print(f"RESULT tp {rank} " + ",".join(f"{v:.6f}" for v in tp_losses),
      flush=True)
assert tp_losses[-1] < tp_losses[0]

print(f"RESULT done {rank}", flush=True)
