"""Hybrid parallelism on a device mesh: dp x sharding(ZeRO) x mp.
Runs on a virtual 8-device CPU mesh unless JAX_PLATFORMS says otherwise:
  python distributed_hybrid.py
  JAX_PLATFORMS=tpu python distributed_hybrid.py   # a real 8-chip slice
"""
import os
import sys

# allow running as `python examples/<script>.py` from a repo checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# This demo needs an 8-device mesh, so it names its platform (the
# choice must be made before the backend initializes): the virtual CPU
# mesh by default, whatever JAX_PLATFORMS says otherwise.
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax

import numpy as np
import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu import distributed as dist
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.models import GPTModel, GPTPretrainingCriterion
from paddle_tpu.parallel.train_step import TrainStep
from paddle_tpu.distributed.checkpoint import (save_train_state,
                                               load_train_state)


def main():
    paddle.seed(0)
    mesh = dist.build_mesh(dp=2, sharding=2, mp=2)
    dist.set_mesh(mesh)

    model = GPTModel.from_config("tiny", dropout=0.0, use_mp=True)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    strategy = DistributedStrategy()
    strategy.sharding = True                      # ZeRO stage 2
    strategy.sharding_configs = {"stage": 2}
    step = TrainStep(model, opt, loss_fn=GPTPretrainingCriterion(),
                     strategy=strategy, mesh=mesh)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (8, 65)).astype(np.int32)
    for it in range(5):
        loss = step.step([ids[:, :-1]], [ids[:, 1:]])
        print(f"iter {it} loss {float(loss.numpy()):.4f}")

    save_train_state(step, "/tmp/hybrid_ckpt")    # sharded checkpoint
    load_train_state(step, "/tmp/hybrid_ckpt")    # restores onto the mesh
    print("sharded checkpoint roundtrip OK")


if __name__ == "__main__":
    main()
