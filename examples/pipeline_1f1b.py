"""Pipeline parallelism with the 1F1B schedule (vs GPipe).
Runs on a virtual 8-device CPU mesh unless JAX_PLATFORMS says otherwise:
  python pipeline_1f1b.py
  JAX_PLATFORMS=tpu python pipeline_1f1b.py   # a real 8-chip slice

Both schedules produce the SAME loss trajectory; 1F1B caps live
activations at O(P) microbatches instead of GPipe's O(M).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax

import numpy as np
import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu import distributed as dist
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.models import gpt_pipe_model, GPTPretrainingCriterion
from paddle_tpu.parallel.train_step import TrainStep


def run(schedule, ids, steps=5):
    mesh = dist.build_mesh(dp=2, pp=4)
    dist.set_mesh(mesh)
    paddle.seed(0)
    # the pipelined form: pre=embeddings, 8 identical blocks (2 per
    # stage), post=LM head
    pipe = gpt_pipe_model("tiny", dropout=0.0, num_layers=8)
    strategy = DistributedStrategy()
    strategy.pipeline = True
    strategy.pipeline_configs["accumulate_steps"] = 4   # M microbatches
    strategy.pipeline_configs["schedule_mode"] = schedule
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=pipe.parameters())
    step = TrainStep(pipe, opt, loss_fn=GPTPretrainingCriterion(),
                     strategy=strategy, donate=False)
    losses = []
    for _ in range(steps):
        loss = step.step([ids[:, :-1]], [ids[:, 1:]])
        losses.append(float(loss.numpy()))
    return losses


def main():
    ids = np.random.RandomState(0).randint(0, 128, (8, 33)) \
        .astype(np.int64)
    gpipe = run("F-then-B", ids)
    f1b1 = run("1F1B", ids)
    print("GPipe :", " ".join(f"{v:.4f}" for v in gpipe))
    print("1F1B  :", " ".join(f"{v:.4f}" for v in f1b1))
    assert np.allclose(gpipe, f1b1, atol=2e-3), "schedules diverged"
    assert f1b1[-1] < f1b1[0], "did not train"
    print("identical trajectories; 1F1B holds O(P) live activations")


if __name__ == "__main__":
    main()
