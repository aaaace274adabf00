"""mp=2 step-time microbench on the 8-device CPU mesh (TP remat check)."""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
import time
import sys
sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", ".."))
import jax
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import optimizer
from paddle_tpu.models import GPTModel, GPTPretrainingCriterion
from paddle_tpu.parallel.train_step import TrainStep

mesh = dist.build_mesh(dp=4, mp=2, devices=jax.devices()[:8])
dist.set_mesh(mesh)
paddle.seed(0)
model = GPTModel(num_layers=4, hidden_size=256, num_heads=8,
                 vocab_size=1024, max_position=256, dropout=0.0,
                 use_mp=True)
opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
step = TrainStep(model, opt, loss_fn=GPTPretrainingCriterion(),
                 donate=False)
rng = np.random.RandomState(0)
ids = rng.randint(0, 1024, (8, 129)).astype(np.int64)
loss = step.step([ids[:, :-1]], [ids[:, 1:]]); loss.numpy()
t0 = time.perf_counter()
N = 20
for _ in range(N):
    loss = step.step([ids[:, :-1]], [ids[:, 1:]])
loss.numpy()
print(f"mp=2 dp=4 step time: {(time.perf_counter()-t0)/N*1000:.1f} ms  "
      f"loss={float(loss.numpy()):.4f}")
