"""Worker for the 2-process localhost distributed test (reference pattern:
unittests/test_collective_base.py — ranks run the same script, results are
printed for the parent to compare)."""
import os
import sys

rank = int(sys.argv[1])
port = sys.argv[2]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["PADDLE_TRAINERS_NUM"] = "2"
os.environ["PADDLE_TRAINER_ID"] = str(rank)
os.environ["PADDLE_TRAINER_ENDPOINTS"] = f"127.0.0.1:{port}"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax

# The launcher (paddle_tpu.distributed.launch) initializes jax.distributed
# BEFORE the user script imports the framework — replicate that here (the
# framework import touches the XLA backend).
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=rank)

import numpy as np
import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu import distributed as dist

# env-contract bootstrap (no-op here since the launcher already
# initialized; still builds the default mesh)
dist.init_parallel_env()
assert jax.process_count() == 2, jax.process_count()
assert dist.get_rank() == rank

mesh = dist.build_mesh(dp=4)   # 2 procs x 2 local devices
dist.set_mesh(mesh)

# cross-process psum through the collective API
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def summed(x):
    return jax.lax.psum(x, "dp")


from jax import shard_map
local = np.full((2, 1), float(rank + 1), np.float32)
glob = dist.mesh.host_local_to_global(local, mesh, "dp", None)
out = jax.jit(shard_map(summed, mesh=mesh, in_specs=P("dp"),
                        out_specs=P("dp")))(glob)
total = float(np.asarray(out.addressable_shards[0].data)[0, 0])
# ranks contribute 1+1+2+2 = 6 over the 4 shards
assert total == 6.0, total
print(f"RESULT psum {rank} {total}", flush=True)

# data-parallel training: per-rank local shard of a shared problem
from paddle_tpu.parallel.train_step import TrainStep


class MSE(nn.Layer):
    def forward(self, p, l):
        return paddle.mean((p - l) ** 2)


paddle.seed(0)   # identical init on both ranks
net = nn.Linear(8, 1)
step = TrainStep(net, optimizer.SGD(learning_rate=0.1,
                                    parameters=net.parameters()),
                 loss_fn=MSE(), mesh=mesh)
rng = np.random.RandomState(0)
x_global = rng.rand(16, 8).astype("float32")
w_true = rng.rand(8, 1).astype("float32")
y_global = x_global @ w_true
# each rank feeds its half (8 rows)
x_local = x_global[rank * 8:(rank + 1) * 8]
y_local = y_global[rank * 8:(rank + 1) * 8]
losses = []
for _ in range(5):
    loss = step.step([x_local], [y_local])
    losses.append(float(loss.numpy()))
print(f"RESULT losses {rank} " + ",".join(f"{v:.6f}" for v in losses),
      flush=True)

# multi-host pipeline parallelism: pp=2 spans the two processes (each
# stage lives on one host's devices) — the round-1 NotImplementedError
# lifted in parallel/train_step.py.  Both GPipe and 1F1B schedules.
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.fleet.meta_parallel import PipelineLayer

for schedule in ("F-then-B", "1F1B"):
    # device order (0,2,1,3): after the (dp, pp) reshape each pp pair is
    # (proc0-device, proc1-device), so the ppermute ring genuinely
    # crosses the process boundary (d0..d1 live on proc 0, d2..d3 on
    # proc 1 — the default order would keep pp within one host)
    devs = jax.devices()
    assert devs[0].process_index != devs[2].process_index, \
        [d.process_index for d in devs]
    mesh_pp = dist.build_mesh(dp=2, pp=2,
                              devices=[devs[0], devs[2],
                                       devs[1], devs[3]])
    for pair in mesh_pp.devices.reshape(2, 2):
        assert pair[0].process_index != pair[1].process_index, \
            "pp pair does not span processes"
    dist.set_mesh(mesh_pp)
    paddle.seed(0)
    blocks = [nn.Sequential(nn.Linear(8, 8), nn.Tanh())
              for _ in range(2)]
    pipe = PipelineLayer(pre=nn.Linear(8, 8), blocks=blocks,
                         post=nn.Linear(8, 1))
    strategy = DistributedStrategy()
    strategy.pipeline = True
    strategy.pipeline_configs["accumulate_steps"] = 2
    strategy.pipeline_configs["schedule_mode"] = schedule
    opt = optimizer.SGD(learning_rate=0.05,
                        parameters=pipe.parameters())
    pstep = TrainStep(pipe, opt, loss_fn=MSE(), strategy=strategy,
                      mesh=mesh_pp, donate=False)
    pl = []
    for _ in range(4):
        # multi-host pipeline contract: every process feeds the
        # identical GLOBAL batch (the pp ring spans hosts)
        loss = pstep.step([x_global], [y_global])
        pl.append(float(loss.numpy()))
    tag = "pp_gpipe" if schedule == "F-then-B" else "pp_1f1b"
    print(f"RESULT {tag} {rank} " + ",".join(f"{v:.6f}" for v in pl),
          flush=True)

print(f"RESULT done {rank}", flush=True)
