"""TorchTrainer: gloo process groups + DDP over the worker gang.

Reference shape: python/ray/train/tests/test_torch_trainer.py — the gang
forms a real torch.distributed group (rank-0 TCP rendezvous), DDP
averages gradients across workers, metrics flow via session.report.
"""

import os
import subprocess
import sys

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import numpy as np
import ray_tpu
from ray_tpu.air import ScalingConfig, session
from ray_tpu.train.torch import TorchTrainer, prepare_model

ray_tpu.init(num_cpus=4, _worker_env={"JAX_PLATFORMS": "cpu"})

def loop(config):
    import torch
    import torch.distributed as dist
    assert dist.is_initialized() and dist.get_world_size() == 2
    rank = dist.get_rank()

    # Gradient averaging check: each rank computes a different loss on
    # the same weights; DDP must produce identical averaged grads.
    torch.manual_seed(0)
    model = torch.nn.Linear(4, 1)
    ddp = prepare_model(model)
    x = torch.full((8, 4), float(rank + 1))
    loss = ddp(x).square().mean()
    loss.backward()
    g = model.weight.grad.clone()
    gathered = [torch.zeros_like(g) for _ in range(2)]
    dist.all_gather(gathered, g)
    assert torch.allclose(gathered[0], gathered[1]), "DDP grads differ"

    # Train a real regression to convergence.
    torch.manual_seed(1 + rank)
    model = prepare_model(torch.nn.Linear(4, 1))
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    xs = torch.randn(256, 4)
    ys = xs @ torch.tensor([[1.0], [-2.0], [3.0], [0.5]]) + 0.25
    for epoch in range(30):
        opt.zero_grad()
        loss = (model(xs) - ys).square().mean()
        loss.backward()
        opt.step()
        session.report({"loss": float(loss)})

trainer = TorchTrainer(loop, scaling_config=ScalingConfig(num_workers=2))
result = trainer.fit()
assert result.metrics["loss"] < 0.05, result.metrics
print("TORCH_TRAINER_OK", round(result.metrics["loss"], 4))
"""


def test_torch_trainer_ddp_end_to_end():
    sys.path.insert(0, _REPO_DIR)
    import __graft_entry__ as g
    env = g.hermetic_cpu_env()
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "TORCH_TRAINER_OK" in r.stdout
