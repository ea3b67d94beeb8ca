"""TransformersTrainer: fine-tune a HF Flax model through the gang.

Reference shape: python/ray/train/tests/test_huggingface_trainer.py
(train over Dataset shards, metrics via session.report, checkpoint
round-trips into a usable model).  Runs hermetically: the model is
built from a config (no pretrained download).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = """
import numpy as np
import ray_tpu
from ray_tpu import data as rd
from ray_tpu.air import ScalingConfig
from ray_tpu.train import TransformersTrainer, load_model

ray_tpu.init(num_cpus=4, _worker_env={"JAX_PLATFORMS": "cpu"})

def model_init():
    from transformers import FlaxGPT2LMHeadModel, GPT2Config
    return FlaxGPT2LMHeadModel(GPT2Config(
        n_layer=2, n_head=2, n_embd=32, n_positions=64, vocab_size=64))

# A deterministic 2-token repeating corpus: loss must fall fast.
rng = np.random.default_rng(0)
rows = [{"tokens": np.tile(rng.integers(0, 64, 2), 9)[:17]}
        for _ in range(64)]
ds = rd.from_items(rows).repartition(2)

trainer = TransformersTrainer(
    model_init_fn=model_init,
    train_loop_config={"epochs": 3, "batch_size": 8, "lr": 5e-3},
    scaling_config=ScalingConfig(num_workers=2),
    datasets={"train": ds})
result = trainer.fit()
print("LOSS_SERIES", [round(m["loss"], 3) for m in result.metrics_history])
assert result.metrics["epoch"] == 2
assert result.metrics_history[-1]["loss"] < result.metrics_history[0]["loss"]

model = load_model(result.checkpoint, model_init)
logits = model(np.asarray([[1, 2, 3]]), params=model.params).logits
assert logits.shape == (1, 3, 64)
print("TRANSFORMERS_TRAINER_OK")
"""


def test_transformers_trainer_end_to_end():
    sys.path.insert(0, _REPO_DIR)
    import __graft_entry__ as g
    env = g.hermetic_cpu_env()
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "TRANSFORMERS_TRAINER_OK" in r.stdout
