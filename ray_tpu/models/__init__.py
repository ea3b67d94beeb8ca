"""ray_tpu.models: flagship model definitions (pure-functional JAX).

Models are (init_params, apply) pairs over plain pytrees with a parallel
pytree of logical-axis annotations, so any model shards under any
`ray_tpu.parallel.MeshSpec` without wrapper classes (contrast the reference,
which wraps torch modules in DDP/FSDP at `train/torch/train_loop_utils.py:70`).
"""

from ray_tpu.models.gpt import (  # noqa: F401
    GPTConfig,
    gpt_forward,
    gpt_init,
    gpt_loss,
    gpt_param_axes,
    make_train_step,
    make_train_state,
)
from ray_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
    llama_param_axes,
)
from ray_tpu.models.serving import (  # noqa: F401
    ServedModel,
    serving_model,
)
from ray_tpu.models.mlp import (  # noqa: F401
    mlp_forward,
    mlp_init,
    mlp_loss,
)
