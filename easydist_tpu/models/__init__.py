"""Model zoo used by tests, examples, and benchmarks.

Pure-functional jax models (init/apply pairs) mirroring the reference's
benchmark model set (benchmark/torch/model/: GPT, wide-ResNet, GAT;
benchmark/bench_case.py:5-25 for the headline configs).  Written TPU-first:
bfloat16-friendly, static shapes, no data-dependent control flow.
"""

from .mlp import mlp_init, mlp_apply, make_mlp_train_step  # noqa: F401
from .gpt import (GPTConfig, gpt_init, gpt_apply,  # noqa: F401
                  make_gpt_train_step)
from .gpt import (init_kv_cache as gpt_init_kv_cache,  # noqa: F401
                  gpt_prefill, gpt_prefill_chunk, gpt_decode_step)
from .decoder import Decoder  # noqa: F401
from .resnet import resnet_init, resnet_apply, make_resnet_train_step  # noqa: F401
from .optim import adam_init, adam_update, sgd_update  # noqa: F401
from .llama import (LlamaConfig, llama_init, llama_apply,  # noqa: F401
                    make_llama_train_step)
from .llama import (init_kv_cache as llama_init_kv_cache,  # noqa: F401
                    llama_prefill, llama_prefill_chunk, llama_decode_step)
from .vit import ViTConfig, vit_init, vit_apply, make_vit_train_step  # noqa: F401
from .gat import GATConfig, gat_init, gat_apply, make_gat_train_step  # noqa: F401
from . import jamba, olmo_hybrid, lfm2_moe  # noqa: F401  (serving only: `decoder(cfg)`)
