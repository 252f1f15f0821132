"""minicpm-2b [dense] — 40L d_model=2304 36H (MHA kv=36) d_ff=5760 vocab=122753.

[arXiv:2404.06395; hf openbmb/MiniCPM-2B-sft-bf16 config.json]. A Llama-like
block (RMSNorm eps 1e-5, rotary theta 10000, SwiGLU, tied head) with three
muP scalings from ``modeling_minicpm.py``:

* ``scale_emb`` 12: the token embeddings are multiplied by 12 before the
  first layer;
* ``scale_depth`` 1.4: each residual branch, attention and MLP, is
  multiplied by 1.4 / sqrt(40) before it is added to the stream;
* ``dim_model_base`` 256: the final normed hidden state is divided by
  2304 / 256 = 9 before the tied head.

The WSD learning-rate schedule is a training-time property (see
repro.training.optimizer.wsd_schedule).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="minicpm-2b",
    family="dense",
    n_layers=40,
    d_model=2304,
    n_heads=36,
    n_kv_heads=36,
    d_ff=5760,
    vocab_size=122753,
    rope_theta=10000.0,
    norm_eps=1e-5,
    tie_embeddings=True,
    scale_emb=12.0,
    scale_depth=1.4,
    dim_model_base=256,
)
