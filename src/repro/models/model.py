"""Public model API: ``build_model(cfg)`` -> Model (init / loss / prefill / decode)."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer
from repro.models.layers import Params


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    def init(self, rng) -> Params:
        return transformer.init_params(rng, self.cfg)

    def init_shapes(self) -> Params:
        """Param ShapeDtypeStructs without allocation (dry-run path)."""
        return jax.eval_shape(
            lambda r: transformer.init_params(r, self.cfg),
            jax.random.key(0))

    def forward(self, params, batch, remat: bool = False):
        logits, _aux = transformer.forward_train(params, self.cfg, batch,
                                                 remat=remat)
        return logits

    def loss(self, params, batch, remat: bool = False):
        return transformer.loss_fn(params, self.cfg, batch, remat=remat)

    # the named scopes tag every operation of the step in the device
    # trace, so a profile attributes device time to prefill or decode
    def prefill(self, params, batch, capacity: int):
        with jax.named_scope("prefill"):
            return transformer.prefill(params, self.cfg, batch, capacity)

    def decode_step(self, params, cache, cur_index, tokens, position=None):
        with jax.named_scope("decode_step"):
            return transformer.decode_step(params, self.cfg, cache,
                                           cur_index, tokens, position)

    def init_cache(self, batch: int, capacity: int, enc_len: int = 0,
                   kv_bits: int = 16):
        return transformer.init_cache(self.cfg, batch, capacity, enc_len,
                                      kv_bits)

    def param_count(self, params: Optional[Params] = None) -> int:
        tree = params if params is not None else self.init_shapes()
        return sum(int(jnp.size(x)) if not hasattr(x, "shape") else
                   int(functools.reduce(lambda a, b: a * b, x.shape, 1))
                   for x in jax.tree.leaves(tree))

    def active_param_count(self) -> int:
        """MoE: params touched per token (shared + top_k of routed experts)."""
        total = self.param_count()
        cfg = self.cfg
        if cfg.moe is None:
            return total
        m = cfg.moe
        n_moe_layers = sum(1 for i in range(cfg.n_layers) if cfg.uses_moe_at(i))
        per_expert = 3 * cfg.d_model * m.expert_d_ff
        inactive = n_moe_layers * (m.n_routed_experts - m.top_k) * per_expert
        return total - inactive


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
