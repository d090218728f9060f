"""Model facade: binds an ArchConfig to init / loss / prefill / decode."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer, encdec
from repro.models.common import (
    init_tree, abstract_tree, axes_tree, count_params,
)


class Model:
    """A thin, stateless namespace of pure functions bound to ``cfg``."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self._mod = encdec if cfg.family == "encdec" else transformer

    # ---- parameters -------------------------------------------------------
    def param_defs(self):
        return self._mod.model_param_defs(self.cfg)

    def init(self, key) -> Dict[str, Any]:
        return init_tree(self.param_defs(), key, jnp.dtype(self.cfg.dtype))

    def abstract_params(self):
        return abstract_tree(self.param_defs(), jnp.dtype(self.cfg.dtype))

    def param_axes(self):
        return axes_tree(self.param_defs())

    def n_params(self) -> int:
        return count_params(self.param_defs())

    def n_active_params(self) -> int:
        """Active parameters per token (MoE discount) for 6ND roofline."""
        cfg = self.cfg
        total = self.n_params()
        if cfg.moe is None:
            return total
        mc = cfg.moe
        n_stack = cfg.n_layers - mc.first_k_dense
        per_expert = 3 * cfg.d_model * mc.d_expert  # swiglu wi(2x) + wo
        # of the experts held here, a token uses top_k x held / experts
        n_held = len(mc.held)
        inactive = n_stack * (n_held - mc.top_k * n_held / mc.n_experts) \
            * per_expert
        return int(total - inactive)

    # ---- execution --------------------------------------------------------
    def loss(self, params, batch):
        return self._mod.loss_fn(params, batch, self.cfg)

    def forward(self, params, batch):
        return self._mod.forward(params, batch, self.cfg)

    def prefill(self, params, batch):
        if self.cfg.family == "encdec":
            return self._mod.forward(params, batch, self.cfg,
                                     last_only=True)[:, 0]
        return self._mod.prefill(params, batch, self.cfg)

    def init_cache(self, batch_size: int, seq_len: int):
        if self.cfg.family == "encdec":
            return encdec.init_cache(self.cfg, batch_size, seq_len)
        return transformer.init_cache(self.cfg, batch_size, seq_len)

    def decode_step(self, params, cache, tokens, embeds=None):
        if self.cfg.family == "encdec":
            return encdec.decode_step(params, cache, tokens, self.cfg)
        return transformer.decode_step(params, cache, tokens, self.cfg,
                                       embeds=embeds)
