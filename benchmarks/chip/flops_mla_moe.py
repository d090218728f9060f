"""Operations and bytes of the MLA + MoE decoder (DeepSeek-V2-Lite), counted
from the configuration's shapes, kept with the benchmark.

``decode_token_flops`` counts the plain model's forward pass for one token:
two operations per multiply-add of every matrix product (attention
projections, the dense layer's and the shared experts' feed-forwards, the
router, the head), the routed experts as the share of a token's top-k
that falls on the experts held here (``k * held / experts`` of one
expert's products, 6 * 8 / 64 for one EP8 rank), and attention in its
expanded form over the keys in the cache (scores over the q head size,
the weighted sum over the v head size, every head). Norms, RoPE, softmax
and elementwise work are not counted, so a share of the peak computed
from it cannot pass what the chip did.

``moe_weight_bytes`` and ``mla_bytes`` are what one decode tick must read
of the two parts at least: the MoE layers' router, held experts and
shared experts; each layer's attention weights, the latent rows of each
live slot's filled positions and one written row per live slot. They are
counted from the work, not from the buffers the program allocates.
"""
from __future__ import annotations

from typing import Iterable


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    return {"d": d, "h": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "kv_lora": cfg["kv_lora_rank"],
            "q_lora": cfg["q_lora_rank"],
            "vocab": cfg["vocab_size"], "layers": layers,
            "dense_layers": dense, "moe_layers": layers - dense,
            "ff_dense": cfg["intermediate_size"],
            "ff_expert": cfg["moe_intermediate_size"],
            "experts": cfg["published"]["n_routed_experts"],
            "held": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"],
            "itemsize": 2 if cfg["torch_dtype"] in ("bfloat16", "float16")
            else 4}


def attention_params(cfg: dict) -> int:
    """Weights of one layer's attention (the matrices; norms are
    counted in bytes, not operations)."""
    m = dims(cfg)
    d, h = m["d"], m["h"]
    if m["q_lora"]:
        q = d * m["q_lora"] + m["q_lora"] * h * m["qk"]
    else:
        q = d * h * m["qk"]
    return (q + d * (m["kv_lora"] + m["rope"])
            + m["kv_lora"] * h * (m["nope"] + m["v"]) + h * m["v"] * d)


def _expert_params(cfg: dict) -> int:
    m = dims(cfg)
    return 3 * m["d"] * m["ff_expert"]


def matmul_flops_per_token(cfg: dict) -> float:
    """Weight matmuls of one token through every layer and the head."""
    m = dims(cfg)
    d = m["d"]
    attn = 2 * attention_params(cfg)
    dense = 2 * 3 * d * m["ff_dense"]
    moe = (2 * d * m["experts"]
           + 2 * _expert_params(cfg) * m["top_k"] * m["held"] / m["experts"]
           + 2 * _expert_params(cfg) * m["shared"])
    return (m["layers"] * attn + m["dense_layers"] * dense
            + m["moe_layers"] * moe + 2 * d * m["vocab"])


def attention_flops(cfg: dict, context: int) -> float:
    """Scores and weighted sum of one query over ``context`` keys, in every
    layer, in the expanded form."""
    m = dims(cfg)
    return m["layers"] * 2 * m["h"] * (m["qk"] + m["v"]) * context


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded (or prefilled-one-token) position with ``context`` keys
    in the cache, itself included."""
    return matmul_flops_per_token(cfg) + attention_flops(cfg, context)


def moe_weight_bytes(cfg: dict) -> int:
    """The MoE layers' routers, held experts and shared experts: what a
    tick reads of them, once each."""
    m = dims(cfg)
    per_layer = (m["d"] * m["experts"]
                 + _expert_params(cfg) * (m["held"] + m["shared"]))
    return m["moe_layers"] * per_layer * m["itemsize"]


def mla_bytes(cfg: dict, contexts: Iterable[int]) -> int:
    """One tick of attention: every layer's attention weights and norms,
    and for each live slot the latent rows of its ``context - 1`` filled
    positions read and its new row written (``contexts``: each live slot's
    context, the new token included)."""
    m = dims(cfg)
    row = (m["kv_lora"] + m["rope"]) * m["itemsize"]
    weights = (attention_params(cfg) + 2 * m["d"] + m["kv_lora"]) \
        * m["itemsize"]
    rows = sum(contexts)          # (context - 1) read + 1 written a slot
    return m["layers"] * (weights + rows * row)
