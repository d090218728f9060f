"""Operations and bytes counted from shapes, kept with the benchmark.

``forward_flops`` counts the plain model's forward pass of a dense
llama-style decoder (GQA attention, SwiGLU feed-forward, untied head):
two operations per multiply-add of every matrix product, nothing for
norms, RoPE, softmax or elementwise work (a few percent at these widths),
so a share of the peak computed from it cannot pass what the chip did.

``hlo_bytes`` reads operand and result shapes from the text of one HLO
instruction, as the profiler names an op event.
"""
from __future__ import annotations

import math
import re

_ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE = re.compile(r"\b([a-z]+[0-9a-z]*)\[([0-9,]*)\]")


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"],
            "window": cfg.get("sliding_window")}


def matmul_flops_per_token(cfg: dict) -> float:
    """Weight matmuls of one token through every layer and the head."""
    m = dims(cfg)
    d, hd = m["d"], m["hd"]
    per_layer = 2 * d * (m["h"] * hd + 2 * m["kv"] * hd) \
        + 2 * m["h"] * hd * d + 2 * d * 2 * m["ff"] + 2 * m["ff"] * d
    return m["layers"] * per_layer + 2 * d * m["vocab"]


def attention_flops(cfg: dict, context: int) -> float:
    """Scores and weighted sum of one query over ``context`` keys, in every
    layer (the window caps the keys a sliding-window layer sees)."""
    m = dims(cfg)
    if m["window"]:
        context = min(context, m["window"])
    return m["layers"] * 4 * m["h"] * m["hd"] * context


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded (or prefilled-one-token) position with ``context`` keys
    in the cache, itself included."""
    return matmul_flops_per_token(cfg) + attention_flops(cfg, context)


def sequence_flops(cfg: dict, batch: int, seq: int) -> float:
    """A causal forward over ``batch`` sequences of ``seq`` tokens."""
    m = dims(cfg)
    window = m["window"] or seq
    keys = sum(min(c, window) for c in range(1, seq + 1))
    attn = m["layers"] * 4 * m["h"] * m["hd"] * keys
    return batch * (seq * matmul_flops_per_token(cfg) + attn)


def hlo_bytes(instruction: str) -> int:
    """Bytes of the result and operands named in one HLO instruction's
    text (``%x = f32[8,1024]{...} custom-call(f32[8,1024]{...} %y), ...``).
    Attributes after the operand list are not read."""
    head = instruction.split("custom_call_target")[0]
    total = 0
    for dtype, shape in _SHAPE.findall(head):
        if dtype not in _ITEMSIZE:
            continue
        n = math.prod(int(s) for s in shape.split(",") if s) if shape else 1
        total += n * _ITEMSIZE[dtype]
    return total
