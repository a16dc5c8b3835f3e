"""Operations and bytes the algorithm needs, from a configuration's sizes
and the live lengths of each step.

These count what the model requires, not what a compiled program does:
padded tokens, masked pages and free slots cost nothing here. A decode
row of live length ``n`` (keys attended, its own included) attends
``min(n, window)`` keys in every layer that still has attention; an
NBL-linearized layer is one d x d GEMM; the LM head runs once per decode
row and once per prompt (the last position's logits only).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class ModelShape:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    layers: int
    nbl_layers: tuple
    window: Optional[int]
    itemsize: int

    @classmethod
    def from_config(cls, cfg: dict) -> "ModelShape":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(d=d, heads=h, kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or d // h,
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                   layers=cfg["num_hidden_layers"],
                   nbl_layers=tuple(cfg.get("nbl_layers", ())),
                   window=cfg.get("sliding_window"),
                   itemsize=DTYPE_BYTES[cfg["torch_dtype"]])

    @property
    def attn_layers(self) -> int:
        return self.layers - len(self.nbl_layers)

    def keys(self, n: int) -> int:
        return min(n, self.window) if self.window else n

    def linear_flops(self) -> int:
        """GEMM operations of one token through the stack, head excluded."""
        d, hd = self.d, self.head_dim
        attn = 2 * d * (2 * self.heads * hd + 2 * self.kv_heads * hd)
        mlp = 2 * 3 * d * self.d_ff
        nbl = 2 * d * d
        return (self.attn_layers * attn + len(self.nbl_layers) * nbl
                + self.layers * mlp)

    def head_flops(self) -> int:
        return 2 * self.d * self.vocab

    def attn_flops(self, n_keys: int) -> int:
        """Scores and weighted sum of one query over ``n_keys`` keys, in
        one layer."""
        return 4 * self.heads * self.head_dim * n_keys

    # ------------------------------------------------------ model step --
    def decode_flops(self, lengths: Sequence[int]) -> int:
        """One decode step over rows of the given live lengths."""
        per_tok = self.linear_flops() + self.head_flops()
        return sum(per_tok + self.attn_layers * self.attn_flops(self.keys(n))
                   for n in lengths)

    def prefill_flops(self, n_tokens: int) -> int:
        """Whole-prompt prefill of ``n_tokens`` (causal attention)."""
        if self.window and n_tokens > self.window:
            w = self.window
            keys = w * (w + 1) // 2 + (n_tokens - w) * w
        else:
            keys = n_tokens * (n_tokens + 1) // 2
        return (self.linear_flops() * n_tokens + self.head_flops()
                + self.attn_layers * 4 * self.heads * self.head_dim * keys)

    # ---------------------------------------------------- paged kernel --
    def paged_attn_cost(self, lengths: Sequence[int],
                        page_size: int) -> tuple[int, int]:
        """(operations, bytes) of the paged decode kernel over one step, in
        every attention layer: the live KV pages read once, q read and o
        written once per row."""
        flops = byts = 0
        page = 2 * page_size * self.kv_heads * self.head_dim * self.itemsize
        qo = 2 * self.heads * self.head_dim * self.itemsize
        for n in lengths:
            k = self.keys(n)
            flops += self.attn_flops(k)
            byts += math.ceil(k / page_size) * page + qo
        return flops * self.attn_layers, byts * self.attn_layers


def roofline_s(flops: float, byts: float, peak_flops: float,
               peak_bw: float) -> tuple[float, str]:
    """Least time at the chip's peaks, and which bound sets it."""
    tc, tm = flops / peak_flops, byts / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
