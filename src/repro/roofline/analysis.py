"""Three-term roofline from the compiled dry-run artifact.

    t_compute = HLO_FLOPs        / (chips · peak_FLOP/s)
    t_memory  = HLO_bytes        / (chips · HBM_bw)
    t_coll    = collective_bytes / (chips · link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()``. Collective bytes are
parsed out of the optimized HLO text: the sum of operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s ICI
per link.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float      # per chip, bf16
    hbm_bw: float          # bytes/s per chip
    ici_bw: float          # bytes/s per link
    hbm_bytes: float       # capacity per chip


HW_V5E = Hardware("tpu_v5e", peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                  hbm_bytes=16e9)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "fp8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[8,128,2048]{2,1,0}   or  f32[] (scalar)
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# "%name = <result-type> opcode(%op1, %op2, ...), ..."
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_CALL_RE = re.compile(
    r"^((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*))\s+([\w\-]+)\((.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _type_bytes(type_str: str) -> int:
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(type_str))


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-op-kind *operand* bytes summed over the module. Optimized HLO
    references operands by name only, so pass 1 maps %name -> result-type
    bytes and pass 2 resolves each collective's operand list. ``-done`` ops
    are skipped (their ``-start`` already counted)."""
    sizes: dict[str, int] = {}
    calls: list[tuple[str, str]] = []       # (kind, operand-string)
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        c = _CALL_RE.match(rest)
        if not c:
            continue
        rtype, opcode, operands = c.groups()
        sizes[name] = _type_bytes(rtype)
        base = opcode
        for suf in ("-start", "-done"):
            if base.endswith(suf):
                base = base[:-len(suf)]
        if base in _COLLECTIVES and not opcode.endswith("-done"):
            # cut operand list at the closing paren of the call
            depth, end = 1, len(operands)
            for i, ch in enumerate(operands):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            calls.append((base, operands[:end]))
    out = {k: 0 for k in _COLLECTIVES}
    for kind, operands in calls:
        total = sum(sizes.get(nm, 0) for nm in _OPERAND_RE.findall(operands))
        out[kind] += total
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float,
                   chips: int, hw: Hardware = HW_V5E) -> dict:
    t_c = flops / (chips * hw.peak_flops)
    t_m = bytes_accessed / (chips * hw.hbm_bw)
    t_x = coll_bytes / (chips * hw.ici_bw)
    terms = {"t_compute": t_c, "t_memory": t_m, "t_collective": t_x}
    dom = max(terms, key=terms.get)
    bound = max(t_c, t_m, t_x)
    return dict(terms, dominant=dom, t_bound=bound,
                frac_compute=(t_c / bound if bound else 0.0))


def model_flops(cfg, n_tokens: int, *, backward: bool = False) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE); D = tokens. Train = fwd+bwd
    (the 6 already includes backward; forward-only = 2·N·D)."""
    n = cfg.n_active_params() if cfg.moe is not None else cfg.n_params()
    per_tok = 6 * n if backward else 2 * n
    return float(per_tok) * n_tokens


def summarize(hlo_text: str, chips: int, cfg=None,
              n_tokens: Optional[int] = None, backward: bool = False,
              hw: Hardware = HW_V5E, xla_cost: Optional[dict] = None) -> dict:
    """Roofline record from optimized HLO text. Uses the trip-count-aware
    walker (roofline.hlo) — compiled.cost_analysis() counts while bodies
    once and is kept only as a cross-reference field."""
    from repro.roofline import hlo as hlo_mod
    c = hlo_mod.analyze(hlo_text)
    flops = c.flops * chips            # per-chip -> global
    bts = c.bytes * chips
    coll = {k: v * chips for k, v in c.coll.items()}
    coll["total"] = c.coll_bytes * chips
    terms = roofline_terms(flops, bts, coll["total"], chips, hw)
    out = {"hlo_flops": flops, "hlo_bytes": bts,
           "collectives": coll, **terms, "chips": chips}
    if xla_cost:
        out["xla_cost_flops"] = float(xla_cost.get("flops", 0.0))
    if cfg is not None and n_tokens:
        mf = model_flops(cfg, n_tokens, backward=backward)
        out["model_flops"] = mf
        out["useful_flop_ratio"] = mf / flops if flops else 0.0
    return out
