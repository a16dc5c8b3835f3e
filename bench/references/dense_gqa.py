"""Plain float32 reference of a dense decoder with NBL-linearized layers.

The architecture, written out from its published description and from
nothing of the program under test: token embedding; per layer, either
pre-norm grouped-query self-attention with rotary positions (rotate-half
pairing, causal, optional sliding window) or, on the layers a config names
in ``nbl_layers``, the NBL replacement ``x + (x W + b)``; then a pre-norm
SwiGLU MLP; a final RMSNorm and the LM head (the embedding's transpose
when tied). Every matrix product runs in float32 at "highest" precision.

The weights are the configuration's seeded weights, drawn here from the
run's seed by the recipe that defines them: a key per scan group of equal
consecutive layers (``groups``), split per layer; each matrix
``normal * fan_in ** -0.5`` rounded to the served dtype; norm gains 1 and
NBL biases 0. No weight, scale or table of the program is read.

``control=True`` computes the same model with every GEMM's operands
rounded to the precision one step below the served dtype (``LOWER``):
float8 e4m3 below bfloat16, with one scale per tensor for weights and per
row for activations; bfloat16 below float32. The output check must refuse
it.

Work is layer by layer with one layer's weights live at a time, and one
sequence's attention at a time, so it fits beside nothing else on the
chip once the program's state is freed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0
# the precision one step below each served dtype
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
         "float16": "float8_e4m3fn"}


def groups(kinds: list) -> list:
    """Scan groups of the seeded key layout: greedy maximal runs of a
    repeated unit (period up to 8) over the flat layer kinds, preferring
    more coverage, then shorter units; a layer that repeats nothing is a
    group of its own."""
    out, i, n = [], 0, len(kinds)
    while i < n:
        best_unit, best_rep, best_cover = (kinds[i],), 1, 1
        for period in range(1, 9):
            if i + period > n:
                break
            unit = tuple(kinds[i:i + period])
            rep = 1
            while (i + (rep + 1) * period <= n
                   and tuple(kinds[i + rep * period:
                                   i + (rep + 1) * period]) == unit):
                rep += 1
            cover = rep * period
            if rep >= 2 and (cover > best_cover or (
                    cover == best_cover and period < len(best_unit))):
                best_unit, best_rep, best_cover = unit, rep, cover
        out.append((best_unit, best_rep))
        i += best_cover
    return out


class Shapes:
    def __init__(self, cfg: dict):
        self.d = cfg["hidden_size"]
        self.h = cfg["num_attention_heads"]
        self.kv = cfg["num_key_value_heads"]
        self.hd = cfg.get("head_dim") or self.d // self.h
        self.ff = cfg["intermediate_size"]
        self.v = cfg["vocab_size"]
        self.n = cfg["num_hidden_layers"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = cfg["rope_theta"]
        self.window = cfg.get("sliding_window")
        self.tied = cfg["tie_word_embeddings"]
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        nbl = set(cfg.get("nbl_layers", ()))
        self.kinds = ["nbl" if i in nbl else "attn" for i in range(self.n)]


def _normal(key, shape, fan_in, dtype):
    w = (jax.random.normal(key, shape) * fan_in ** -0.5).astype(dtype)
    return w.astype(jnp.float32)


def layer_weights(s: Shapes, kind: str, key) -> dict:
    k1, k2 = jax.random.split(key)
    p = {}
    if kind == "attn":
        a = jax.random.split(k1, 4)
        p["wq"] = _normal(a[0], (s.d, s.h * s.hd), s.d, s.dtype)
        p["wk"] = _normal(a[1], (s.d, s.kv * s.hd), s.d, s.dtype)
        p["wv"] = _normal(a[2], (s.d, s.kv * s.hd), s.d, s.dtype)
        p["wo"] = _normal(a[3], (s.h * s.hd, s.d), s.h * s.hd, s.dtype)
    else:
        p["w"] = _normal(k1, (s.d, s.d), s.d, s.dtype)
        p["b"] = jnp.zeros((s.d,), jnp.float32)
    m = jax.random.split(k2, 3)
    p["w_gate"] = _normal(m[0], (s.d, s.ff), s.d, s.dtype)
    p["w_up"] = _normal(m[1], (s.d, s.ff), s.d, s.dtype)
    p["w_down"] = _normal(m[2], (s.ff, s.d), s.ff, s.dtype)
    return p


def layer_keys(s: Shapes, key) -> tuple:
    """(embedding key, head key, [per-layer key])."""
    gs = groups(s.kinds)
    keys = jax.random.split(key, len(gs) + 2)
    per_layer = []
    for gi, (unit, rep) in enumerate(gs):
        gkeys = jax.random.split(keys[2 + gi], len(unit))
        lks = [jax.random.split(gkeys[u], rep) for u in range(len(unit))]
        for r in range(rep):
            for u in range(len(unit)):
                per_layer.append(lks[u][r])
    return keys[0], keys[1], per_layer


# ----------------------------------------------------------- numerics --

def _round(x, lower, axis):
    """Round to the ``lower`` dtype; float8 with one scale per slice along
    ``axis``."""
    if lower == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, lower):
    if lower:
        x, w = _round(x, lower, -1), _round(w, lower, None)
    return x @ w


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (H, S, hd): rotate-half pairing of the two halves."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(s: Shapes, p, h, control):
    """One sequence: h (S, d) -> (S, d)."""
    n = h.shape[0]
    q = _mm(h, p["wq"], control).reshape(n, s.h, s.hd).transpose(1, 0, 2)
    k = _mm(h, p["wk"], control).reshape(n, s.kv, s.hd).transpose(1, 0, 2)
    v = _mm(h, p["wv"], control).reshape(n, s.kv, s.hd).transpose(1, 0, 2)
    q, k = _rope(q, s.theta), _rope(k, s.theta)
    rep = s.h // s.kv
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    sc = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(s.hd)
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    mask = j <= i
    if s.window:
        mask &= (i - j) < s.window
    sc = jnp.where(mask[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, axis=-1), v)
    return _mm(o.transpose(1, 0, 2).reshape(n, s.h * s.hd), p["wo"], control)


def _layer(s: Shapes, kind: str, control, key, x):
    """Draw one layer's weights and run it on x (B, S, d)."""
    p = layer_weights(s, kind, key)
    if kind == "attn":
        h = _rms(x, s.eps)
        x = x + jax.lax.map(lambda hb: _attention(s, p, hb, control), h)
    else:
        x = x + _mm(x, p["w"], control) + p["b"]
    h = _rms(x, s.eps)
    g = _mm(h, p["w_gate"], control)
    u = _mm(h, p["w_up"], control)
    return x + _mm(jax.nn.silu(g) * u, p["w_down"], control)


def final_hidden(cfg: dict, key_seed: int, tokens: np.ndarray,
                 control: bool = False):
    """Final-normed hidden states (B, S, d) of padded token rows;
    ``control`` computes them one precision step down."""
    s = Shapes(cfg)
    control = LOWER[cfg["torch_dtype"]] if control else None
    k_emb, _, per_layer = layer_keys(s, jax.random.PRNGKey(key_seed))
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda k: _normal(k, (s.v, s.d), s.d, s.dtype))(
            k_emb)
        x = embed[jnp.asarray(tokens)]
        del embed
        fns = {kind: jax.jit(lambda k, x, kind=kind:
                             _layer(s, kind, control, k, x))
               for kind in set(s.kinds)}
        for kind, k in zip(s.kinds, per_layer):
            x = fns[kind](k, x)
        return jax.jit(lambda x: _rms(x, s.eps))(x)


def head_matrix(cfg: dict, key_seed: int):
    s = Shapes(cfg)
    k_emb, k_head, _ = layer_keys(s, jax.random.PRNGKey(key_seed))
    if s.tied:
        return jax.jit(lambda k: _normal(k, (s.v, s.d), s.d, s.dtype).T)(
            k_emb)
    return jax.jit(lambda k: _normal(k, (s.d, s.v), s.d, s.dtype))(k_head)


def served_positions(seqs: list, pad_to: int) -> tuple:
    """Pack (prompt, served) pairs into padded rows; the logits that chose
    served token j sit at row position ``len(prompt) - 1 + j``."""
    rows = np.zeros((len(seqs), pad_to), np.int32)
    pos = []
    for b, (prompt, served) in enumerate(seqs):
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        assert len(full) <= pad_to, (len(full), pad_to)
        rows[b, :len(full)] = full
        pos.append(np.arange(len(prompt) - 1, len(full)))
    return rows, pos


def logit_gaps(cfg: dict, key_seed: int, seqs: list, pad_to: int,
               control: bool = False) -> list:
    """Per request: the float32 reference's best logit minus its logit of
    each served token (``control=False``), or of the token the control
    ranks first at that position (``control=True``)."""
    rows, pos = served_positions(seqs, pad_to)
    hid = final_hidden(cfg, key_seed, rows)
    hid8 = final_hidden(cfg, key_seed, rows, control=True) if control \
        else None
    head = head_matrix(cfg, key_seed)

    @jax.jit
    def gaps(h, w, tok):
        with jax.default_matmul_precision("highest"):
            lg = h @ w
        return lg.max(-1) - jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]

    lower = LOWER[cfg["torch_dtype"]]

    @jax.jit
    def ctrl_tokens(h, w):
        return jnp.argmax(_mm(h, w, lower), axis=-1)

    out = []
    for b, (_, served) in enumerate(seqs):
        if control:
            tok = ctrl_tokens(hid8[b], head)
        else:
            tok = np.zeros(pad_to, np.int32)
            tok[pos[b]] = served
        g = np.asarray(gaps(hid[b], head, jnp.asarray(tok)), np.float64)
        out.append(g[pos[b]])
    return out
