"""Mixture-of-Experts FFN: top-k routing, capacity-based dispatch, shared
experts (DeepSeek-MoE style), expert parallelism over the "model" mesh axis.

Dispatch uses scatter/gather (sort-free): for each (token, slot) we compute
the expert id and the token's position within that expert's capacity buffer
via a cumulative-sum over a one-hot routing matrix; tokens beyond capacity are
dropped (weights renormalized over surviving slots at combine). With tokens
sharded over (pod, data) and the (E, C, d) buffer sharded over "model", the
scatter/gather lower to the MoE all-to-all pattern.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.models.layers import init_mlp, mlp


def init_moe(key: jax.Array, cfg) -> dict:
    m = cfg.moe
    d, ff, e = cfg.d_model, m.d_expert, m.n_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.param_dtype)
    s_in, s_out = d ** -0.5, ff ** -0.5

    def expert_w(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(dt)

    p = {
        "router": (jax.random.normal(k1, (d, e)) * s_in).astype(jnp.float32),
        "w_gate": expert_w(k2, (e, d, ff), s_in),
        "w_up":   expert_w(k3, (e, d, ff), s_in),
        "w_down": expert_w(k4, (e, ff, d), s_out),
    }
    if m.n_shared:
        p["shared"] = init_mlp(k5, d, m.n_shared * ff, dt)
    return p


def _capacity(n_tokens: int, cfg) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts) + 1
    return max(c, m.top_k)


def moe_ffn(cfg, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss). Dispatches to the explicit
    expert-parallel shard_map path when the mesh has a divisible "model"
    axis (the pjit scatter/gather formulation makes XLA materialize and
    all-reduce the full dispatch buffer in the gather backward — 27× the
    necessary combine traffic on deepseek-moe; EXPERIMENTS.md §Perf H2)."""
    from repro.distributed.api import axis_size, dp_axes, has_axis
    n_dp = 1
    for a in dp_axes():
        n_dp *= axis_size(a)
    if has_axis("model") and cfg.moe.n_experts % axis_size("model") == 0 \
            and axis_size("model") > 1 and x.shape[0] % n_dp == 0:
        return _moe_ffn_ep(cfg, p, x)
    return _moe_ffn_dense(cfg, p, x)


def _moe_ffn_dense(cfg, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Single-device / fallback path (pjit-auto sharded)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    cap = _capacity(t, cfg)

    logits = xt.astype(jnp.float32) @ p["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)              # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch): E * mean(f_e * p_e)
    sel = jax.nn.one_hot(idx[:, 0], m.n_experts, dtype=jnp.float32)
    frac = sel.mean(0)
    aux = m.router_aux_weight * m.n_experts * jnp.sum(frac * probs.mean(0))

    # slot-major flattening: slot 0 of every token gets capacity priority
    flat_e = idx.T.reshape(-1)                             # (kT,)
    oh = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
    pos_in_e = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1   # (kT,)
    keep = pos_in_e < cap
    pos_safe = jnp.where(keep, pos_in_e, cap)              # OOB -> dropped

    xk = jnp.tile(xt, (m.top_k, 1))                        # (kT, d)
    buf = jnp.zeros((m.n_experts, cap + 1, d), xt.dtype)
    buf = buf.at[flat_e, pos_safe].add(xk, mode="drop")
    buf = constrain(buf[:, :cap], "model", None, None)     # EP

    # expert FFN (einsum over stacked experts)
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(g) * u
    h = constrain(h, "model", None, None)
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    y_buf = jnp.pad(y_buf, ((0, 0), (0, 1), (0, 0)))       # slot `cap` = zeros

    yk = y_buf[flat_e, pos_safe]                           # (kT, d)
    yk = jnp.where(keep[:, None], yk, 0)
    gate_k = gate.T.reshape(-1)[:, None].astype(yk.dtype)
    y = (yk * gate_k).reshape(m.top_k, t, d).sum(0)

    if m.n_shared:
        y = y + mlp(p["shared"], xt, cfg.mlp_act)
    return y.reshape(b, s, d), aux


def _moe_ffn_ep(cfg, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Explicit expert parallelism over the "model" axis via shard_map.

    Activations arrive model-replicated (the attention output all-reduce
    already paid for that), so *dispatch is entirely local*: each model
    shard scatters only the tokens routed to its own E/M experts into an
    (E/M, cap, d) buffer, runs its experts, and the combine is ONE psum of
    the (T, d) partial outputs — bytes = tokens·d per layer instead of the
    full dispatch buffer. DP axes stay auto (FSDP/ZeRO untouched).
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.api import axis_size, dp_axes

    m = cfg.moe
    b, s, d = x.shape
    n_model = axis_size("model")
    e_per = m.n_experts // n_model
    px = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    dp = dp_axes()
    n_dp = 1
    for a in dp:
        n_dp *= axis_size(a)
    # fully-manual region (partial-auto shard_map inside scan+grad trips a
    # JAX sharding-roundtrip bug); FSDP weight shards are gathered
    # explicitly, which reverse-differentiates into the reduce-scatter of
    # ZeRO-3 — exactly the production schedule.
    fsdp_axis = 1 if (d % n_dp == 0 and dp) else None

    def shard_fn(pxl, xl):
        mi = jax.lax.axis_index("model")
        if fsdp_axis is not None and dp:
            pxl = dict(pxl,
                       w_gate=jax.lax.all_gather(pxl["w_gate"], dp, axis=1,
                                                 tiled=True),
                       w_up=jax.lax.all_gather(pxl["w_up"], dp, axis=1,
                                               tiled=True),
                       w_down=jax.lax.all_gather(pxl["w_down"], dp, axis=2,
                                                 tiled=True))
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        cap = _capacity(t, cfg)

        logits = xt.astype(jnp.float32) @ pxl["router"]      # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, m.top_k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        sel = jax.nn.one_hot(idx[:, 0], m.n_experts, dtype=jnp.float32)
        aux = m.router_aux_weight * m.n_experts * jnp.sum(
            sel.mean(0) * probs.mean(0))

        flat_e = idx.T.reshape(-1)                           # (kT,) global
        oh = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
        pos_in_e = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1
        local_e = flat_e - mi * e_per
        keep = (local_e >= 0) & (local_e < e_per) & (pos_in_e < cap)
        le = jnp.where(keep, local_e, 0)
        pos = jnp.where(keep, pos_in_e, cap)

        xk = jnp.tile(xt, (m.top_k, 1))
        xk = jnp.where(keep[:, None], xk, 0)
        buf = jnp.zeros((e_per, cap + 1, d), xt.dtype)
        buf = buf.at[le, pos].add(xk, mode="drop")[:, :cap]

        g = jnp.einsum("ecd,edf->ecf", buf, pxl["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, pxl["w_up"])
        h = jax.nn.silu(g) * u
        y_buf = jnp.einsum("ecf,efd->ecd", h, pxl["w_down"])
        y_buf = jnp.pad(y_buf, ((0, 0), (0, 1), (0, 0)))

        yk = y_buf[le, pos]
        yk = jnp.where(keep[:, None], yk, 0)
        gate_k = gate.T.reshape(-1)[:, None].astype(yk.dtype)
        y = (yk * gate_k).reshape(m.top_k, t, d).sum(0)
        y = jax.lax.psum(y, "model")                        # the combine
        aux = jax.lax.pmean(aux, dp) if dp else aux
        return y.reshape(bl, sl, d), aux

    dps = dp if len(dp) != 1 else dp[0]
    w_in = (P("model", dps, None) if fsdp_axis is not None
            else P("model", None, None))
    w_down_in = (P("model", None, dps) if fsdp_axis is not None
                 else P("model", None, None))
    pspecs = {"router": P(), "w_gate": w_in, "w_up": w_in,
              "w_down": w_down_in}
    x_in = P(dps, None, None) if dp else P(None, None, None)
    y, aux = jax.shard_map(
        shard_fn, in_specs=(pspecs, x_in), out_specs=(x_in, P()),
        axis_names=set(dp) | {"model"}, check_vma=False)(px, x)
    if m.n_shared:
        y = y + mlp(p["shared"], x.reshape(-1, d), cfg.mlp_act
                    ).reshape(b, s, d)
    return y, aux
