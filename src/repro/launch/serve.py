"""Serving drivers — thin compatibility layer over the engine.

Production path: ``launch/engine.py`` (continuous batching over a slot
cache, scheduler-driven admission). This module keeps two entry points:

  serve_requests   convenience wrapper: prompts in, tokens out, running the
                   continuous-batching engine under the hood.
  generate         the original fixed-batch, fixed-length decode loop. Kept
                   as the *reference* implementation: the engine parity test
                   asserts per-request engine output == generate output.

NBL-linearized layers carry no KV cache, so a compressed model's serve
state is (K−m)/K of the baseline's — the engine's scheduler converts that
saving into extra concurrent slots (launch/scheduler.nbl_slot_budget).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import cache_specs, param_specs
from repro.jitcache import shared_jit
from repro.launch.engine import Engine
from repro.launch.specs import cache_shapes, param_shapes
from repro.models import decode_step, prefill


def serve_requests(cfg: ModelConfig, params, prompts: Sequence, *,
                   max_new: int, max_len: Optional[int] = None,
                   n_slots: Optional[int] = None,
                   cache_budget_bytes: Optional[int] = None,
                   eos_id: Optional[int] = None,
                   temperature: float = 0.0, seed: int = 0):
    """Serve a batch of (possibly ragged) prompts through the engine.

    Returns (list of per-request token arrays in submission order, stats).
    """
    prompts = [jnp.asarray(p).reshape(-1) for p in prompts]
    if not prompts:
        raise ValueError("serve_requests needs at least one prompt")
    if max_len is None:
        max_len = max(int(p.shape[0]) for p in prompts) + max_new
    if n_slots is None and cache_budget_bytes is None:
        n_slots = min(len(prompts), 8)
    eng = Engine(cfg, params, max_len=max_len, n_slots=n_slots,
                 cache_budget_bytes=cache_budget_bytes, eos_id=eos_id,
                 temperature=temperature, seed=seed)
    # strict: this batch wrapper has no per-request error channel, so an
    # unservable prompt must raise rather than silently come back as an
    # empty token array (Engine.submit's default records-and-returns for
    # the async serving frontend)
    rids = [eng.submit(p, max_new, strict=True) for p in prompts]
    out = eng.run()
    return [out[r] for r in rids], eng.stats()


def make_serve_fns(cfg: ModelConfig, *, batch: int, prompt_len: int,
                   max_new: int, donate: bool = True):
    """Returns (prefill_jit, decode_jit) for the fixed-batch path. Call
    under the serving mesh. (The engine builds its own sharded fns.)"""
    cache_len = prompt_len + max_new
    pspecs = param_specs(param_shapes(cfg))
    cspecs = cache_specs(cache_shapes(cfg, batch, cache_len))

    def _prefill(params, tokens, enc=None):
        return prefill(cfg, params, tokens, enc=enc, cache_len=cache_len)

    def _decode(params, token, cache, pos):
        return decode_step(cfg, params, token, cache, pos)

    enc_spec = (P("data", None, None),) if cfg.family == "vlm" else ()
    prefill_jit = jax.jit(  # nbl: disable=jit-discipline -- sharded: shardings captured from the caller's mesh, per-mesh by design
        _prefill,
        in_shardings=(pspecs, P("data", None)) + enc_spec,
        out_shardings=(None, cspecs))
    decode_jit = jax.jit(  # nbl: disable=jit-discipline -- sharded: shardings captured from the caller's mesh, per-mesh by design
        _decode,
        in_shardings=(pspecs, P("data", None), cspecs, P()),
        out_shardings=(None, cspecs),
        donate_argnums=(2,) if donate else ())
    return prefill_jit, decode_jit


def generate(cfg: ModelConfig, params, tokens, *, max_new: int,
             enc=None, greedy: bool = True, seed: int = 0,
             use_jit_fns: Optional[tuple] = None):
    """Fixed-batch generation (reference loop; all sequences share one
    position). tokens: (B, S) int32 prompt. Returns (B, max_new)."""
    b, s = tokens.shape
    if use_jit_fns is not None:
        prefill_fn, decode_fn = use_jit_fns
    else:
        # shared across calls: generate() is the parity REFERENCE the tests
        # and the fuzz harness call by the hundred — fresh per-call lambdas
        # here used to retrace the whole model every single time
        cache_len = s + max_new
        prefill_fn = shared_jit(
            ("serve.generate_prefill", cfg, cache_len),
            lambda: jax.jit(lambda p, t, e=None: prefill(
                cfg, p, t, enc=e, cache_len=cache_len)))
        decode_fn = shared_jit(
            ("serve.generate_decode", cfg),
            lambda: jax.jit(lambda p, t, c, i: decode_step(cfg, p, t, c, i)))

    args = (params, tokens) + ((enc,) if enc is not None else ())
    logits, cache = prefill_fn(*args)
    key = jax.random.PRNGKey(seed)
    out = []
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    for i in range(max_new):
        out.append(tok)
        logits, cache = decode_fn(params, tok, cache, jnp.int32(s + i))
        if greedy:
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits[:, -1])[:, None]
            tok = tok.astype(jnp.int32)
    return jnp.concatenate(out, axis=1)
