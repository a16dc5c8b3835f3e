#!/usr/bin/env python3
"""Run compress-and-serve once on a TPU at the published widths of a model.

    python3 chip_smoke.py                 # one chip
    python3 chip_smoke.py --four-chips    # sharded serving on a (1, 4) mesh

One chip: ``mistral-7b`` at its published widths (d_model 4096, 32 query
and 8 KV heads of 128, d_ff 14336, vocab 32000, window 4096) cut to 8 of
its 32 layers, with seeded random bf16 weights. In one process:

1. device check: refuse (exit 1) unless JAX's first device is a TPU;
2. kernels: the compiled Mosaic paged-attention kernel, decode and
   mixed-row routes, against their XLA references at Mistral widths;
3. compress: calibrate, select m=3 layers by the CCA bound, substitute the
   LMMSE linear maps (``nbl_compress``);
4. serve: the base model, then the NBL-3 model, each behind the TCP front
   end (``launch/server.NBLServer``) on a thread over the paged engine,
   driven by the stdlib client in ``examples/stream_client.py``.

``--four-chips`` runs only the sharded path: the full 32-layer model served
by the paged engine under a ("data", "model") = (1, 4) mesh, compared with
``serve.generate`` on the same mesh.

Every phase raises on failure, so the script then exits non-zero. The last
line of stdout, printed only when every phase passed, is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# kernel parity: bf16 inputs are exact in the f32 math of both routes; the
# outputs differ by the final bf16 rounding of each (half an ulp, 2**-9
# relative: <= 0.009 at |o| <= 4.5, the largest |v| a one-key slot returns)
# and by the MXU's f32 matmul passes. 2e-2 covers both; a wrong page, mask
# or scale moves outputs by O(|v|) ~ 1.
KERNEL_ATOL = 2e-2
# sharded prefill logits, paged engine vs generate(): the same bf16 model
# over differently padded prompts (bucketed vs exact), so the two differ
# only by reduction order; bf16 rounding (2**-8 relative per op) accumulated
# over 32 residual layers stays within a few percent of the logit scale,
# while a misplaced shard or mask changes logits by O(100%).
LOGITS_RTOL = 5e-2

CUT_LAYERS = 8                  # of mistral-7b's 32
NBL_M = 3                       # 3 of 8: the paper's 12-of-32 ratio
# calibration tokens: 8 batches of 4 x 512 = 16384, four times d_model, so
# the d x d covariances are full rank
CALIB = dict(batch=4, seq=512, n_batches=8)
ENGINE = dict(paged=True, page_size=64, step_tokens=128, n_slots=8,
              max_len=1024)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _load_client():
    """examples/stream_client.Client — the stdlib-only client a remote
    caller would use; it is a script, not a package module."""
    path = os.path.join(ROOT, "examples", "stream_client.py")
    spec = importlib.util.spec_from_file_location("stream_client", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.Client


def _prompts(vocab: int, lens, seed: int):
    from repro.data.synthetic import ZipfMarkov
    proc = ZipfMarkov(vocab, seed=seed)
    return [proc.sample(1, n, seed + i)[0] for i, n in enumerate(lens)]


def _describe(cfg, params) -> str:
    import jax
    n = sum(x.size for x in jax.tree.leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    win = {b.window for b in cfg.blocks() if b.kind == "attn"}
    return (f"{cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads} "
            f"(kv {cfg.n_kv_heads}) x {cfg.head_dim}, d_ff {cfg.d_ff}, "
            f"vocab {cfg.vocab_size}, window {sorted(win, key=str)}, "
            f"{cfg.n_blocks} layers, {n / 1e9:.2f} B params, "
            f"{nbytes / 1e9:.2f} GB {cfg.param_dtype}")


def init_model(cfg, seed: int, out_shardings=None):
    """Seeded random weights, built on the device (sharded when given
    ``out_shardings``) by one jit."""
    import jax
    from repro.models import init_params
    kw = {} if out_shardings is None else dict(out_shardings=out_shardings)
    init = jax.jit(lambda k: init_params(k, cfg), **kw)  # nbl: disable=jit-discipline -- one call per process
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


# ------------------------------------------------------------- phases ------

def check_kernels(seed: int) -> None:
    """Compiled Mosaic kernel vs the XLA reference, decode and mixed-row
    routes, at Mistral widths in bf16: 8 slots of up to 1024 tokens in
    pages of 64, 8 KV heads, 4 query heads per KV head, head_dim 128."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import paged_attention as pa

    b, kv, rep, hd, ps, npg, w = 8, 8, 4, 128, 64, 16, 16
    rng = np.random.default_rng(seed)
    bf = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)  # noqa: E731
    kp, vp = bf(b * npg, kv, ps, hd), bf(b * npg, kv, ps, hd)
    lens = np.array([1, 64, 65, 300, 511, 777, 1024, 0], np.int32)
    tbl = rng.permutation(b * npg).reshape(b, npg).astype(np.int32)
    for i, n in enumerate(lens):
        tbl[i, -(-int(n) // ps):] = -1          # unallocated past the length
    tbl_j = jnp.asarray(tbl)

    q = bf(b, kv, rep, hd)
    kw = dict(window=4096)
    got = jax.jit(lambda *a: pa.paged_attention(*a, **kw))(
        q, kp, vp, tbl_j, jnp.asarray(lens))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: pa.paged_decode_xla(*a, **kw))(
            q, kp, vp, tbl_j, jnp.asarray(lens))
    live = (lens > 0)[:, None, None, None]
    err = float(np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(want, np.float32)) * live))
    log(f"kernel paged_attention vs paged_decode_xla: max abs err {err:.3e} "
        f"(tol {KERNEL_ATOL})")
    check(np.isfinite(np.asarray(got, np.float32)).all(), "non-finite output")
    check(err <= KERNEL_ATOL, f"kernel error {err} over {KERNEL_ATOL}")

    # mixed rows: decode rows (len 1), full and short chunk rows, inactive
    # rows; a 256-token window so the window mask is exercised
    qm = bf(b, kv, rep, w, hd)
    row_len = np.array([1, w, 5, w, 1, 0, w, 0], np.int32)
    row_pos = np.maximum(lens - row_len, 0).astype(np.int32)
    kw = dict(window=256)
    args = (qm, kp, vp, tbl_j, jnp.asarray(row_pos), jnp.asarray(row_len))
    got = jax.jit(lambda *a: pa.paged_mixed(*a, use_kernel=True, **kw))(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda *a: pa.paged_mixed_xla(*a, **kw))(*args)
    valid = (np.arange(w)[None, :] < row_len[:, None])[:, None, None, :, None]
    err = float(np.max(np.abs(np.asarray(got, np.float32)
                              - np.asarray(want, np.float32)) * valid))
    log(f"kernel paged_mixed(use_kernel=True) vs paged_mixed_xla (W={w}): "
        f"max abs err {err:.3e} (tol {KERNEL_ATOL})")
    check(np.isfinite(np.asarray(got, np.float32)).all(), "non-finite output")
    check(err <= KERNEL_ATOL, f"kernel error {err} over {KERNEL_ATOL}")


def compress(cfg, params, seed: int, m: int):
    """calibrate every attention layer -> select m by the CCA bound ->
    substitute their LMMSE linear maps."""
    from repro.core.api import nbl_compress
    from repro.core.calibrate import calibrate
    from repro.data import calib_factory

    fac = calib_factory(cfg, seed=seed, **CALIB)
    t0 = time.monotonic()
    calib = calibrate(cfg, params, fac)
    t1 = time.monotonic()
    ncfg, nparams, report = nbl_compress(cfg, params, fac, m, calib=calib)
    t2 = time.monotonic()
    n_tok = CALIB["batch"] * CALIB["seq"] * CALIB["n_batches"]
    log(f"calibrate: {t1 - t0:.1f} s over layers {sorted(calib)} ({n_tok} "
        f"tokens; device moments, then host float64 CCA/LMMSE per layer)")
    log(f"select + substitute: {t2 - t1:.1f} s")
    log(report.summary())
    check(len(report.layers) == m
          and sum(b.kind == "nbl" for b in ncfg.blocks()) == m,
          f"expected {m} linearized layers, got {report.layers}")
    return ncfg, nparams


def serve(name: str, cfg, params, prompts, max_new: int, *,
          check_kernel: bool = True) -> dict:
    """Serve ``prompts`` over the TCP front end (server thread + engine
    step thread, one process); returns {rid: tokens} in submission order.
    Raises unless every request finishes with ``max_new`` tokens and the
    page pool ends empty."""
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.engine import AsyncEngine, Engine
    from repro.launch.server import NBLServer

    Client = _load_client()
    t0 = time.monotonic()
    eng = Engine(cfg, params, **ENGINE)
    srv = NBLServer(AsyncEngine(eng))
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    cli = Client(port=srv.port, timeout=900)
    try:
        rids = [cli.submit(p, max_new, stream=False) for p in prompts]
        done: dict = {}
        for ev in cli.events():
            if ev.get("event") == "done":
                done[ev["rid"]] = ev
                if len(done) == len(rids):
                    break
        stats = cli.stats()
    finally:
        cli.close()
        srv.shutdown(drain=True)
        th.join()
    secs = time.monotonic() - t0
    for rid in rids:
        ev = done[rid]
        check(ev["status"] == "finished"
              and len(ev["tokens"]) == max_new, f"request {rid}: {ev}")
    eng.allocator.check_invariants()
    leaked = eng.allocator.in_use
    log(f"serve {name}: {len(rids)} requests finished, prompts "
        f"{[len(p) for p in prompts]}, max_new {max_new}, {secs:.1f} s "
        f"(compiles included); {stats['n_fused_dispatches']} fused steps, "
        f"{stats['n_prefills']} prefills, {leaked} leaked pages")
    check(leaked == 0 and stats["pages_in_use"] == 0,
          f"{leaked} pages leaked")

    if check_kernel:
        # the engine's own decode-step jit, lowered again at its live
        # shapes: the Mosaic kernel must be in it, not the XLA route
        fn = eng._fused_fn(1)
        z = jnp.zeros((eng.n_slots,), jnp.int32)
        hlo = fn.lower(eng.params, jnp.zeros((eng.n_slots, 1), jnp.int32),
                       eng.cache, z, z, jnp.asarray(eng.page_tbl)
                       ).compile().as_text()
        n_calls = hlo.count("tpu_custom_call")
        log(f"serve {name}: fused step HLO holds {n_calls} tpu_custom_call")
        check(n_calls > 0, "no Mosaic kernel in the fused step")
    return {rid: np.asarray(done[rid]["tokens"]) for rid in rids}


def run_one_chip(cfg, seed: int) -> None:
    t = time.monotonic()
    check_kernels(seed)
    log(f"phase kernels: {time.monotonic() - t:.1f} s")

    t = time.monotonic()
    params = init_model(cfg, seed)
    log(f"model: {_describe(cfg, params)} (seed {seed}); "
        f"init {time.monotonic() - t:.1f} s")

    t = time.monotonic()
    ncfg, nparams = compress(cfg, params, seed, NBL_M)
    log(f"phase compress: {time.monotonic() - t:.1f} s")

    prompts = _prompts(cfg.vocab_size, (100, 150, 200, 250, 300, 400), seed)
    for name, c, p in (("base", cfg, params), (f"NBL-{NBL_M}", ncfg, nparams)):
        t = time.monotonic()
        serve(name, c, p, prompts, max_new=32)
        log(f"phase serve {name}: {time.monotonic() - t:.1f} s")


def run_four_chips(cfg, seed: int) -> None:
    """The full model, sharded over 4 chips: paged engine vs generate()."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.sharding import param_specs
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import generate, make_serve_fns
    from repro.launch.specs import param_shapes

    max_new = 16
    prompts = _prompts(cfg.vocab_size, (200, 160, 240), seed)
    with jax.set_mesh(make_mesh((1, 4), ("data", "model"))):
        t = time.monotonic()
        params = init_model(cfg, seed, param_specs(param_shapes(cfg)))
        log(f"model: {_describe(cfg, params)} (seed {seed}), sharded "
            f"(data, model) = (1, 4); init {time.monotonic() - t:.1f} s")

        t = time.monotonic()
        p0 = jnp.asarray(prompts[0])[None]
        fns = make_serve_fns(cfg, batch=1, prompt_len=p0.shape[1],
                             max_new=max_new)
        ref_logits = np.asarray(fns[0](params, p0)[0][0, -1], np.float32)
        ref_toks = np.asarray(generate(cfg, params, p0, max_new=max_new,
                                       use_jit_fns=fns))[0]
        # the reference's top-2 logit gap at each of its steps (teacher-
        # forced on its own tokens): a served token may flip where the gap
        # is inside the bf16 noise the prefill comparison below measures
        logits, cache = fns[0](params, p0)
        gaps = []
        for i in range(max_new):
            top2 = np.sort(np.asarray(logits[0, -1], np.float32))[-2:]
            gaps.append(float(top2[1] - top2[0]))
            tok = jnp.asarray(ref_toks[i:i + 1])[None]
            logits, cache = fns[1](params, tok, cache,
                                   jnp.int32(p0.shape[1] + i))
        del logits, cache
        log(f"reference generate(): {time.monotonic() - t:.1f} s")

        # the engine's own admission prefill jit for prompt 0 (bucketed,
        # paged cache layout), compared with generate()'s prefill
        eng = Engine(cfg, params, **ENGINE)
        plen = p0.shape[1]
        token_len, cache_len, masked = eng._prefill_plan(plen)
        toks = np.zeros((1, token_len), np.int32)
        toks[0, :plen] = prompts[0]
        logits, _ = eng._prefill_fn(token_len, cache_len, masked, False)(
            eng.params, jnp.asarray(toks), jnp.int32(plen))
        got_logits = np.asarray(logits[0, -1], np.float32)
        scale = float(np.max(np.abs(ref_logits)))
        err = float(np.max(np.abs(got_logits - ref_logits)))
        log(f"prefill logits, engine vs generate(): max abs diff {err:.4f}, "
            f"{err / scale:.4f} of max |logit| {scale:.3f} "
            f"(tol {LOGITS_RTOL}); argmax {int(got_logits.argmax())} vs "
            f"{int(ref_logits.argmax())}")
        check(np.isfinite(got_logits).all(), "non-finite logits")
        check(err <= LOGITS_RTOL * scale,
              f"logits differ by {err}, over {LOGITS_RTOL} x {scale}")
        del eng, logits

        t = time.monotonic()
        out = serve("sharded mistral-7b", cfg, params, prompts, max_new)
        log(f"phase serve sharded: {time.monotonic() - t:.1f} s")
    got = next(iter(out.values()))
    agree = int(np.sum(got == ref_toks))
    first_diff = next((i for i in range(max_new) if got[i] != ref_toks[i]),
                      None)
    log(f"token agreement, served prompt 0 vs generate(): {agree}/{max_new}"
        f" (first difference at step {first_diff}); generate()'s top-2 logit"
        f" gaps per step: {' '.join(f'{g:.3f}' for g in gaps)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded path on a (1, 4) mesh, against "
                         "generate() on the same mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}: run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.jitcache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: {dev['kind']} x{dev['count']} ({dev['platform']}); "
        f"compile cache {cache_dir}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev['platform']}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if dev["count"] < need:
        print(f"chip_smoke: needs {need} chips, found {dev['count']}",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.configs.base import dense_stack
    t0 = time.monotonic()
    if args.four_chips:
        run_four_chips(get_config("mistral-7b"), args.seed)
    else:
        log(f"cut: mistral-7b depth {CUT_LAYERS} of 32 layers, published "
            f"widths kept, seeded random weights")
        run_one_chip(get_config("mistral-7b",
                                stack=dense_stack(CUT_LAYERS, window=4096)),
                     args.seed)
    log(f"total: {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
