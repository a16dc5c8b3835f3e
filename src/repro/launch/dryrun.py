import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell on
512 placeholder host devices, record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out experiments/dryrun

Each cell produces a JSON blob with: compile ok/fail, per-device bytes from
compiled.memory_analysis(), FLOPs/bytes from cost_analysis(), the parsed
collective schedule, and the three roofline terms (§Roofline). ``--nbl m``
dry-runs the NBL-compressed variant (layers chosen deepest-first, the
paper's observed selection pattern) — the KV-cache saving shows up directly
in argument bytes.
"""  # noqa: E402

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import (  # noqa: E402
    ASSIGNED_ARCHS, SHAPES, get_config, shape_applicable,
)
from repro.core.surgery import nbl_variant  # noqa: E402
from repro.distributed.api import shaped_spec  # noqa: E402
from repro.distributed.sharding import (  # noqa: E402
    batch_specs, cache_specs, param_specs,
)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs, param_shapes  # noqa: E402
from repro.models import decode_step, fused_step, loss_fn, prefill  # noqa: E402
from repro.optim import adamw_init, adamw_update, get_schedule  # noqa: E402
from repro.roofline.analysis import summarize  # noqa: E402


def build_target(cfg, shape):
    """Returns (fn, args_shapes, in_shardings, n_tokens, backward)."""
    ins = input_specs(cfg, shape)
    pshapes = param_shapes(cfg)
    pspecs = param_specs(pshapes)
    sched = get_schedule("cosine", 3e-4, 100, 10_000)

    if shape.kind == "train":
        oshapes = jax.eval_shape(lambda: adamw_init(pshapes))
        ospecs = {"mu": pspecs, "nu": pspecs, "count": P()}

        def train_step(params, opt, batch, step):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, batch, remat=True),
                has_aux=True)(params)
            params, opt, om = adamw_update(grads, opt, params,
                                           lr=sched(step))
            return params, opt, dict(metrics, **om)

        args = (pshapes, oshapes, ins["batch"],
                jax.ShapeDtypeStruct((), np.int32))
        shardings = (pspecs, ospecs, batch_specs(ins["batch"]), P())
        ntok = shape.global_batch * shape.seq_len
        return train_step, args, shardings, ntok, True

    if shape.kind == "prefill":
        def prefill_step(params, tokens, enc=None):
            return prefill(cfg, params, tokens, enc=enc,
                           cache_len=shape.seq_len)
        args = (pshapes, ins["tokens"])
        shardings = (pspecs, shaped_spec(ins["tokens"].shape, "dp", None))
        if "enc" in ins:
            args += (ins["enc"],)
            shardings += (shaped_spec(ins["enc"].shape, "dp", None, None),)
        ntok = shape.global_batch * shape.seq_len
        return prefill_step, args, shardings, ntok, False

    if shape.kind in ("prefill_shared", "prefill_chunked", "spec_verify"):
        # partial prefill: suffix/chunk tokens at absolute positions past
        # pooled prefix pages — a shared prompt prefix (engine _admit), the
        # request's own earlier chunks (engine _chunk_step), or the
        # speculative verifier's candidate block (engine _run_spec_verify,
        # which additionally reads the last γ+1 logits rows); the jit is
        # identical, only the prefix table's provenance differs
        n_logits = 9 if shape.kind == "spec_verify" else 1   # γ=8 verify

        def shared_prefill_step(params, tokens, cache, ptbl, plen):
            return prefill(cfg, params, tokens, cache_len=shape.seq_len,
                           paged=True, prefix_cache=cache, prefix_tbl=ptbl,
                           prefix_len=plen, n_logits=n_logits)
        args = (pshapes, ins["tokens"], ins["cache"], ins["prefix_tbl"],
                ins["prefix_len"])
        shardings = (pspecs, shaped_spec(ins["tokens"].shape, "dp", None),
                     cache_specs(ins["cache"]), P(), P())
        ntok = shape.global_batch * shape.seq_len
        return shared_prefill_step, args, shardings, ntok, False

    if shape.kind == "fused_step":
        # the fused engine dispatch: mixed decode + chunk rows through one
        # jit (engine _execute_fused) — row_len masks each row's valid span
        def fused(params, tokens, cache, row_pos, row_len, tbl):
            return fused_step(cfg, params, tokens, cache, row_pos,
                              row_len, tbl)
        args = (pshapes, ins["tokens"], ins["cache"], ins["row_pos"],
                ins["row_len"], ins["page_tbl"])
        shardings = (pspecs, shaped_spec(ins["tokens"].shape, "dp", None),
                     cache_specs(ins["cache"]),
                     shaped_spec(ins["row_pos"].shape, "dp"),
                     shaped_spec(ins["row_len"].shape, "dp"),
                     shaped_spec(ins["page_tbl"].shape, "dp", None))
        ntok = shape.global_batch * shape.seq_len
        return fused, args, shardings, ntok, False

    # decode/serve: one new token per sequence against a seq_len KV cache.
    # "serve" is the engine's batched slot-decode: pos is a per-slot (B,)
    # vector sharded with the slot dim; "decode" keeps the scalar pos;
    # "serve_paged" decodes against page pools via a per-slot page table.
    cspecs = cache_specs(ins["cache"])
    pos_spec = shaped_spec(ins["pos"].shape, "dp") if ins["pos"].ndim else P()
    if shape.kind == "serve_paged":
        def paged_step(params, token, cache, pos, tbl):
            return decode_step(cfg, params, token, cache, pos, page_tbl=tbl)
        args = (pshapes, ins["token"], ins["cache"], ins["pos"],
                ins["page_tbl"])
        shardings = (pspecs, shaped_spec(ins["token"].shape, "dp", None),
                     cspecs, pos_spec,
                     shaped_spec(ins["page_tbl"].shape, "dp", None))
        return paged_step, args, shardings, shape.global_batch, False

    def serve_step(params, token, cache, pos):
        return decode_step(cfg, params, token, cache, pos)
    args = (pshapes, ins["token"], ins["cache"], ins["pos"])
    shardings = (pspecs, shaped_spec(ins["token"].shape, "dp", None),
                 cspecs, pos_spec)
    return serve_step, args, shardings, shape.global_batch, False


def run_cell(arch: str, shape_name: str, multi_pod: bool, nbl_m: int = 0,
             donate: bool = True) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "nbl_m": nbl_m}
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    cfg = nbl_variant(cfg, nbl_m)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(tuple(mesh.shape.values())))
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            fn, args, shardings, ntok, backward = build_target(cfg, shape)
            donate_args = ()
            if donate and shape.kind == "train":
                donate_args = (0, 1)
            elif donate and shape.kind in ("decode", "serve", "serve_paged",
                                           "fused_step"):
                donate_args = (2,)
            lowered = jax.jit(fn, in_shardings=shardings,  # nbl: disable=jit-discipline -- AOT lower/compile cell: the jit exists to be lowered once and measured, never reused
                              donate_argnums=donate_args).lower(*args)
            compiled = lowered.compile()
            try:
                mem = compiled.memory_analysis()
                rec["memory"] = {
                    k: int(getattr(mem, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes",
                              "generated_code_size_in_bytes")
                    if hasattr(mem, k)}
            except Exception as e:      # CPU backend may not support it
                rec["memory"] = {"error": str(e)}
            cost = compiled.cost_analysis() or {}
            hlo = compiled.as_text()
            rec["roofline"] = summarize(
                hlo, chips, cfg=cfg, n_tokens=ntok, backward=backward,
                xla_cost=cost)
            rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["compile_s"] = round(time.time() - t0, 1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--nbl", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shp in shapes:
            for mp in meshes:
                rec = run_cell(arch, shp, mp, args.nbl)
                results.append(rec)
                tag = f"{arch:22s} {shp:12s} {rec['mesh']:8s}"
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"{tag} OK  t_c={r['t_compute']:.3e}s "
                          f"t_m={r['t_memory']:.3e}s "
                          f"t_x={r['t_collective']:.3e}s "
                          f"dom={r['dominant']} "
                          f"({rec['compile_s']}s compile)", flush=True)
                elif rec["status"] == "skipped":
                    print(f"{tag} SKIP ({rec['reason'][:60]})", flush=True)
                else:
                    print(f"{tag} FAIL {rec['error'][:120]}", flush=True)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        suffix = f"_nbl{args.nbl}" if args.nbl else ""
        path = os.path.join(
            args.out, f"dryrun_{args.arch}_{args.shape}_{args.mesh}{suffix}"
            .replace("/", "-") + ".json")
        with open(path, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote", path)
    n_fail = sum(r["status"] == "fail" for r in results)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
