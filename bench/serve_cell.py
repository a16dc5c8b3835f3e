"""Child process of a benchmark run: the only process that touches JAX.

Started by ``bench/run_cell.py`` with one JSON line on stdin (the
configuration, seed and run settings). It

1. refuses any platform but ``tpu``, fewer chips than the cell asks for,
   and a ``device_kind`` missing from ``bench/peaks.json`` (exit 3);
2. builds the configuration's compressed model on the device in one jit
   from the seed, then the paged ``Engine``, ``AsyncEngine`` and the TCP
   front end ``NBLServer``, with the engine settings of the config file.
   A cell on more than one chip builds the weights and the engine under a
   (data, model) = (1, chips) mesh, with the program's own parameter
   specs, so the engine serves tensor-parallel; on one chip no mesh is
   made;
3. warms the fused step and the prefill and page-assign programs of every
   prompt bucket the traffic reaches, with JAX's compile cache at
   ``.jax_cache/`` in the checkout;
4. answers commands, one JSON line each way: ``trace_start`` /
   ``trace_stop`` (the profiler, and the program's spans and counters
   recorded for that sub-window only), ``window`` (compiles are counted
   while it is open), and ``finish``: stop serving, read the peak device
   memory, free the program, run the plain reference over the sample of
   served requests and reply with the logit gaps.

Protocol lines go to the original stdout; everything else the process
prints goes to stderr.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from bench.lib.seeds import key_seed  # noqa: E402
from bench.lib.spec import load_module  # noqa: E402


class DeviceError(RuntimeError):
    pass


def device_info(chips: int, kinds) -> dict:
    """The device as JAX reports it; raises unless it is a TPU with at
    least ``chips`` chips of a kind in the peak table."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise DeviceError(f"needs a TPU, JAX found {dev['platform']}")
    if dev["count"] < chips:
        raise DeviceError(f"needs {chips} chips, found {dev['count']}")
    if dev["kind"] not in kinds:
        raise DeviceError(f"device kind {dev['kind']!r} is not in "
                          f"bench/peaks.json ({sorted(kinds)})")
    dev["count"] = chips
    return dev


def program_config(cfg: dict):
    """The program's ModelConfig for a config file, checked key by key
    against the sizes the file states."""
    from repro.configs import get_config
    from repro.configs.base import dense_stack
    from repro.core.surgery import compress_config

    prog = cfg["program"]
    base = get_config(prog["arch"], stack=dense_stack(
        cfg["num_hidden_layers"], window=cfg.get("sliding_window")),
        **prog.get("overrides", {}))
    nbl = list(cfg.get("nbl_layers", ()))
    mc = compress_config(base, nbl) if nbl else base
    blocks = mc.blocks()
    got = {"hidden_size": mc.d_model, "num_attention_heads": mc.n_heads,
           "num_key_value_heads": mc.n_kv_heads, "head_dim": mc.head_dim,
           "intermediate_size": mc.d_ff, "vocab_size": mc.vocab_size,
           "num_hidden_layers": mc.n_blocks, "rms_norm_eps": mc.norm_eps,
           "rope_theta": mc.rope_theta,
           "tie_word_embeddings": mc.tie_embeddings,
           "torch_dtype": mc.param_dtype,
           "hidden_act": mc.mlp_act,
           "sliding_window": next((b.window for b in blocks
                                   if b.kind == "attn"), None),
           "nbl_layers": [i for i, b in enumerate(blocks)
                          if b.kind == "nbl"]}
    bad = {k: (cfg.get(k), v) for k, v in got.items() if cfg.get(k) != v}
    if bad or mc.compute_dtype != cfg["torch_dtype"]:
        raise ValueError(f"program config differs from the file: {bad}, "
                         f"compute dtype {mc.compute_dtype}")
    return mc


def make_obs(base_cls):
    """An Observability that also records, while ``recording`` is set,
    what the per-layer readers need: each step's host split and batch,
    each fused dispatch's live lengths, each prefill's tokens and each
    admission's queue wait."""

    class BenchObs(base_cls):
        def __init__(self):
            super().__init__(trace=False, timeline_capacity=0,
                             trace_annotations=True)
            self.engine = None
            self.recording = False
            self.rec = {"steps": [], "dispatches": [], "prefills": [],
                        "waits": []}
            self.admit_s = 0.0          # this step's admission wall time

        def note_span(self, span, seconds):
            if span == "bench.plan_admission":
                self.admit_s += seconds

        def annotate(self, name):
            if self.recording and name == "nbl.fused_step":
                eng = self.engine
                self.rec["dispatches"].append(
                    [int(eng.slot_pos[s]) + 1 for s in eng.active_slots
                     if eng.slot_chunk_pos[s] < 0])
            return super().annotate(name)

        def on_prefill(self, n_tokens):
            super().on_prefill(n_tokens)
            if self.recording:
                self.rec["prefills"].append(int(n_tokens))

        def on_admit(self, req, now, chunked):
            super().on_admit(req, now, chunked)
            if self.recording:
                self.rec["waits"].append(now - req.t_submit)

        def on_step(self, engine, *, t0, t1, dispatch_s, n_decoding,
                    **kw):
            super().on_step(engine, t0=t0, t1=t1, dispatch_s=dispatch_s,
                            n_decoding=n_decoding, **kw)
            if self.recording:
                self.rec["steps"].append([t1 - t0, dispatch_s, n_decoding,
                                          self.admit_s])
            self.admit_s = 0.0

        def records(self) -> dict:
            return dict(self.rec, n_slots=self.engine.n_slots)

    return BenchObs()


def annotate_methods(eng, names, obs) -> None:
    """Wrap engine methods in profiler spans of the benchmark's own, so
    idle device time can be named by what the host was doing, and hand
    each call's wall time to ``obs.note_span``."""
    from jax.profiler import TraceAnnotation

    for name in names:
        orig = getattr(eng, name)

        def wrapped(*a, _orig=orig, _span="bench." + name.strip("_"),
                    **kw):
            t = time.monotonic()
            with TraceAnnotation(_span):
                out = _orig(*a, **kw)
            obs.note_span(_span, time.monotonic() - t)
            return out
        setattr(eng, name, wrapped)


def init_weights(mc, seed: int, mesh):
    """The seeded weights, made on the device in one jit; under a mesh
    each leaf comes out laid out by the program's own parameter specs."""
    import jax
    from repro.models import init_params

    kw = {}
    if mesh is not None:
        from repro.distributed.sharding import param_specs
        from repro.launch.specs import param_shapes
        kw["out_shardings"] = param_specs(param_shapes(mc))
    init = jax.jit(lambda k: init_params(k, mc), **kw)  # nbl: disable=jit-discipline -- one call per process
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


def bytes_per_device(tree) -> dict:
    """Bytes of a pytree of arrays held on each device."""
    import jax
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def fused_step_memory(eng) -> dict:
    """What the compiler reports for the engine's decode-step program
    (bytes on each device; read from the compile cache once warmed)."""
    import jax.numpy as jnp
    z = jnp.zeros((eng.n_slots,), jnp.int32)
    m = eng._fused_fn(1).lower(
        eng.params, jnp.zeros((eng.n_slots, 1), jnp.int32), eng.cache, z, z,
        jnp.asarray(eng.page_tbl)).compile().memory_analysis()
    if m is None:
        return {}
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(m, k)}


def warm_prompts(eng, prompt_lens) -> list:
    """One prompt length per prefill program the traffic will use."""
    by_plan = {}
    for n in sorted(set(prompt_lens)):
        by_plan.setdefault(eng._prefill_plan(n), n)
    return sorted(by_plan.values())


class CompileCounter:
    """Counts programs built (compiled or read from the compile cache)
    while ``open``."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
              "/jax/compilation_cache/task_disabled_cache")

    def __init__(self):
        import jax
        self.open, self.n = False, 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **kw):
        if self.open and event in self.EVENTS:
            self.n += 1


def main() -> int:
    proto = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)                      # stray prints go to stderr

    def reply(obj):
        proto.write(json.dumps(obj) + "\n")
        proto.flush()

    t_start = time.monotonic()
    spec = json.loads(sys.stdin.readline())
    cfg = spec["config"]
    cache_dir = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        dev = device_info(spec["chips"], spec["peak_kinds"])
    except DeviceError as e:
        print(f"serve_cell: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import numpy as np
    from repro.launch.engine import AsyncEngine, Engine
    from repro.launch.mesh import make_mesh
    from repro.launch.server import NBLServer
    from repro.obs import Observability

    mc = program_config(cfg)
    seed = key_seed(spec["seed"])
    # several chips serve tensor-parallel; one chip builds no mesh at all
    mesh = make_mesh((1, spec["chips"]), ("data", "model")) \
        if spec["chips"] > 1 else None
    e = cfg["engine"]
    obs = make_obs(Observability) if spec["trace"] else None
    # the engine keeps the mesh it is built under and steps under it
    with jax.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        t = time.monotonic()
        params = init_weights(mc, seed, mesh)
        t_init = time.monotonic() - t
        held = bytes_per_device(params)
        eng = Engine(mc, params, max_len=e["max_len"], n_slots=e["n_slots"],
                     paged=e["paged"], page_size=e["page_size"],
                     fused_step=e["fused_step"], obs=obs)
        del params
        if obs is not None:
            obs.engine = eng
        t = time.monotonic()
        warm = warm_prompts(eng, spec["prompt_lens"])
        rng = np.random.default_rng(0)
        for n in warm:
            eng.submit(rng.integers(0, mc.vocab_size, n), 2, strict=True)
        eng.run()
        t_warm = time.monotonic() - t
        step_mem = fused_step_memory(eng)
    print(f"serve_cell: weights spread over {len(held)} device(s), "
          f"{max(held.values()) / 1e9:.3f} GB on the fullest; mesh "
          f"{dict(mesh.shape) if mesh is not None else None}",
          file=sys.stderr)
    if spec["trace"]:
        annotate_methods(eng, ("step", "_plan_admission", "_commit_fused"),
                         obs)
    aeng = AsyncEngine(eng, max_pending=e["max_pending"],
                       retain_results=e["retain_results"])
    srv = NBLServer(aeng)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    counter = CompileCounter()
    print(f"serve_cell: {dev['kind']} x{dev['count']}; init {t_init:.1f} s,"
          f" warm {t_warm:.1f} s over prompt buckets {warm}; ready after "
          f"{time.monotonic() - t_start:.1f} s; fused step memory_analysis "
          f"{step_mem}", file=sys.stderr)
    reply({"event": "ready", "port": srv.port, "device": dev})

    prof_dir = os.path.join(spec["run_dir"], "profile")
    t_on = t_off = None
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd["cmd"]
        if op == "window":
            counter.open = cmd["open"]
            reply({"event": "ok"})
        elif op == "trace_start":
            jax.profiler.start_trace(prof_dir)
            obs.recording = True
            t_on = time.monotonic()
            reply({"event": "ok"})
        elif op == "trace_stop":
            obs.recording = False
            t_off = time.monotonic()
            jax.profiler.stop_trace()
            reply({"event": "ok"})
        elif op == "finish":
            srv.shutdown(drain=False)
            th.join()
            out = {"event": "result", "compiles_in_window": counter.n,
                   "fused_step_memory": step_mem,
                   "memory_peak_bytes": max(
                       (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in jax.devices()[:dev["count"]])}
            if spec["trace"]:
                out.update(trace_files(spec["run_dir"], prof_dir, obs,
                                       t_off - t_on))
            # free the program's state before the reference runs
            eng.params = eng.cache = None
            del eng, aeng, srv, obs
            gc.collect()
            t = time.monotonic()
            ref = load_module(os.path.join(HERE, "references",
                                           cfg["reference"] + ".py"))
            sample = [(np.asarray(p, np.int32), np.asarray(s, np.int32))
                      for p, s in cmd["sample"]]
            if sample:
                out["gaps"] = [g.tolist() for g in ref.logit_gaps(
                    cfg, seed, sample, spec["ref_pad"])]
                out["reference_s"] = time.monotonic() - t
                if cmd.get("control"):
                    out["control_gaps"] = [g.tolist() for g in
                                           ref.logit_gaps(
                        cfg, seed, sample, spec["ref_pad"], control=True)]
            out.setdefault("reference_s", 0.0)
            reply(out)
            return 0
    return 4


def trace_files(run_dir: str, prof_dir: str, obs, window_s: float) -> dict:
    """Normalize the profile into JSON beside the recorded spans and
    counters, and drop the raw profile."""
    import glob
    import shutil
    from bench.lib.trace import normalize

    paths = sorted(glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                             recursive=True))
    norm = normalize(paths[-1]) if paths else None
    shutil.rmtree(prof_dir, ignore_errors=True)
    path = os.path.join(run_dir, "trace.json")
    with open(path, "w") as f:
        json.dump({"trace": norm, "obs": obs.records(),
                   "window_s": window_s}, f)
    return {"trace_file": path}


if __name__ == "__main__":
    sys.exit(main())
