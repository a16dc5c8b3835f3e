"""A tiny cell for the benchmark's own CPU tests: a copy of the benchmark
in a temporary checkout with one more configuration, traffic mix, limit
and BENCHMARK.json entry, and a child command that skips the harness's
look for a chip (and can break the timed path underneath)."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "source": "tiny-dense preset of the program, for CPU tests only",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 4, "nbl_layers": [3], "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "sliding_window": None,
    "tie_word_embeddings": True, "torch_dtype": "float32",
    "vocab_size": 512, "program": {"arch": "tiny-dense"},
    "reference": "dense_gqa",
    "engine": {"paged": True, "page_size": 8, "max_len": 96, "n_slots": 4,
               "max_pending": 16, "retain_results": False,
               "fused_step": True}}
TRAFFIC = {
    "loop": "open", "rate_per_s": 8,
    "prompt_len": {"median": 16, "sigma": 0.6, "min": 8, "max": 40},
    "output_len": {"median": 16, "sigma": 0.4, "min": 8, "max": 32},
    "tokens": {"zipf_a": 1.2, "p_copy": 0.6}, "block": 16, "draw_seed": 3,
    "ramp_s": 1, "trace_s": 1, "check_requests": 4}
# on four chips: as many KV heads as chips, so the sharded engine splits
# the heads, the KV pool and the paged attention over "model"
CONFIG4 = dict(CONFIG, num_key_value_heads=4,
               program={"arch": "tiny-dense", "overrides": {"n_kv_heads": 4}})
# float32 program against the float32 reference: gaps are rounding only
LIMITS = {"max_logit_gap": {"limit": 1e-3}, "min_tokens": 20}
CELL = "tiny.open"

# the child: the harness's serve_cell with the chip check skipped and,
# by name, one fault planted in the timed path
WRAPPER = '''
import os
import sys
if {chips} > 1:               # virtual CPU devices, set before JAX starts
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={chips}"
sys.path.insert(0, {bench!r})
sys.path.insert(0, {src!r})
import serve_cell
serve_cell.device_info = lambda chips, kinds: {{
    "platform": "cpu", "kind": sorted(kinds)[0], "count": chips}}
fault = sys.argv[1]
import jax
import repro.launch.engine as E
if fault == "token":          # a token altered where it is produced
    _sample = E.Engine._sample
    E.Engine._sample = lambda self, row: (_sample(self, row) + 1) \\
        % self.cfg.vocab_size
elif fault == "state":        # a step that returns its state unchanged
    _fused_fn = E.Engine._fused_fn
    def _broken(self, width):
        fn = _fused_fn(self, width)
        return jax.jit(lambda p, t, c, *rest: (fn(p, t, c, *rest)[0], c))
    E.Engine._fused_fn = _broken
elif fault == "exchange":     # the exchange between chips left out: each
    import repro.models.transformer as T    # chip keeps its partial sum
    from jax.sharding import PartitionSpec as P
    def _mlp(p, x, act):
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        lead = (None,) * (h.ndim - 1)
        return jax.shard_map(lambda a, w: a @ w,
                             in_specs=(P(*lead, "model"), P("model", None)),
                             out_specs=P(), check_vma=False)(h, p["w_down"])
    T.mlp = _mlp
sys.exit(serve_cell.main())
'''


def make_root(tmp: Path, bench_from: Path = ROOT, chips: int = 1) -> Path:
    """A checkout holding BENCHMARK.json and bench/ plus the tiny cell, on
    ``chips`` chips."""
    root = tmp / "checkout"
    shutil.copytree(bench_from / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(CONFIG if chips == 1 else CONFIG4))
    (root / "bench" / "traffic" / "tiny-open.json").write_text(
        json.dumps(TRAFFIC))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps(LIMITS))
    spec = json.loads((bench_from / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": CONFIG["source"],
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": CELL, "config": "tiny",
                              "traffic": "tiny-open", "chips": chips,
                              "why": "tests"})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    for m in spec["per_layer"]:
        if m["name"] in ("host_ms_per_step", "queue_wait_p95_ms",
                         "step_mfu.chat"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def child_cmd(tmp: Path, fault: str = "none", bench: Path = None,
              chips: int = 1) -> list:
    bench = bench or ROOT / "bench"
    path = tmp / f"child_{fault}.py"
    path.write_text(WRAPPER.format(bench=str(bench), src=str(ROOT / "src"),
                                   chips=chips))
    return [sys.executable, str(path), fault]


def run(root: Path, cmd: list, capsys, *args) -> tuple:
    """Run the harness in this process (it never imports JAX); returns
    (exit code, result line or None, stderr). With ``capfd`` in place of
    ``capsys`` the stderr holds the child's log too."""
    sys.path.insert(0, str(ROOT))
    from bench import run_cell
    rc = run_cell.run(["--workload", CELL] + list(args), child_cmd=cmd,
                      root=str(root))
    out, err = capsys.readouterr()
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    return rc, (json.loads(lines[-1]) if lines else None), err
