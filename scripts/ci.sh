#!/usr/bin/env bash
# One-step CI for a bare CPU image:
#   0. static analysis: python -m repro.analysis over src/repro +
#      benchmarks + examples (guarded-by lock discipline, jit-discipline /
#      retrace hazards, hot-path host syncs incl. the perf_counter
#      ownership rule, obs-hook hygiene). Runs FIRST — it needs no jax
#      tracing and fails in ~a second. The --json report lands next to the
#      benchmark artifacts. Any finding not in scripts/analysis_baseline
#      .json (burned to empty) fails the build.
#   1. tier-1 suite (the ROADMAP verify command)
#   2. fast continuous-batching engine smoke on the tiny config
#   3. paged-engine smoke: interpret-mode paged-attention kernel vs its XLA
#      reference + paged-engine/generate() token parity on the tiny config
#   4. prefix-sharing smoke: two requests sharing a 2-page prefix — the
#      second admission prefills the suffix only (refcounted CoW pages)
#      and still exact-matches generate(); then the prefix_throughput
#      benchmark scenario under --fast
#   5. chunked-prefill smoke: a long prompt admitted one page-aligned
#      chunk per step next to two active decodes — decode tokens emitted
#      BETWEEN chunks, exact parity — then the serving-oracle fuzz suite
#      at a bounded example count (50 seeds x 6 engine modes x {sync,
#      async} x {fused, legacy} = 1200 randomized workloads vs
#      generate(), the sixth mode being engine-native speculative
#      decoding and every mode replayed through BOTH the fused
#      one-dispatch step pipeline and the legacy two-dispatch oracle —
#      docs/architecture.md), then the chunked_throughput and
#      fused_throughput benchmark scenarios under --fast (the latter
#      asserting p99 inter-token latency during long-prompt admission
#      strictly below the legacy path at equal HBM budget)
#   6. async serving smoke: the newline-JSON TCP server is started on a
#      free port, 3 overlapping requests are streamed through the
#      examples/stream_client.py Client, one is cancelled mid-stream —
#      survivors exact-match generate(), the victim's partial tokens are a
#      greedy-exact prefix, and the page pool ends with ZERO leaked pages.
#      The server runs with observability on (the default): the metrics
#      op is scraped MID-STREAM, the Prometheus exposition is parsed
#      line-by-line and key series are asserted non-zero. Then the
#      async_throughput benchmark scenario under --fast — which itself
#      asserts the obs overhead guard (registry-enabled streamed tok/s
#      within 3% of disabled + zero extra device dispatches at m=0).
#   7. speculative smoke: the server is restarted with --draft-m (the
#      NBL self-drafter registered engine-side), a spec stream and a
#      plain stream run concurrently through the client — both
#      exact-match generate(), the stats op shows bursts ran and ZERO
#      leaked pages after rollback. Then the speculative_throughput
#      benchmark scenario under --fast (calibrated drafter beating the
#      non-spec engine at equal HBM budget, in-benchmark parity).
#
#   bash scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
# the CPU recipe: the smokes below import JAX in a parent and Popen a
# server child, which on a TPU host would find the chip taken. The chip
# path is chip_smoke.py, one process.
export JAX_PLATFORMS=cpu

echo "== static analysis: repro.analysis (4 passes, empty baseline) =="
# replaces the old grep-based perf_counter lint: the host-sync pass owns
# the "raw time.perf_counter() only under src/repro/obs/" rule now, with
# per-line suppressions instead of a magic site count
mkdir -p benchmarks/out
python -m repro.analysis src/repro benchmarks examples \
    --json benchmarks/out/analysis.json

echo "== tier-1: pytest =="
python -m pytest -x -q

echo "== engine smoke (tiny config) =="
python - <<'EOF'
import warnings; warnings.filterwarnings("ignore")
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.engine import Engine
from repro.launch.serve import generate
from repro.models import init_params

cfg = get_config("tiny-dense")
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
           for n in (5, 9, 7)]
refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                            max_new=4))[0] for p in prompts]
eng = Engine(cfg, params, max_len=16, n_slots=2)
rids = [eng.submit(p, 4) for p in prompts]
out = eng.run()
for i, rid in enumerate(rids):
    np.testing.assert_array_equal(out[rid], refs[i])
s = eng.stats()
print(f"engine smoke OK: {s['n']} requests, {s['n_decode_steps']} decode "
      f"sweeps, {s['n_slots']} slots")
EOF

echo "== paged engine smoke (tiny config, interpret-mode kernel) =="
python - <<'EOF'
import warnings; warnings.filterwarnings("ignore")
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention, paged_decode_xla
from repro.launch.engine import Engine
from repro.launch.serve import generate
from repro.models import init_params

# interpret-mode Pallas kernel vs XLA reference (GQA + window + softcap)
rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((2, 2, 2, 16)), jnp.float32)
kp = jnp.asarray(rng.standard_normal((6, 2, 8, 16)), jnp.float32)
vp = jnp.asarray(rng.standard_normal((6, 2, 8, 16)), jnp.float32)
tbl = jnp.asarray([[3, 1, -1], [5, -1, -1]], jnp.int32)
lens = jnp.asarray([11, 4], jnp.int32)
out = paged_attention(q, kp, vp, tbl, lens, window=6, softcap=30.0,
                      interpret=True)
ref = paged_decode_xla(q, kp, vp, tbl, lens, window=6, softcap=30.0)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           atol=2e-5, rtol=2e-5)

# paged engine / generate() token parity under page pressure
cfg = get_config("tiny-dense")
params = init_params(jax.random.PRNGKey(0), cfg)
prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
           for n in (5, 9, 7)]
refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                            max_new=4))[0] for p in prompts]
eng = Engine(cfg, params, max_len=16, n_slots=2, paged=True, page_size=4)
rids = [eng.submit(p, 4) for p in prompts]
outp = eng.run()
for i, rid in enumerate(rids):
    np.testing.assert_array_equal(outp[rid], refs[i])
eng.allocator.check_invariants()
s = eng.stats()
print(f"paged smoke OK: kernel==xla; {s['n']} requests, "
      f"{s['n_decode_steps']} decode sweeps, {s['n_pages']} pages, "
      f"peak {s['peak_pages_in_use']} in use")
EOF

echo "== prefix-sharing smoke (tiny config) =="
python - <<'EOF'
import warnings; warnings.filterwarnings("ignore")
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.engine import Engine
from repro.launch.serve import generate
from repro.models import init_params

cfg = get_config("tiny-dense")
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
sys_p = rng.integers(0, cfg.vocab_size, 17).astype(np.int32)  # >= 2 full pages
prompts = [np.concatenate([sys_p, rng.integers(0, cfg.vocab_size, n)
                           .astype(np.int32)]) for n in (4, 6)]
refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                            max_new=5))[0] for p in prompts]
eng = Engine(cfg, params, max_len=48, n_slots=2, paged=True, page_size=8,
             prefix_sharing=True)
rids = [eng.submit(p, 5) for p in prompts]
out = eng.run()
for i, rid in enumerate(rids):
    np.testing.assert_array_equal(out[rid], refs[i])
s = eng.stats()
assert s["n_prefix_hits"] == 1, s          # 2nd admission hit the index
# 2nd prefill covered ONLY the suffix past the 2 shared pages (16 tokens)
assert s["n_prefill_tokens"] == len(prompts[0]) + len(prompts[1]) - 16, s
eng.allocator.check_invariants()
print(f"prefix smoke OK: {s['n']} requests, {s['n_prefix_hits']} hit, "
      f"{s['n_prefill_tokens']} tokens prefilled, "
      f"{s['n_shared_prompt_tokens']} shared")
EOF

echo "== prefix_throughput scenario (--fast) =="
python -m benchmarks.run --fast --only prefix_throughput > /dev/null
test -s benchmarks/out/prefix_throughput.json

echo "== chunked-prefill smoke (tiny config) =="
python - <<'EOF'
import warnings; warnings.filterwarnings("ignore")
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.engine import Engine
from repro.launch.serve import generate
from repro.models import init_params

cfg = get_config("tiny-dense")
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
shorts = [rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
          for _ in range(2)]
longp = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                            max_new=n))[0]
        for p, n in [(shorts[0], 12), (shorts[1], 12), (longp, 4)]]

eng = Engine(cfg, params, max_len=40, n_slots=3, paged=True, page_size=4,
             chunked_prefill=True, prefill_chunk_tokens=4)
sids = [eng.submit(p, 12) for p in shorts]
eng.step(); eng.step()                     # shorts mid-decode
lid = eng.submit(longp, 4)                 # 6 chunks of 1 page each
eng.run()
s = eng.stats()
# decode tokens were emitted BETWEEN chunks (the engine-native statistic,
# validated against a hand count in tests/test_paging.py)
assert s["n_interleaved_decode_steps"] >= 3, s
for rid, want in zip(sids + [lid], refs):
    np.testing.assert_array_equal(eng.finished[rid].tokens, want)
eng.allocator.check_invariants()
print(f"chunked smoke OK: {s['n_chunks']} chunks, "
      f"{s['n_interleaved_decode_steps']} interleaved decode steps, "
      f"exact parity")
EOF

echo "== serving-oracle fuzz suite (1200 examples: 50 seeds x 6 modes x {sync,async} x {fused,legacy}) =="
NBL_FUZZ_EXAMPLES=50 python -m pytest -q tests/test_serving_fuzz.py

echo "== chunked_throughput scenario (--fast) =="
python -m benchmarks.run --fast --only chunked_throughput > /dev/null
test -s benchmarks/out/chunked_throughput.json

echo "== fused_throughput scenario (--fast, one-dispatch step vs legacy) =="
python -m benchmarks.run --fast --only fused_throughput > /dev/null
test -s benchmarks/out/fused_throughput.json

echo "== async serving smoke (TCP server: stream 3, cancel 1 mid-stream) =="
python - <<'EOF'
import warnings; warnings.filterwarnings("ignore")
import importlib.util, subprocess, sys
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.serve import generate
from repro.models import init_params

# the server inits params from (config, seed), so this process can
# recompute generate() references for token-exact parity over the wire
# --step-delay-s widens each decode step so the mid-stream cancel below
# cannot race the victim's completion on a descheduled CI box
proc = subprocess.Popen(
    [sys.executable, "-m", "repro.launch.server", "--port", "0",
     "--config", "tiny-dense", "--seed", "0", "--max-len", "48",
     "--n-slots", "2", "--paged", "--page-size", "4",
     "--step-delay-s", "0.02"],
    stdout=subprocess.PIPE, text=True)
try:
    line = proc.stdout.readline().strip()
    assert line.startswith("LISTENING"), line
    port = int(line.split()[1])

    spec = importlib.util.spec_from_file_location(
        "stream_client", "examples/stream_client.py")
    sc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sc)
    cli = sc.Client("127.0.0.1", port, timeout=300)

    cfg = get_config("tiny-dense")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 7)]
    new = (6, 6, 32)
    refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                max_new=mn))[0]
            for p, mn in zip(prompts, new)]

    rids = [cli.submit(p, mn, tag=i)
            for i, (p, mn) in enumerate(zip(prompts, new))]
    victim = rids[2]
    tokens = {r: [] for r in rids}; done = {}
    scrape = None
    for ev in cli.events():
        if ev["event"] == "token":
            tokens[ev["rid"]].append(ev["token"])
            if scrape is None and sum(map(len, tokens.values())) == 3:
                scrape = cli.metrics()       # obs scrape MID-STREAM
            if ev["rid"] == victim and len(tokens[victim]) == 2:
                cli.cancel(victim)           # mid-stream, from the client
        elif ev["event"] == "done":
            done[ev["rid"]] = ev
            if len(done) == 3:
                break

    # --- observability surface: mid-stream scrape is live + consistent
    import re
    assert scrape is not None and scrape["enabled"], scrape
    snap = scrape["metrics"]
    assert snap["labels"]["engine_mode"] == "paged", snap["labels"]
    assert snap["counters"]["nbl_requests_submitted_total"] == 3
    assert snap["counters"]["nbl_tokens_emitted_total"] >= 3
    assert snap["counters"]["nbl_decode_steps_total"] >= 1
    assert snap["last_step"]["n_decoding"] >= 1   # caught it mid-flight
    text = scrape["prometheus"]
    sample = re.compile(r'^[A-Za-z_:][A-Za-z0-9_:]*(\{[^}]*\})? '
                        r'[-+0-9.einfEINF]+$')
    lines = [l for l in text.splitlines() if l and not l.startswith("#")]
    assert lines and all(sample.match(l) for l in lines), lines[:5]
    nz = {l.split("{")[0] for l in lines
          if float(l.rsplit(" ", 1)[1]) > 0}
    for key in ("nbl_requests_submitted_total", "nbl_tokens_emitted_total",
                "nbl_decode_steps_total", "nbl_prefills_total",
                "nbl_ttft_seconds_count", "nbl_pages_in_use"):
        assert any(s.startswith(key) for s in nz), (key, sorted(nz))
    for i in range(2):                       # survivors: exact parity
        assert done[rids[i]]["status"] == "finished", done[rids[i]]
        np.testing.assert_array_equal(np.asarray(done[rids[i]]["tokens"]),
                                      refs[i])
    assert done[victim]["status"] == "cancelled", done[victim]
    nv = len(done[victim]["tokens"])
    assert 2 <= nv < 32                      # stopped mid-generation
    np.testing.assert_array_equal(np.asarray(done[victim]["tokens"]),
                                  refs[2][:nv])   # greedy-exact prefix
    st = cli.stats()
    assert st["pages_in_use"] == 0, st       # ZERO leaked pages
    assert st["n_cancelled"] == 1 and st["n"] == 2, st
    cli.shutdown(); cli.close()
    proc.wait(timeout=120)
    assert proc.returncode == 0, proc.returncode
    print(f"async smoke OK: 2 survivors exact, victim cancelled at {nv} "
          f"tokens, 0 leaked pages, clean server exit")
finally:
    if proc.poll() is None:
        proc.kill()
EOF

echo "== async_throughput scenario (--fast, incl. obs overhead guard) =="
python -m benchmarks.run --fast --only async_throughput > /dev/null
test -s benchmarks/out/async_throughput.json

echo "== speculative smoke (TCP server with --draft-m: spec + plain streams) =="
python - <<'EOF'
import warnings; warnings.filterwarnings("ignore")
import importlib.util, subprocess, sys
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch.serve import generate
from repro.models import init_params

# --draft-m registers the zero-map NBL self-drafter engine-side; greedy
# acceptance keeps the stream token-exact regardless of draft quality,
# so the smoke asserts PARITY through draft/verify/rollback, not speed
proc = subprocess.Popen(
    [sys.executable, "-m", "repro.launch.server", "--port", "0",
     "--config", "tiny-dense", "--seed", "0", "--max-len", "48",
     "--n-slots", "2", "--paged", "--page-size", "4", "--draft-m", "2"],
    stdout=subprocess.PIPE, text=True)
try:
    line = proc.stdout.readline().strip()
    assert line.startswith("LISTENING"), line
    port = int(line.split()[1])

    spec = importlib.util.spec_from_file_location(
        "stream_client", "examples/stream_client.py")
    sc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sc)
    cli = sc.Client("127.0.0.1", port, timeout=300)

    cfg = get_config("tiny-dense")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9)]
    refs = [np.asarray(generate(cfg, params, jnp.asarray(p)[None],
                                max_new=12))[0] for p in prompts]

    # one speculative stream, one plain — mixed traffic over the wire
    rids = [cli.submit(prompts[0], 12, tag=0, spec_gamma=3, draft_m=2),
            cli.submit(prompts[1], 12, tag=1)]
    done = {}
    for ev in cli.events():
        if ev["event"] == "done":
            done[ev["rid"]] = ev
            if len(done) == 2:
                break
    for rid, want in zip(rids, refs):
        assert done[rid]["status"] == "finished", done[rid]
        np.testing.assert_array_equal(np.asarray(done[rid]["tokens"]), want)

    st = cli.stats()
    assert st["pages_in_use"] == 0, st         # rollback freed every page
    assert st["n_spec_bursts"] >= 1, st        # the spec path really ran
    assert st["n_spec_tokens"] >= 1, st
    # a spec submission that cannot fit its candidate span is rejected
    # with an error, over the wire, without killing the stream loop
    bad = cli.submit(prompts[0], 40, spec_gamma=3, draft_m=2)
    for ev in cli.events():
        if ev["event"] == "done" and ev["rid"] == bad:
            assert ev["status"] == "rejected" and "max_len" in ev["error"]
            break
    cli.shutdown(); cli.close()
    proc.wait(timeout=120)
    assert proc.returncode == 0, proc.returncode
    print(f"spec smoke OK: spec+plain exact parity, "
          f"{st['n_spec_bursts']} bursts, "
          f"{st['n_spec_accepted_tokens']} accepted, 0 leaked pages")
finally:
    if proc.poll() is None:
        proc.kill()
EOF

echo "== speculative_throughput scenario (--fast, calibrated drafter) =="
python -m benchmarks.run --fast --only speculative_throughput > /dev/null
test -s benchmarks/out/speculative_throughput.json

echo "CI OK"
