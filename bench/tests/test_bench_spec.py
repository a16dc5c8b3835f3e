"""The harness finds a configuration, a traffic mix, a limit and a
per-layer reader by name, from files added to a checkout, without an
edit to any file already there."""
import hashlib
import json
import sys
from pathlib import Path

import pytest

import bench_tiny_cell as tiny

sys.path.insert(0, str(tiny.ROOT))

from bench.lib import traffic as tgen  # noqa: E402
from bench.lib.spec import Bench, SpecError, read_metric  # noqa: E402

READER = '''
def read(ctx, name):
    return {"name": name, "steps": len(ctx["obs"]["steps"])}
'''


def digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in (root / "bench").rglob("*") if p.is_file()}


@pytest.fixture
def root(tmp_path):
    return tiny.make_root(tmp_path)


def test_added_files_are_found_by_name(root):
    before = digests(root)
    b = root / "bench"
    cfg = dict(tiny.CONFIG, hidden_size=96)
    (b / "configs" / "wide.json").write_text(json.dumps(cfg))
    (b / "traffic" / "bursty.json").write_text(json.dumps(
        dict(tiny.TRAFFIC, rate_per_s=3)))
    (b / "limits" / "wide.bursty.json").write_text(json.dumps(tiny.LIMITS))
    (b / "layer_metrics" / "new_share.py").write_text(READER)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "wide", "source": "tests",
                            "file": "bench/configs/wide.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": "wide.bursty", "config": "wide",
                              "traffic": "bursty", "chips": 1,
                              "why": "tests"})
    spec["per_layer"].append({"name": "new_share.burst", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "kernels", "moves": "tokens_per_s",
                              "workloads": ["wide.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = Bench(str(root))
    cell = bench.cell("wide.bursty")
    assert bench.config(cell["config"])["hidden_size"] == 96
    t = bench.traffic(cell["traffic"])
    assert t["rate_per_s"] == 3
    assert bench.limits("wide.bursty") == tiny.LIMITS
    assert [m["name"] for m in bench.per_layer("wide.bursty")] == \
        ["new_share.burst"]
    # a "<base>.<variant>" metric without a file of its own reads with
    # the base name's reader
    assert bench.reader_path("new_share.burst").endswith("new_share.py")
    got = read_metric(bench, "new_share.burst", {"obs": {"steps": [1, 2]}})
    assert got == {"name": "new_share.burst", "steps": 2}
    # the generator takes the new mix as it is
    plan = tgen.plan(t, vocab=cfg["vocab_size"], seed=5, seconds=2,
                     n_slots=4)
    assert plan and all(len(r["prompt"]) >= 8 for r in plan)
    # nothing that was there changed
    after = digests(root)
    assert {k: after[k] for k in before} == before


def test_every_cell_of_the_benchmark_resolves():
    bench = Bench(str(tiny.ROOT))
    for w in bench.spec["workloads"]:
        cfg = bench.config(w["config"])
        bench.traffic(w["traffic"])
        assert bench.limits(w["name"])["max_logit_gap"]["limit"] > 0
        assert (tiny.ROOT / "bench" / "references"
                / f"{cfg['reference']}.py").is_file()
        assert bench.end_to_end(w["name"])
        for m in bench.per_layer(w["name"]):
            bench.reader_path(m["name"])
        e2e = {m["name"] for m in bench.end_to_end(w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert all(m["moves"] in e2e for m in bench.per_layer(w["name"]))


def test_unknown_names_are_refused(root):
    bench = Bench(str(root))
    with pytest.raises(SpecError):
        bench.cell("no-such-cell")
    with pytest.raises(SpecError):
        bench.traffic("no-such-mix")
    with pytest.raises(SpecError):
        bench.reader_path("no_such_metric.chat")
