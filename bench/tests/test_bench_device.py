"""The command refuses a device that is not a TPU, too few chips, and a
device kind the peak table lacks; and it prints no result without the
program beside it."""
import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny_cell as tiny

sys.path.insert(0, str(tiny.ROOT / "bench"))

import serve_cell  # noqa: E402

KINDS = ["TPU v5 lite"]


class FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("devs,chips,msg", [
    ([FakeDevice("cpu", "cpu")], 1, "needs a TPU"),
    ([FakeDevice("tpu", "TPU v5 lite")], 4, "needs 4 chips"),
    ([FakeDevice("tpu", "TPU v99")], 1, "not in bench/peaks.json"),
])
def test_device_refused(devs, chips, msg, monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: devs)
    with pytest.raises(serve_cell.DeviceError, match=msg):
        serve_cell.device_info(chips, KINDS)


def test_device_accepted(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda: [FakeDevice("tpu", "TPU v5 lite")] * 4)
    assert serve_cell.device_info(1, KINDS) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_command_on_cpu_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload",
         "mistral7b-8L-nbl3.chat-poisson", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=tiny.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_files_alone_print_no_result(tmp_path, capsys):
    """A checkout that holds only BENCHMARK.json and bench/: even with the
    chip check skipped, the program is missing and no result comes."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(tiny.ROOT / "BENCHMARK.json", alone)
    shutil.copytree(tiny.ROOT / "bench", alone / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    root = tiny.make_root(tmp_path / "b", bench_from=alone)
    cmd = tiny.child_cmd(tmp_path, bench=root / "bench")
    # the child wrapper adds the real src/ to the path; point it away
    text = (tmp_path / "child_none.py").read_text().replace(
        str(tiny.ROOT / "src"), str(alone / "src"))
    (tmp_path / "child_none.py").write_text(text)
    rc, res, err = tiny.run(root, cmd, capsys, "--seed", "1", "--seconds",
                            "1", "--trace", "0")
    assert rc != 0 and res is None
    assert "run failed" in err
