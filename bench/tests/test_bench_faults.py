"""A whole run of the harness at a tiny size on the CPU, with the chip
check skipped: sound, it comes out correct; with the timed path broken
underneath, or with the reference one precision step down in the
program's place (the control), it does not."""
import pytest

import bench_tiny_cell as tiny

SEEDS = ("--seed", "2147483905", "--seconds", "2", "--trace", "0")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("faults"))


def test_sound_run_is_correct_and_reports_every_metric(root, tmp_path,
                                                       capsys):
    rc, res, err = tiny.run(root, tiny.child_cmd(tmp_path), capsys, *SEEDS)
    assert rc == 0, err[-2000:]
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "ttft_p95_ms",
                                   "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert err.rstrip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_step_is_not_correct(fault, root, tmp_path, capsys):
    rc, res, err = tiny.run(root, tiny.child_cmd(tmp_path, fault), capsys,
                            *SEEDS)
    assert rc == 0, err[-2000:]
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > 10 * gap["limit"]


def test_control_is_not_correct(root, tmp_path, capsys):
    """The control: the float32 reference computed in bfloat16, judged in
    the program's place at each served position, comes out not correct
    against the limit the sound program stays under."""
    rc, res, err = tiny.run(root, tiny.child_cmd(tmp_path), capsys,
                            *SEEDS, "--control", "1")
    assert rc == 0, err[-2000:]
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert res["checks"]["served_tokens_checked"]["value"] >= \
        tiny.LIMITS["min_tokens"]
