"""The harness on four virtual CPU devices, with the chip check skipped:
the tiny cell asks for 4 chips, so the child builds its weights and the
engine under a (data, model) = (1, 4) mesh and serves tensor-parallel.
Sound, it comes out correct against the plain reference; with the
exchange between chips left out underneath, it does not."""
import pytest

import bench_tiny_cell as tiny

SEEDS = ("--seed", "2147483911", "--seconds", "2", "--trace", "0")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("four"), chips=4)


def test_sharded_run_is_correct(root, tmp_path, capfd):
    rc, res, err = tiny.run(root, tiny.child_cmd(tmp_path, chips=4), capfd,
                            *SEEDS)
    assert rc == 0, err[-3000:]
    assert "weights spread over 4 device(s)" in err
    assert "'model': 4" in err
    assert res["device"]["count"] == 4
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_exchange_left_out_is_not_correct(root, tmp_path, capfd):
    rc, res, err = tiny.run(root, tiny.child_cmd(tmp_path, "exchange",
                                                 chips=4), capfd, *SEEDS)
    assert rc == 0, err[-3000:]
    assert "weights spread over 4 device(s)" in err
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > 10 * gap["limit"]
