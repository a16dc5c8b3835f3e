"""``program_config`` builds the model a configuration file states, and
refuses a file whose sizes disagree with what the program builds. Only
configurations are built here, no weights."""
import json
import sys

import pytest

import bench_tiny_cell as tiny

sys.path.insert(0, str(tiny.ROOT / "bench"))

import serve_cell  # noqa: E402

FILES = ["mistral-7b-8L-nbl3", "minicpm-2b-nbl15"]


def config_file(name: str) -> dict:
    return json.loads((tiny.ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", FILES)
def test_files_build_the_stack_they_state(name):
    cfg = config_file(name)
    mc = serve_cell.program_config(cfg)
    blocks = mc.blocks()
    assert mc.n_blocks == len(blocks) == cfg["num_hidden_layers"]
    assert [i for i, b in enumerate(blocks) if b.kind == "nbl"] == \
        cfg["nbl_layers"]
    assert all(b.window == cfg["sliding_window"] for b in blocks
               if b.kind == "attn")


@pytest.mark.parametrize("change", [
    {"intermediate_size": 11008},
    {"head_dim": 64},
    {"num_key_value_heads": 4},
    {"vocab_size": 32768},
], ids=["mlp-width", "head-dim", "kv-heads", "vocab"])
def test_file_that_disagrees_with_the_program_is_refused(change):
    cfg = dict(config_file("mistral-7b-8L-nbl3"), **change)
    with pytest.raises(ValueError, match="differs from the file"):
        serve_cell.program_config(cfg)
