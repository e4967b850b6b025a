"""CPU tests of the benchmark: its yardsticks, its trace reduction, how it
finds a cell's files, and the comparison that decides ``correct``, driven
at a tiny size through the program's real training step."""

import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "qwen2-tiny", "family": "qwen2", "source": "test",
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 500, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
}

TINY_MIX = {
    "seq_len": 64, "batch_per_chip": 2, "n_batches": 4, "structure": 0.8,
    "mesh": {"1": {"pod": 1, "data": 1, "model": 1},
             "4": {"pod": 2, "data": 2, "model": 1}},
    "step": {"grad_path": "mlfabric", "lr": 0.1, "gamma": 0.9,
             "remat": True, "bucket_bytes": 4096, "shortest_first": True,
             "compress_inter": True, "overlap_chunks": 1},
    "trace_steps": 2,
}


def tiny_cell(chips=1, limits=None, **step):
    from benchlib.cells import Cell

    mix = copy.deepcopy(TINY_MIX)
    mix["step"].update(step)
    if mix["step"]["grad_path"] == "auto":
        mix["step"] = {k: mix["step"][k]
                       for k in ("grad_path", "lr", "gamma", "remat")}
    return Cell(name="tiny", chips=chips, config_name="qwen2-tiny",
                config=dict(TINY_CONFIG), traffic="tiny", mix=mix,
                limits=limits or {"loss_gap": 1.0, "grad_gap": 1.0,
                                  "delta_gap": 1.0},
                end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                            {"name": "setup_s", "unit": "s"}],
                per_layer=[], bench_dir=BENCH)


@pytest.fixture
def tiny():
    return tiny_cell
