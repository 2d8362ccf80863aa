"""Cells at a size a CPU test holds: the published cells' shapes of
config and mix, with every width cut so the whole run takes seconds."""

from __future__ import annotations

import copy

from bench import harness

TINY_MODEL = {
    "arch": "qwen3-0.6b",
    "overrides": {"d_model": 64, "n_layers": 2, "num_heads": 4,
                  "num_kv_heads": 2, "head_dim": 16, "d_ff": 96,
                  "vocab_size": 512, "chunk_q": 32, "chunk_k": 32,
                  "param_dtype": "float32", "compute_dtype": "float32"},
    "model": {"hidden_size": 64, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 96, "vocab_size": 512,
              "rms_norm_eps": 1e-06, "rope_theta": 1000000,
              "attention_bias": False, "qk_norm": True,
              "torch_dtype": "bfloat16"},
}


def tiny_cell(traffic: str, **mix_over) -> dict:
    """The cell of `traffic`'s mix on the tiny model, with `mix_over`
    replacing keys of the mix."""
    mix = copy.deepcopy(harness.load_json(
        harness.BENCH / "traffic" / f"{traffic}.json"))
    mix.update(batch=2, seq_len=64)
    mix.update(mix_over)
    model = copy.deepcopy(TINY_MODEL)
    model["model"] = dict(model["model"])
    return {"workload": {"name": f"tiny.{traffic}", "chips": 1},
            "config": {"name": "tiny"}, "model": model, "mix": mix,
            "end_to_end": [], "per_layer": []}
