"""Tiny cells for the CPU tests: the cells' own drivers and mesh shapes,
at widths a test run can hold."""
from __future__ import annotations

from bench import common as C

def config(norm="layernorm", gated=True, act="silu", kv=4, layers=2,
           dtype="bfloat16", rot=0.25):
    return {"program_config": "stablelm-1.6b", "hidden_size": 64,
            "intermediate_size": 128, "num_attention_heads": 4,
            "num_key_value_heads": kv, "head_dim": 16,
            "num_hidden_layers": layers, "vocab_size": 512,
            "hidden_act": act, "mlp_gated": gated, "norm_type": norm,
            "layer_norm_eps": 1e-5 if norm == "layernorm" else 1e-6,
            "partial_rotary_factor": rot, "rope_theta": 10000,
            "tie_word_embeddings": False, "torch_dtype": dtype}


def cell(name: str, cfg: dict, limits: dict, chips: int = 1):
    """A tiny copy of cell ``name``: its driver, mesh and metrics, with
    small traffic and the given limits."""
    bj = C.load_json(C.ROOT / "BENCHMARK.json")
    spec = dict(C.load_json(C.BENCH / "workloads" / f"{name}.json"))
    spec["limits"] = limits
    traffic = {"kind": "train", "global_batch": 4 * chips, "seq_len": 32,
               "markov_states": 64, "feed_batches": 4}
    return C.Cell(name=name, chips=chips, config_name="tiny", config=cfg,
                  traffic_name="tiny", traffic=traffic, spec=spec,
                  end_to_end=[m for m in bj["end_to_end"]
                              if C._applies(m, name)],
                  per_layer=[m for m in bj["per_layer"]
                             if C._applies(m, name)])
