"""A micro-hello cell small enough for the CPU: the configuration, both
traffic kinds and limits, in the same shape as the cells' files."""
from __future__ import annotations

TINY_CONF = {
    "name": "micro-hello-test",
    "hidden_size": 64, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 512,
    "run": {"arch": "micro-hello", "reference": "dense",
            "depth_key": "num_hidden_layers",
            "widths": {"d_model": "hidden_size", "d_ff": "intermediate_size",
                       "num_heads": "num_attention_heads",
                       "num_kv_heads": "num_key_value_heads",
                       "vocab_size": "vocab_size"},
            "head_dim": 16, "mlp": "gated_silu", "tied_embeddings": True,
            "norm_eps": 1e-05, "rope_theta": 10000.0,
            "param_dtype": "float32", "compute_dtype": "bfloat16"},
}

FORK_MIX = {"kind": "fork_invoke",
            "arrivals": {"process": "poisson", "rate_per_s": 4.0,
                         "order_seed": 1},
            "prompt_tokens": {"values": [16, 32], "weights": [1, 1]},
            "output_tokens": 4, "drain_s": 60, "sample_requests": 8}

SERVE_MIX = {"kind": "open_poisson",
             "arrivals": {"process": "poisson", "rate_per_s": 8.0,
                          "order_seed": 1},
             "prompt_tokens": {"values": [32, 64], "weights": [0.5, 0.5]},
             "output_tokens": 6, "max_active": 3, "kv_page_tokens": 16,
             "drain_s": 60, "sample_requests": 8}

# At this size and seed, bf16 serving reads a widest gap under 0.005 against
# the float32 reference and the fp8 control about 0.05; an altered token
# reads far more.
LIMITS = {"served_logit_gap": 0.02}


CELLS = {"fork": "stablelm-fork-invoke", "serve": "granite-code-serve"}


def workload(kind: str) -> dict:
    """The real cell of this kind, run on the tiny configuration and mix."""
    return {"name": CELLS[kind], "config": TINY_CONF["name"], "traffic": kind,
            "chips": 1}
