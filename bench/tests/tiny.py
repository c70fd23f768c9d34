"""A configuration and mixes small enough for the CPU, with the same keys
as the benchmark's own files."""
import copy

CONFIG = {
    "name": "tiny", "architectures": ["Qwen2ForCausalLM"],
    "program_arch": "qwen2.5-14b",
    "num_hidden_layers": 2, "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 8, "num_key_value_heads": 2, "vocab_size": 512,
    "hidden_act": "silu", "rope_theta": 1000000.0,
    "tie_word_embeddings": False, "max_batch": 4,
    "assumed": {"head_dim": 32, "rms_norm_eps": 1e-06,
                "matmul_precision": "highest",
                "m2_active_ratio": 0.30, "m2_ratio_fp": 0.25,
                "m2_ratio_int8": 0.25, "m2_ratio_int4": 0.50,
                "m2_predictor_rank": 16},
}

DOCQA = {
    "arrival": {"process": "poisson", "rate_rps": 3.0},
    "documents": {"count": 3, "tokens": {"median": 64, "sigma": 0.5,
                                         "min": 48, "max": 96,
                                         "multiple": 16}},
    "prompt_tokens": {"median": 12, "sigma": 0.5, "min": 8, "max": 16},
    "answer_tokens": {"median": 12, "sigma": 0.5, "min": 8, "max": 16},
    "prefill_chunk": 16, "prefix_cache": True,
    "kv_tokens": {"hbm": 160, "dram": 96},
    "slo": {"class": "interactive", "ttft_s": 7.0, "mean_gap_s": 0.6},
    "check": {"requests": 6},
}

CHAT = {
    "arrival": {"process": "poisson", "rate_rps": 3.0},
    "prompt_tokens": {"median": 20, "sigma": 0.6, "min": 10, "max": 40},
    "answer_tokens": {"median": 6, "sigma": 0.5, "min": 3, "max": 10},
    "prefill_chunk": 16, "prefix_cache": True,
    "kv_tokens": {"hbm_per_slot": 64, "dram": 256},
    "slo": {"class": "interactive", "ttft_s": 7.0, "mean_gap_s": 0.6},
    "check": {"requests": 3},
}

LIMITS = {"served_gap": {"limit": 1e-3}, "last_logits_rms": {"limit": 1e-3}}


def config(**over):
    c = copy.deepcopy(CONFIG)
    c.update(over)
    return c
