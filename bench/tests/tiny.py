"""Tiny cells for the CPU tests: the harness's code paths at a size a
test run can hold."""

QWEN_LIKE = {"n_layers": 2, "d_model": 256, "n_heads": 10, "n_kv_heads": 2,
             "head_dim": 32, "q_dim": 320, "kv_dim": 64, "d_ff": 512,
             "vocab": 512, "qk_norm": True, "qkv_bias": False,
             "rope_theta": 1e6, "norm_eps": 1e-6, "dtype": "bfloat16",
             "bits": 2}
MISTRAL_LIKE = {**QWEN_LIKE, "n_heads": 24, "q_dim": 768, "qk_norm": False,
                "norm_eps": 1e-5}


def cell(model=QWEN_LIKE, arrivals="poisson", limit=0.05):
    mix = {"arrivals": arrivals,
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 128},
           "output": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                      "min": 4, "max": 64},
           "sampling": {"temperature": 0.7, "top_p": 0.9},
           "check_every": 2, "size_seed": 1}
    geo = {"max_seq_len": 192, "n_slots": 4, "page_size": 16, "n_pages": 64,
           "prefill_chunk": 32, "token_budget": 36, "requests": 60,
           "in_flight": 2, "warm_s": 0.5, "rate_rps": 4.0, "plan_seeds": [1],
           "tick_model": {"decode_tick_s": 0.02, "prefill_token_s": 2e-4},
           "check": {"min_served": 40, "max_tokens": 2000,
                     "widest_gap_limit": limit}}
    return {"workload": {"name": "tiny.mix", "config": "tiny",
                         "traffic": "mix", "chips": 1},
            "model": dict(model), "mix": mix, "cell": geo}
