"""A configuration and traffic mix small enough for a CPU test run."""

SPEC = {"name": "smoke", "arch": "dense_decoder", "hidden_act": "silu",
        "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "num_hidden_layers": 4, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "tie_word_embeddings": True, "torch_dtype": "float32",
        "vocab_size": 1024, "salo_window": 16, "salo_sinks": 2}

TRAFFIC = {"kind": "serve", "arrivals": "poisson", "rate_per_s": 4.0,
           "schedule_seed": 0, "backlog": 6,
           "prompt_tokens": {"dist": "log_uniform", "min": 8, "max": 96},
           "output_tokens": {"dist": "uniform", "min": 16, "max": 32},
           "engine": {"max_batch": 4, "page": 4, "chunk": 16,
                      "kv_dtype": "int8"}}

# float32 compute with the int8 slab: sound runs read gaps of 0 to 0.0037
# and the fp8 control 0.052 to 0.133 (CPU, seeds 3 to 7)
CHECK = {"sample_tokens": 80, "max_logit_gap": 0.02}

TRAIN = {"kind": "train", "seq": 1024, "batch": 2,
         "docs": {"dist": "log_uniform", "min": 64, "max": 1024},
         "zipf_a": 1.2,
         "optimizer": {"lr": 3e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                       "weight_decay": 0.1, "grad_clip": 1.0,
                       "warmup_steps": 10, "total_steps": 200,
                       "min_lr_ratio": 0.1, "master_weights": True},
         "check_steps": 3}

# float32 program against the float32 reference: sound runs read ~1e-6
TRAIN_CHECK = {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
               "update_norm_gap": 1e-3}
