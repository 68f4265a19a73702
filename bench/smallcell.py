"""A benchmark cell small enough for the CPU: the Qwen2 mechanisms
(GQA, QKV bias, tied head) at toy widths, under GRPO traffic of the
grpo-recur kind. Tests drive the whole harness with it."""

SPEC = {
    "name": "toy-qwen2", "arch": "qwen2-1.5b", "slots": 8,
    "dtype": "bfloat16", "weights_seed": 1,
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_hidden_layers": 2,
               "num_key_value_heads": 2, "head_dim": 16,
               "rms_norm_eps": 1e-6, "rope_theta": 1e6,
               "tie_word_embeddings": True, "vocab_size": 512},
    # bf16 program readings at this size stay under 0.01 (max over
    # sampled tokens); the float8 control reads several times that
    "correct": {"max_logit_gap": 0.02},
}

MIX = {
    "generator": "grpo", "problems": 3, "group": 8, "prompt_len": [10, 20],
    "scale": {"law": "pareto", "alpha": 1.0, "low": 8, "cap": 40},
    "rollout_sigma": 0.5, "min_new": 4, "recurring": True,
    "sizes_seed": 0, "problems_seed": 1, "warm_steps": 2,
    "policy_drift": 0.003, "profile": {"after": 4, "rounds": 4},
}
