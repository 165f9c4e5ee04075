import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# ``tests/bench/bench_tiny.py`` builds a tiny cell from each configuration
# in BENCHMARK.json, by name, from its ``TINY_CONFIGS``; configurations the
# benchmark gained after that file are given theirs here, before any test
# module is collected.
TINY_CONFIGS = {
    "granite-4.0-h-small": dict(
        program_args=["--arch", "granite-4.0-h-small", "--preset", "tiny",
                      "--expert-share", "0/2"],
        hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
        head_dim=32, intermediate_size=384, vocab_size=512, mamba_d_head=32,
        mamba_d_state=16, mamba_n_heads=8, num_experts_per_tok=2,
        num_local_experts=2, expert_share={"shard": 0, "shards": 2}),
}


def pytest_configure(config):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "bench"))
    import bench_tiny
    for name, tiny in TINY_CONFIGS.items():
        bench_tiny.TINY_CONFIGS.setdefault(name, tiny)
