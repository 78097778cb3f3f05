"""Tiny sizes for the configurations that came after
`test_harness_cpu.py` was written: its `root` fixture shrinks every
declared configuration from that module's `_TINY_CFG`, which knows the
first two. An autouse fixture runs before a test's own fixtures, so the
table is whole by the time `root` reads it, whichever test module of this
directory borrowed the fixture; no file that is there is edited."""
import pytest

TINY_KANANA = {
    "hidden_size": 64, "num_attention_heads": 2, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "intermediate_size": 96, "moe_intermediate_size": 24,
    "n_routed_experts": 2, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "vocab_size": 48, "bptt": 32,
    "published": {"num_hidden_layers": 48, "n_routed_experts": 8,
                  "vocab_size": 384},
    # `root` copies `classes` into the check
    "classes": 48,
}


@pytest.fixture(autouse=True)
def _tiny_sizes_of_later_configurations():
    import test_harness_cpu

    # In the harness's runs only: a few thousand weights moved by 3e-7 a
    # step move the bf16 copy too little for the loss on a pool batch to
    # fall in a handful of steps (tests/conftest.py gives the later tiny
    # configurations the same rate)
    test_harness_cpu._TINY_CFG.setdefault("kanana2_30b_a3b", dict(
        TINY_KANANA, optimizer={"name": "adam",
                                "params": {"learning_rate": 1e-3}}))
