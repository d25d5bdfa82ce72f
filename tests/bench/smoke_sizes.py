"""Configuration files cut to ``ArchConfig.smoke()``-like widths for CPU
tests: the benchmark's own files with only the sizes changed."""
import copy

import _bench_path  # noqa: F401
from bench import common


def smoke_config(family: str, limits=None) -> dict:
    """The family's configuration file at smoke sizes. ``limits``: the
    limit of each number that file compares, for these sizes (the file's
    own limits are for its full size); None leaves every number
    uncompared."""
    name = "starcoder2-15b-s10" if family == "dense" else "deepseek-moe-16b-s7"
    c = copy.deepcopy(common.config(name))
    c.update(hidden_size=64, num_attention_heads=4, head_dim=16,
             num_key_value_heads=2 if family == "dense" else 4,
             intermediate_size=128, vocab_size=256, num_hidden_layers=2)
    if family == "moe":
        c.update(n_routed_experts=4, num_experts_per_tok=2,
                 n_shared_experts=1, moe_intermediate_size=32)
    c["deployment"].update(max_slots=4, max_seq_len=64)
    assert limits is None or set(limits) == set(c["correct"]), limits
    c["correct"] = dict(limits or {})
    return c


SMOKE_MIX = {
    "arrivals": "poisson",
    "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                   "min": 8, "max": 40},
    "output_len": {"dist": "uniform", "min": 4, "max": 16},
}

SMOKE_CELL = {"rate_per_s": 4.0, "lead_s": 0.5, "tail_s": 60}

# the limits, at these sizes on the CPU, of the numbers each family's
# configuration file compares. Over seeds 1-6 and 2**33 + 5, sound runs read
# mean_logit_gap 0-0.00039 (dense) and untied_mean_gap 0-0.00086 (moe); the
# planted faults of test_bench_faults.py read from 0.0744 (dense, prefill)
# and 0.152 (moe, prefill) up
SMOKE_LIMITS = {"dense": {"mean_logit_gap": 0.01},
                "moe": {"untied_mean_gap": 0.02}}


def mid_config(family: str, limits=None) -> dict:
    """Wider than smoke: at d_model 512 the bf16 program and the W8A8
    control read apart (the control is the program's own lower-precision
    serving path)."""
    c = smoke_config(family, limits)
    c.update(hidden_size=512, num_attention_heads=8, head_dim=64,
             num_key_value_heads=2 if family == "dense" else 8,
             intermediate_size=2048, vocab_size=8192)
    if family == "moe":
        c.update(n_routed_experts=8, moe_intermediate_size=256)
    c["deployment"].update(max_seq_len=128)
    return c


MID_MIX = {
    "arrivals": "poisson",
    "prompt_len": {"dist": "lognormal", "median": 32, "sigma": 0.6,
                   "min": 16, "max": 64},
    "output_len": {"dist": "uniform", "min": 16, "max": 48},
}
MID_CELL = {"rate_per_s": 3.0, "lead_s": 0.5, "tail_s": 60}
