"""Shared fixtures of the benchmark's CPU tests: a smoke-size configuration
and mixes that run the whole harness in seconds."""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# at smoke size on the CPU (ten runs) the program reads a mean gap of 0 to
# 7.6e-4 and the fp8 control about 0.05: the smoke limit sits between them.
# int8 reads 0 to 4.2e-3 here, too near the program on ~55 tokens of a
# 500-token vocabulary to separate; it is separated at the cells' sizes
SMOKE_CONF = {
    "reference": "llama",
    "config": {
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 4, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "vocab_size": 500,
    },
    "engine": {
        "dtype": "bfloat16", "tenants": ["model"], "decode_rows": 4,
        "page_size": 8, "kv_pages": 96, "kernel_backend": "pallas",
        "prefill_chunk": 32, "prefill_token_budget": 32,
        "max_prefill_per_step": 2, "bucket_min": 8, "bucket_growth": 2.0,
        "staging_growth": 2.0,
    },
    "correctness": {"limits": {"gap_max": 0.05, "gap_mean": 0.005}, "sample_tokens": 48,
                    "min_requests": 6, "max_requests": 12},
}

SMOKE_MIXES = {
    "open": {"loop": "open", "rate_per_s": 6.0, "lead_in_s": 0.5,
             "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 60},
             "output": {"median": 8, "sigma": 0.4, "min": 4, "max": 16}},
    "closed": {"loop": "closed", "clients": 4, "lead_in_s": 0.5,
               "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 60},
               "output": {"median": 8, "sigma": 0.4, "min": 4, "max": 16}},
}


@pytest.fixture
def smoke_conf():
    return copy.deepcopy(SMOKE_CONF)


@pytest.fixture(params=sorted(SMOKE_MIXES))
def smoke_mix(request):
    return copy.deepcopy(SMOKE_MIXES[request.param])
