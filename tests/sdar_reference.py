"""The plain reference of the ``sdar`` family, where the program's tests
import it: one implementation, kept with the benchmark
(``benchmarks/harness/reference_sdar.py``, which imports nothing of
``mlrun_tpu``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.reference_sdar import *  # noqa: E402,F401,F403
from benchmarks.harness.reference_sdar import (  # noqa: E402,F401
    _mm,
    _rms_norm,
    _rope,
)


def fields_of(config) -> dict:
    """The reference's fields of a program config (``SdarConfig``)."""
    return {"vocab_size": config.vocab_size, "n_layers": config.n_layers,
            "embed_dim": config.embed_dim, "n_heads": config.n_heads,
            "n_kv_heads": config.n_kv_heads, "head_dim": config.head_dim,
            "n_experts": config.n_experts, "top_k": config.top_k,
            "expert_dim": config.expert_dim, "norm_topk": config.norm_topk,
            "rope_theta": config.rope_theta, "norm_eps": config.norm_eps,
            "block_length": config.block_length,
            "mask_token_id": config.mask_token_id}
