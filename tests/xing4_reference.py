"""The plain reference of the ``xing4`` family, where the program's tests
import it: one implementation, kept with the benchmark
(``benchmarks/harness/reference_xing4.py``, which imports nothing of
``mlrun_tpu``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.reference_xing4 import *  # noqa: E402,F401,F403
from benchmarks.harness.reference_xing4 import (  # noqa: E402,F401
    _mm,
    _rms_norm,
    _rope,
)


def fields_of(config) -> dict:
    """The reference's fields of a program config (``Xing4Config``)."""
    names = ("vocab_size", "n_layers", "first_k_dense", "embed_dim",
             "n_heads", "q_lora_rank", "kv_lora_rank", "nope_dim",
             "rope_dim", "v_dim", "mlp_dim", "n_experts", "top_k",
             "expert_dim", "n_shared_experts", "routed_scale", "norm_topk",
             "norm_eps", "rope_theta", "rope_factor", "rope_original_max",
             "rope_beta_fast", "rope_beta_slow", "rope_mscale",
             "rope_mscale_all_dim", "hc_mult", "hc_iters", "hc_eps",
             "hc_clamp")
    return {name: getattr(config, name) for name in names}
