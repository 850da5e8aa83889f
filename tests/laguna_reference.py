"""The plain reference of the ``laguna`` family, where the program's tests
import it: one implementation, kept with the benchmark
(``benchmarks/harness/reference_laguna.py``, which imports nothing of
``mlrun_tpu``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.reference_laguna import *  # noqa: E402,F401,F403


def fields_of(config) -> dict:
    """The reference's fields of a program config (``LagunaConfig``)."""
    names = ("vocab_size", "n_layers", "embed_dim", "n_heads", "n_kv_heads",
             "head_dim", "layer_types", "mlp_layer_types", "heads_per_layer",
             "sliding_window", "mlp_dim", "n_experts", "top_k", "expert_dim",
             "shared_dim", "routed_scale", "norm_topk", "experts_held",
             "norm_eps", "rope_theta", "rope_factor", "rope_original_max",
             "rope_beta_fast", "rope_beta_slow", "rope_attention_factor",
             "rope_partial", "window_rope_theta", "window_rope_partial")
    return {name: getattr(config, name) for name in names}
