"""The plain reference of the ``nemotronh`` family, where the program's
tests import it: one implementation, kept with the benchmark
(``benchmarks/harness/reference_nemotronh.py``, which imports nothing of
``mlrun_tpu``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.reference_nemotronh import *  # noqa: E402,F401,F403


def fields_of(config) -> dict:
    """The reference's fields of a program config (``NemotronHConfig``)."""
    names = ("vocab_size", "n_layers", "pattern", "embed_dim", "n_heads",
             "n_kv_heads", "head_dim", "ssm_heads", "ssm_head_dim",
             "ssm_groups", "ssm_state", "conv_kernel", "time_step_min",
             "time_step_max", "time_step_floor", "n_experts", "top_k",
             "expert_dim", "shared_dim", "routed_scale", "norm_topk",
             "experts_held", "norm_eps")
    return {name: getattr(config, name) for name in names}
