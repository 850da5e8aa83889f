from .llama import (  # noqa: F401
    LlamaConfig,
    forward,
    init_permutation_params,
    llama3_1b,
    llama3_8b,
    llama3_70b,
    loss_fn,
    param_shapes,
    permutation_pair,
    tiny_llama,
)
from .lora import (  # noqa: F401
    init_lora,
    init_lora_nonzero,
    lora_param_count,
    merge_lora,
)
from .bert import (  # noqa: F401
    BertConfig,
    bert_base,
    classification_loss,
    classify,
    encode,
    mlm_logits,
    mlm_loss,
    tiny_bert,
)
from .moe import (  # noqa: F401
    MoEConfig,
    SdarConfig,
    make_moe_rules,
    mixtral_8x7b_like,
    tiny_moe,
    tiny_sdar,
)
from .xing4 import Xing4Config, tiny_xing4, xing4_29b_a4b  # noqa: F401
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    nemotron_3_nano_30b_a3b,
    tiny_nemotron_h,
)
from . import llama as _llama, moe as _moe, nemotron_h as _nemotron_h, \
    xing4 as _xing4


def init_params(config, key):
    """The weights that the config's own module defines, by its recipe:
    ``models/xing4.py`` for the latent-attention family,
    ``models/nemotron_h.py`` for the one with state-space layers,
    ``models/moe.py`` for any other config with experts,
    ``models/llama.py`` for the dense decoder."""
    if isinstance(config, Xing4Config):
        module = _xing4
    elif isinstance(config, NemotronHConfig):
        module = _nemotron_h
    else:
        module = _moe if isinstance(config, MoEConfig) else _llama
    return module.init_params(config, key)

from . import vit  # noqa: F401  (vit.classify/encode stay namespaced —
# bert exports the same verb names at package level)
from .vit import ViTConfig, tiny_vit, vit_b16, vit_l16  # noqa: F401
from . import t5  # noqa: F401  (t5.encode/decode stay namespaced)
from .t5 import T5Config, t5_base, t5_large, tiny_t5  # noqa: F401
