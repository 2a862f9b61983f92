"""Model zoo: TPU-first transformer family.

The reference keeps models inside user frameworks (torch modules in Train
examples, small MLP/CNN catalogs in RLlib — `rllib/models/catalog.py`); here
decoder-only transformers are framework citizens: pure-JAX pytrees with
logical sharding axes on every parameter, scan-over-layers bodies, and
Pallas attention (`ray_tpu.ops`).
"""

from .generate import (  # noqa: F401
    CacheTraffic,
    cache_gather_slot,
    cache_insert_slot,
    chunk_room,
    decode_step,
    decode_step_slots,
    generate,
    init_kv_cache,
    init_slot_cache,
    prefill,
    prefill_chunk,
    prefill_chunk_jit,
    prefill_chunked,
    prefill_lanes,
    prefill_lanes_jit,
    prefix_holds,
)
from .transformer import (  # noqa: F401
    TransformerConfig,
    init_params,
    forward,
    forward_with_aux,
    lm_loss,
    make_train_step,
    count_params,
    flops_per_token,
    decode_flops_per_token,
    engine_flops_table,
)
from .vit import (  # noqa: F401
    ViTConfig,
    init_vit_params,
    make_vit_train_step,
    vit_forward,
    vit_loss,
)
