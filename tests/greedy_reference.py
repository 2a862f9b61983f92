"""The serve tests' greedy oracle: `models.generate` — whole-prompt
`prefill` and scanned `decode_step`s in one program — which shares no
code with the chunk program, the slot programs or the engine."""

import numpy as np


def greedy_stream(cfg, prompt, n, *, max_len, params=None, seed=0):
    """The first ``n`` greedy tokens after ``prompt`` (a list of ints)
    from a cache of ``max_len`` positions, with ``params`` or, as a
    `DecodeSessionCore(seed=)` draws them, ``init_params(PRNGKey(seed))``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import generate, init_params
    if params is None:
        params, _ = init_params(jax.random.PRNGKey(seed), cfg)
    out = generate(params, jnp.asarray([prompt], jnp.int32), cfg=cfg,
                   max_new_tokens=n, max_len=max_len)
    return np.asarray(out)[0].tolist()
