"""Token-by-token serving: a stateful decode session on a Serve replica.

TTFT-style serving without waiting for the full completion: the replica
holds the KV cache between calls, so `start` pays one prefill and every
`next_token` call is a single decode step (the reference delegates this
to external engines; here it is the in-tree transformer runtime).
"""

import ray_tpu
from ray_tpu import serve


def main():
    ray_tpu.init(num_cpus=4)
    serve.start()

    @serve.deployment(max_concurrent_queries=4)
    class DecodeSession:
        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            # DecodeSessionCore compiles its engine's programs once per
            # replica and locks its tables (the replica runs threaded)
            self.core = DecodeSessionCore(
                TransformerConfig.tiny(max_seq_len=64,
                                       attention_impl="reference",
                                       dtype=jnp.float32), max_len=64)

        def __call__(self, req):
            return self.core.handle(req)

    handle = serve.run(DecodeSession.bind())
    out = handle.remote({"op": "start", "prompt": [[5, 6, 7]]}).result(
        timeout_s=180.0)
    sid = out["sid"]
    stream = [out["token"][0]]
    for _ in range(4):
        out = handle.remote({"op": "next", "sid": sid}).result(
            timeout_s=60.0)
        stream.append(out["token"][0])
    print("streamed tokens:", stream)
    assert len(stream) == 5
    print("EXAMPLE_OK serve_streaming_decode")
    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
