"""SSE token streaming through the HTTP proxy (reference capability:
Serve's StreamingResponse path): the proxy drives a decode-session
replica and emits one event per token on a single connection."""

import json

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def streaming_app():
    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    serve.start()

    @serve.deployment(max_concurrent_queries=4)
    class Gen:
        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            self.core = DecodeSessionCore(
                TransformerConfig.tiny(max_seq_len=64,
                                       attention_impl="reference",
                                       dtype=jnp.float32), max_len=64)

        def __call__(self, req):
            return self.core.handle(req)

    @serve.deployment
    class Closed:
        """Speaks the session protocol and admits nobody."""

        def __call__(self, req):
            return {"error": f"closed to {req['op']}"}

    serve.run(Gen.bind(), name="gen")
    serve.run(Closed.bind(), name="closed")
    yield serve.api.http_address()
    serve.shutdown()
    ray_tpu.shutdown()


def _sse_events(resp):
    events = []
    for line in resp.iter_lines():
        if line.startswith(b"data: "):
            body = line[len(b"data: "):]
            if body == b"[DONE]":
                events.append("DONE")
            else:
                events.append(json.loads(body))
    return events


def test_stream_emits_token_events(streaming_app):
    import requests
    addr = streaming_app
    with requests.post(f"{addr}/gen/stream",
                       json={"prompt": [5, 6, 7],
                             "max_new_tokens": 6},
                       stream=True, timeout=180) as r:
        assert r.status_code == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        events = _sse_events(r)
    assert events[-1] == "DONE"
    toks = [e for e in events[:-1] if isinstance(e, dict)]
    assert len(toks) == 6
    assert "sid" in toks[0]
    assert all("token" in e for e in toks)

    # the proxy released the session at stream end: the sid is gone
    sid = toks[0]["sid"]
    out = requests.post(f"{addr}/gen",
                        json={"op": "next", "sid": sid},
                        timeout=30).json()
    assert "error" in out


def test_stream_of_a_refused_start_ends_with_the_error_in_band(
        streaming_app):
    import requests
    with requests.post(f"{streaming_app}/closed/stream",
                       json={"prompt": [5, 6, 7], "max_new_tokens": 6},
                       stream=True, timeout=60) as r:
        assert r.status_code == 200
        events = _sse_events(r)
    assert events == [{"error": "closed to start"}, "DONE"]


@pytest.mark.parametrize("body", [
    {"data": "plain"},                                  # no JSON object
    {"json": {"prompt": [[1, 2], [3, 4]]}},             # a group of two
])
def test_stream_rejects_non_json(streaming_app, body):
    import requests
    r = requests.post(f"{streaming_app}/gen/stream", timeout=30, **body)
    assert r.status_code == 400


def test_non_streaming_path_still_works(streaming_app):
    import requests
    out = requests.post(f"{streaming_app}/gen",
                        json={"op": "start", "prompt": [[1, 2, 3]]},
                        timeout=120).json()
    assert "sid" in out
    requests.post(f"{streaming_app}/gen",
                  json={"op": "end", "sid": out["sid"]}, timeout=30)
