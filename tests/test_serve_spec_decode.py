"""Chunked-prefill admission + speculative decoding (PR-6).

The continuous-batching engine's two model-side optimisations
(serve/decode_session.py): a joining session's prompt is consumed in
fixed-shape chunk programs BETWEEN shared decode steps (admission,
failover resume, and legacy chunked prefill share ONE compiled chunk
program set), and a draft model proposes k tokens per iteration that
one batched k-wide target forward verifies exactly (greedy acceptance
is exact-match, so token streams stay byte-identical to plain decode).
Tier-1, CPU, tiny model.
"""

import time

import pytest

from greedy_reference import greedy_stream
from ray_tpu.core.config import GlobalConfig


def _tiny_cfg(max_seq_len=64, **kw):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    return TransformerConfig.tiny(max_seq_len=max_seq_len,
                                  attention_impl="reference",
                                  dtype=jnp.float32, **kw)


def _ref_streams(cfg, prompts, want, seed=3, max_len=64):
    """Sequential batch-1 greedy references."""
    return [greedy_stream(cfg, p, want, max_len=max_len, seed=seed)
            for p in prompts]


def _drain(core, sid, toks, want):
    while len(toks) < want:
        out = core.handle({"op": "next_chunk", "sid": sid,
                           "max_tokens": want - len(toks)})
        assert "error" not in out, out
        toks += out["tokens"]
    return toks


# ------------------------------------------------------- model-level units

def test_verify_step_slots_is_exact_greedy_verification():
    """The k-wide verify program IS the greedy chain: correct proposals
    are all accepted, a wrong proposal truncates acceptance exactly at
    the divergence, and the emitted tokens equal the sequential
    decode-step chain either way (with per-slot pos, garbage slots
    around the live one)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (cache_insert_slot, decode_step,
                                init_kv_cache, init_params,
                                init_slot_cache, prefill,
                                verify_step_slots)
    cfg = _tiny_cfg()
    params, _ = init_params(jax.random.PRNGKey(3), cfg)
    prompt = jnp.asarray([[7, 11, 13, 17, 19]], jnp.int32)
    cache = init_kv_cache(cfg, 1, 64)
    logits, cache = prefill(params, prompt, cfg, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    # sequential greedy chain: the ground truth the verifier must match
    chain = [int(tok[0])]
    c1 = cache
    for _ in range(4):
        l1, c1 = decode_step(params, jnp.asarray([chain[-1]], jnp.int32),
                             c1, cfg)
        chain.append(int(jnp.argmax(l1, -1)[0]))

    def fresh_slots():
        sc = init_slot_cache(cfg, 3, 64)
        return cache_insert_slot(sc, cache, jnp.int32(1))

    active = jnp.asarray([False, True, False])
    k = 4  # verify width: last_tok + 3 proposals

    # (a) perfect proposals -> all k accepted, greedy == chain
    fed = jnp.zeros((3, k), jnp.int32).at[1].set(
        jnp.asarray(chain[:k], jnp.int32))
    props = fed[:, 1:]
    g, acc, sc = verify_step_slots(params, fed, props, fresh_slots(),
                                   active, cfg)
    assert int(acc[1]) == k
    assert [int(x) for x in g[1]] == chain[1:k + 1]
    assert int(sc["pos"][1]) == 5 + k
    assert int(sc["pos"][0]) == 0      # inactive slots never advance

    # (b) proposal 2 wrong -> exactly 2 tokens emitted (1 accepted
    # draft + the correction), and the correction is the true token
    bad = list(chain[:k])
    bad[2] = (bad[2] + 1) % cfg.vocab_size
    fed_b = jnp.zeros((3, k), jnp.int32).at[1].set(
        jnp.asarray(bad, jnp.int32))
    g, acc, sc = verify_step_slots(params, fed_b, fed_b[:, 1:],
                                   fresh_slots(), active, cfg)
    assert int(acc[1]) == 2
    assert [int(x) for x in g[1][:2]] == chain[1:3]
    assert int(sc["pos"][1]) == 5 + 2

    # (c) continuing the cache after a partial acceptance stays on the
    # true chain: rejected-suffix K/V writes must be invisible
    fed_c = jnp.zeros((3, k), jnp.int32).at[1, 0].set(chain[2])
    g2, acc2, _ = verify_step_slots(params, fed_c, fed_c[:, 1:], sc,
                                    active, cfg)
    assert int(g2[1][0]) == chain[3]


def test_draft_propose_slots_matches_eager_chain():
    """One scanned dispatch proposes the same k tokens as k eager slot
    decode steps (the whole point: k-for-1 dispatch amortization with
    zero behavior change)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (cache_insert_slot, decode_step_slots,
                                draft_propose_slots, init_kv_cache,
                                init_params, init_slot_cache, prefill)
    cfg = _tiny_cfg()
    params, _ = init_params(jax.random.PRNGKey(5), cfg)
    prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    cache = init_kv_cache(cfg, 1, 64)
    logits, cache = prefill(params, prompt, cfg, cache)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)[0]
    sc = cache_insert_slot(init_slot_cache(cfg, 2, 64), cache,
                           jnp.int32(0))
    active = jnp.asarray([True, False])
    toks = jnp.asarray([tok0, 0], jnp.int32)

    props, pc = draft_propose_slots(params, toks, sc, active, cfg, 3)
    ref, rc, t = [], sc, toks
    for _ in range(3):
        l, rc = decode_step_slots(params, t, rc, active, cfg)
        t = jnp.where(active, jnp.argmax(l, -1).astype(jnp.int32), t)
        ref.append(int(t[0]))
    assert [int(x) for x in props[0]] == ref
    assert int(pc["pos"][0]) == int(rc["pos"][0]) == 8


# -------------------------------------------------- chunked-prefill admission

def test_chunked_admission_token_parity_across_chunk_boundaries():
    """Acceptance: chunked admission emits byte-identical streams for
    prompt lengths straddling the chunk boundary (below, exact, above,
    multiple), including a mid-stream join under load — and the whole
    run compiles the one prefill chunk shape."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    want = 10
    prompts = [[5, 6, 7], [1, 2, 3, 4], [9, 8, 7, 6, 5],
               [3] * 8, [4] * 9]   # chunk=4: 3 | 4 | 5 | 8 | 9
    refs = _ref_streams(cfg, prompts, want)
    core = DecodeSessionCore(
        cfg, max_len=64, seed=3,
        engine=DecodeEngineConfig(prefill_chunk_tokens=4))
    # staggered: s0 streams alone, s1..s4 join while s0 is mid-stream
    r0 = core.handle({"op": "start", "prompt": prompts[0]})
    s0 = _drain(core, r0["sid"], list(r0["token"]), 5)
    mids = [core.handle({"op": "start", "prompt": p})
            for p in prompts[1:]]
    outs = [_drain(core, r["sid"], list(r["token"]), want)
            for r in mids]
    s0 = _drain(core, r0["sid"], s0, want)
    for r in (r0, *mids):
        core.handle({"op": "end", "sid": r["sid"]})
    assert [s0] + outs == refs
    st = core.handle({"op": "stats"})["engine"]
    assert st["prefill_chunks"] >= 5
    pf_shapes = [s for s in st["program_shapes"]
                 if s.startswith("prefill_chunk")]
    assert pf_shapes == ["prefill_chunk:1x4"], (
        f"admission must reuse the ONE fixed chunk shape (a remainder "
        f"is padded into it), compiled: {pf_shapes}")
    # 3 | 4 | 5 | 8 | 9 tokens: 1 + 1 + 2 + 2 + 3 programs, of which
    # those of 3, 5 and 9 end in a padded remainder
    assert (st["prefill_chunks"], st["prefill_tails"],
            st["prefill_pad_tokens"]) == (9, 3, 1 + 3 + 3)
    assert "distinct_program_shapes" in st


def test_chunked_admission_and_resume_share_program_shapes():
    """Satellite: a failover resume after chunked admissions adds NO
    new prefill program shape — admission and resume walk the same
    fixed-shape chunk programs, so resumes can never compile-storm."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    want = 10
    prompt = [5, 6, 7, 8, 9]
    (ref,) = _ref_streams(cfg, [prompt], want)
    core = DecodeSessionCore(
        cfg, max_len=64, seed=3,
        engine=DecodeEngineConfig(prefill_chunk_tokens=4))
    r = core.handle({"op": "start", "prompt": prompt})
    _drain(core, r["sid"], list(r["token"]), want)
    core.handle({"op": "end", "sid": r["sid"]})
    shapes_before = set(
        core.handle({"op": "stats"})["engine"]["program_shapes"])
    # resume mid-stream at an awkward cut (prefix length 5+7=12: three
    # chunk blocks; the admission's 5 were one block and a padded one)
    rr = core.handle({"op": "resume", "prompt": prompt,
                      "generated": ref[:7]})
    assert rr["seq"] == 7
    toks = ref[:7] + list(rr["token"])
    toks = _drain(core, rr["sid"], toks, want)
    assert toks == ref
    core.handle({"op": "end", "sid": rr["sid"]})
    shapes_after = set(
        core.handle({"op": "stats"})["engine"]["program_shapes"])
    new = {s for s in shapes_after - shapes_before
           if s.startswith("prefill_chunk")}
    assert not new, f"resume compiled new prefill shapes: {new}"


# ------------------------------------------------------ speculative decoding

def test_spec_decode_token_parity_shared_draft():
    """Acceptance: speculative decoding with a weight-shared draft is
    byte-identical to plain greedy decode, accepts (nearly) every
    proposal, and takes measurably fewer engine iterations per token."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    want = 16
    prompts = [[5, 6, 7], list(range(10)), [9] * 6]
    refs = _ref_streams(cfg, prompts, want)
    core = DecodeSessionCore(
        cfg, max_len=64, seed=3,
        engine=DecodeEngineConfig(spec_draft="shared", spec_k=4))
    rs = [core.handle({"op": "start", "prompt": p}) for p in prompts]
    outs = [_drain(core, r["sid"], list(r["token"]), want) for r in rs]
    for r in rs:
        core.handle({"op": "end", "sid": r["sid"]})
    assert outs == refs
    st = core.handle({"op": "stats"})["engine"]
    spec = st["spec"]
    assert spec["enabled"] and not spec["disabled"]
    assert spec["proposed"] > 0
    assert spec["acceptance"] >= 0.9, spec
    # dispatch amortization: far fewer iterations than tokens decoded
    assert st["steps"] * 2 <= st["tokens"], st


def test_spec_decode_token_parity_random_draft():
    """The core guarantee: an arbitrarily BAD draft (fresh random
    weights — near-zero acceptance) slows the stream but can never
    change it.  Greedy verification emits only the target's own chain."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    want = 12
    prompts = [[5, 6, 7], [1, 2]]
    refs = _ref_streams(cfg, prompts, want)
    draft_cfg = _tiny_cfg(n_layers=1)   # smaller AND untrained
    core = DecodeSessionCore(
        cfg, max_len=64, seed=3,
        engine=DecodeEngineConfig(spec_draft=draft_cfg, spec_k=3))
    rs = [core.handle({"op": "start", "prompt": p}) for p in prompts]
    outs = [_drain(core, r["sid"], list(r["token"]), want) for r in rs]
    for r in rs:
        core.handle({"op": "end", "sid": r["sid"]})
    assert outs == refs
    spec = core.handle({"op": "stats"})["engine"]["spec"]
    assert spec["proposed"] > 0 and spec["fallbacks"] == 0


def test_resume_into_speculating_engine():
    """PR-5 failover extension: a journal replay resumed INTO an engine
    that speculates (chunked teacher-forced admission + spec decode on
    the resumed slot) continues the stream byte-identically, for cuts
    landing mid-chunk and mid-speculation-window."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    want = 16
    prompt = [5, 6, 7]
    (ref,) = _ref_streams(cfg, [prompt], want)
    for cut in (1, 6, 11):
        fresh = DecodeSessionCore(
            cfg, max_len=64, seed=3,
            engine=DecodeEngineConfig(prefill_chunk_tokens=4,
                                      spec_draft="shared", spec_k=4))
        rr = fresh.handle({"op": "resume", "prompt": prompt,
                           "generated": ref[:cut]})
        assert "error" not in rr, rr
        assert rr["seq"] == cut
        toks = ref[:cut] + list(rr["token"])
        toks = _drain(fresh, rr["sid"], toks, want)
        assert toks == ref, f"cut={cut}: {toks} != {ref}"
        fresh.handle({"op": "end", "sid": rr["sid"]})
        fresh.engine.shutdown()


# ------------------------------------------------------------------- chaos

@pytest.fixture
def chaos_cleanup():
    import os

    from ray_tpu.util import fault_injection as fi
    yield
    fi.disarm()
    GlobalConfig.update({"chaos_plan": ""})
    os.environ.pop("RAY_TPU_CHAOS_PLAN", None)


def test_chaos_spec_verify_degrades_to_plain_decode(chaos_cleanup):
    """Chaos site serve.spec_verify: a persistently-failing draft/verify
    path falls back to a plain decode step each iteration and disables
    speculation after spec_fail_disable strikes — the stream stays
    byte-identical throughout (degrade, never corrupt)."""
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    from ray_tpu.util import fault_injection as fi
    cfg = _tiny_cfg()
    want = 16
    prompt = [5, 6, 7]
    (ref,) = _ref_streams(cfg, [prompt], want)
    fi.arm([{"site": "serve.spec_verify", "action": "error"}])
    core = DecodeSessionCore(
        cfg, max_len=64, seed=3,
        engine=DecodeEngineConfig(spec_draft="shared", spec_k=4,
                                  spec_fail_disable=3))
    r = core.handle({"op": "start", "prompt": prompt})
    toks = _drain(core, r["sid"], list(r["token"]), want)
    core.handle({"op": "end", "sid": r["sid"]})
    assert toks == ref, "a draft fault must never corrupt the stream"
    spec = core.handle({"op": "stats"})["engine"]["spec"]
    assert spec["fallbacks"] >= 3
    assert spec["disabled"], spec
    # one-shot fault: a single failed iteration degrades that step only
    fi.disarm()
    fi.arm([{"site": "serve.spec_verify", "action": "error",
             "match": {"nth": 2}}])
    core2 = DecodeSessionCore(
        cfg, max_len=64, seed=3,
        engine=DecodeEngineConfig(spec_draft="shared", spec_k=4))
    r = core2.handle({"op": "start", "prompt": prompt})
    toks = _drain(core2, r["sid"], list(r["token"]), want)
    core2.handle({"op": "end", "sid": r["sid"]})
    assert toks == ref
    spec = core2.handle({"op": "stats"})["engine"]["spec"]
    assert spec["fallbacks"] == 1 and not spec["disabled"], spec


# ------------------------------------------------------------ observability

def test_prefill_and_spec_metrics_exported():
    """Observability satellite: chunk/spec counters land in the
    process registry and engine_stats carries the acceptance ratio."""
    from ray_tpu import metrics
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    core = DecodeSessionCore(
        _tiny_cfg(), max_len=64, seed=1,
        engine=DecodeEngineConfig(spec_draft="shared", spec_k=4))
    r = core.handle({"op": "start", "prompt": [1, 2, 3]})
    out = core.handle({"op": "next_chunk", "sid": r["sid"],
                       "max_tokens": 8})
    assert len(out["tokens"]) >= 1
    core.handle({"op": "end", "sid": r["sid"]})
    deadline = time.monotonic() + 10
    text = ""
    while time.monotonic() < deadline:
        text = metrics.prometheus_text()
        if "ray_tpu_serve_spec_tokens_accepted_total" in text:
            break
        time.sleep(0.1)
    assert "ray_tpu_serve_prefill_chunks_total" in text
    assert "ray_tpu_serve_spec_tokens_proposed_total" in text
    assert "ray_tpu_serve_spec_tokens_accepted_total" in text
    assert "ray_tpu_serve_spec_acceptance_ratio" in text
    spec = core.handle({"op": "stats"})["engine"]["spec"]
    assert spec["acceptance"] is not None
