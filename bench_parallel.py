"""Parallelism-layer characterization on the virtual 8-device CPU mesh.

The VERDICT-r3 ask: even without multi-chip hardware, measure the
RELATIVE behavior of the parallel layer — PP bubble fraction vs
microbatch count, ring-vs-dense attention cost, EP all_to_all overhead —
so the next on-chip session has concrete predictions to check (the
reference's release/benchmarks publish the same style of scaling
tables).  Numbers here are CPU-mesh wall clock: collective cost models
ICI only in structure, not bandwidth, so the useful signal is the
TREND (bubble shrinking as 1/m, ring's overhead ratio, EP's dispatch
tax), not absolute ms.

Prints a markdown table + one JSON line; also writes
PARALLEL_BENCH.json for the round ledger.
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)          # compile + warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_pipeline():
    """Step time vs n_micro at pp=4: bubble fraction (S-1)/(m+S-1)
    should show as wall-clock shrinking toward the m→inf asymptote."""
    from ray_tpu.models import (TransformerConfig, forward_with_aux,
                                init_params)
    from ray_tpu.parallel import MeshSpec, create_mesh

    rows = []
    stages = 4
    mesh = create_mesh(MeshSpec(dp=1, fsdp=1, pp=stages, sp=1, tp=2))
    for m in (1, 2, 4, 8, 16):
        cfg = TransformerConfig.tiny(
            n_layers=8, d_model=128, max_seq_len=64,
            attention_impl="reference", dtype=jnp.float32,
            pp_stages=stages, pp_microbatches=m)
        params, _ = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 64), 0,
                                    cfg.vocab_size)
        with jax.set_mesh(mesh):
            fwd = jax.jit(lambda p, t, _cfg=cfg:
                          forward_with_aux(p, t, _cfg)[0])
            ms = _time(fwd, params, tokens) * 1e3
        bubble = (stages - 1) / (m + stages - 1)
        rows.append({"n_micro": m, "ms": round(ms, 1),
                     "bubble_theory": round(bubble, 3)})
        print(f"pp4 n_micro={m:<3d} {ms:8.1f} ms   "
              f"theoretical bubble {bubble:.3f}", file=sys.stderr)
    return rows


def bench_ring_vs_dense():
    """Ring attention (sp=8) vs single-device dense attention at
    growing sequence length; ring's win on real hardware is memory
    (seq/8 per chip) — on the CPU mesh the signal is compute parity
    and the per-step ppermute tax."""
    from ray_tpu.ops.attention import reference_attention
    from ray_tpu.ops.ring_attention import make_ring_attention
    from ray_tpu.parallel import MeshSpec, create_mesh

    mesh = create_mesh(MeshSpec(dp=1, fsdp=1, pp=1, sp=8, tp=1))
    ring = make_ring_attention(mesh)
    dense = jax.jit(lambda q, k, v:
                    reference_attention(q, k, v, causal=True))
    rows = []
    for seq in (1024, 4096, 8192):
        ks = jax.random.split(jax.random.PRNGKey(seq), 3)
        q, k, v = (jax.random.normal(kk, (1, seq, 8, 64), jnp.float32)
                   for kk in ks)
        t_ring = _time(ring, q, k, v, iters=3) * 1e3
        t_dense = _time(dense, q, k, v, iters=3) * 1e3
        rows.append({"seq": seq, "ring_ms": round(t_ring, 1),
                     "dense_ms": round(t_dense, 1),
                     "ratio": round(t_ring / t_dense, 2)})
        print(f"seq={seq:<6d} ring {t_ring:8.1f} ms   dense "
              f"{t_dense:8.1f} ms   ratio {t_ring / t_dense:.2f}",
              file=sys.stderr)
    return rows


def bench_moe_ep():
    """MoE ffn with experts sharded over ep=8 (GSPMD inserts
    all_to_alls) vs the SAME computation fully replicated: the delta is
    the dispatch/combine + all_to_all tax."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.moe import moe_ffn
    from ray_tpu.parallel import MeshSpec, create_mesh

    mesh = create_mesh(MeshSpec(dp=1, fsdp=1, pp=1, sp=1, tp=1, ep=8))
    b, s, d, f, E = 8, 256, 128, 512, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    y = jax.random.normal(ks[0], (b, s, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, E)) * 0.1
    w_in = jax.random.normal(ks[2], (E, d, f)) * 0.1
    w_out = jax.random.normal(ks[3], (E, f, d)) * 0.1
    w_gate = jax.random.normal(ks[4], (E, d, f)) * 0.1

    def run(y, router, w_in, w_out, w_gate):
        out, _ = moe_ffn(y, router, w_in, w_out, w_gate, top_k=2,
                         capacity_factor=2.0)
        return out

    t_repl = _time(jax.jit(run), y, router, w_in, w_out, w_gate,
                   iters=3) * 1e3
    with jax.set_mesh(mesh):
        ep = NamedSharding(mesh, P("ep"))
        w_in_s, w_out_s, w_gate_s = (jax.device_put(w, ep)
                                     for w in (w_in, w_out, w_gate))
        t_ep = _time(jax.jit(run), y, router, w_in_s, w_out_s,
                     w_gate_s, iters=3) * 1e3
    print(f"moe E=8 top2: replicated {t_repl:.1f} ms   ep-sharded "
          f"{t_ep:.1f} ms   ratio {t_ep / t_repl:.2f}",
          file=sys.stderr)
    return {"replicated_ms": round(t_repl, 1),
            "ep8_ms": round(t_ep, 1),
            "ratio": round(t_ep / t_repl, 2)}


def main():
    result = {
        "metric": "parallel_layer_characterization",
        "value": 1.0, "unit": "suite", "vs_baseline": 1.0,
        "detail": {
            "mesh": "8-device virtual CPU",
            "pipeline_pp4": bench_pipeline(),
            "ring_vs_dense_sp8": bench_ring_vs_dense(),
            "moe_ep8": bench_moe_ep(),
        },
    }
    print(json.dumps(result))
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(
                __file__)), "PARALLEL_BENCH.json"), "w") as f:
            json.dump(result, f, indent=1)
    except OSError:
        pass


if __name__ == "__main__":
    main()
