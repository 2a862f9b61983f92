"""Do the program's names reach a profiler trace?

Run on the chip, in one process that holds it (the parent of nothing):

    python3 -m perfbench.tools.scope_probe [--xplane <file.xplane.pb> ...]

It jits one small function that holds a `jax.named_scope` around a scatter
into a cache and the program's flash attention forward kernel (whose
`pallas_call` carries ``name=``), traces three executions, and reports for
each of the names where the trace has it: in the raw bytes of the
``.xplane.pb`` at all, in an event's name, in an event's stats, in a plane's
or line's name or stats.  With ``--xplane`` it makes the same report for
traces that are already there (a cell's ``--trace 1`` run leaves its trace
under ``.perfbench_out/<cell>/trace``).  The result goes to stdout and to
``chiprun_out/scope_probe.json``.  PERF.md section 7 holds what it found.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

NAMES = ("kv_cache_update", "flash_attention_fwd", "probe_scope")


def where(path: str, names=NAMES) -> dict:
    """For each name, the places of the trace that mention it."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_file(path)
    out = {n: {"in_file_bytes": raw.count(n.encode()), "event_names": 0,
               "event_stats": 0, "plane_or_line": [], "examples": []}
           for n in names}

    def note(n, kind, text):
        out[n][kind] += 1
        if len(out[n]["examples"]) < 3:
            out[n]["examples"].append(f"{kind}: {text[:400]}")

    for plane in data.planes:
        meta = [plane.name] + [f"{k}={v}" for k, v in plane.stats]
        for line in plane.lines:
            meta.append(f"{plane.name}/{line.name}")
            for e in line.events:
                stats = [f"{k}={v}" for k, v in e.stats]
                for n in names:
                    if n in e.name:
                        note(n, "event_names", f"{plane.name}/{line.name}: "
                                               f"{e.name}")
                    for s in stats:
                        if n in s:
                            note(n, "event_stats",
                                 f"{plane.name}/{line.name}: {e.name[:80]} "
                                 f"[{s}]")
        for n in names:
            out[n]["plane_or_line"] += [m[:200] for m in meta if n in m]
    return {"file": path, "bytes": len(raw),
            "planes": [p.name for p in data.planes], "names": out}


def record(out_dir: str):
    """Trace three executions of the probe program; returns the trace file
    and how often the compiled HLO text mentions each name."""
    import jax
    import jax.numpy as jnp

    from perfbench import xplane
    from ray_tpu.ops.flash_attention import flash_attention

    @jax.jit
    def program(cache, new, pos, q, k, v):
        with jax.named_scope("probe_scope"):
            with jax.named_scope("kv_cache_update"):
                cache = cache.at[jnp.arange(cache.shape[0]), pos].set(new)
            o = flash_attention(q, k, v, causal=True)
        return cache, o

    key = jax.random.PRNGKey(0)
    cache = jnp.zeros((8, 1024, 16, 64), jnp.bfloat16)
    new = jnp.ones((8, 16, 64), jnp.bfloat16)
    pos = jnp.arange(8, dtype=jnp.int32) * 3
    q = jax.random.normal(key, (2, 1024, 16, 64), jnp.bfloat16)
    jax.block_until_ready(program(cache, new, pos, q, q, q))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        for _ in range(3):
            jax.block_until_ready(program(cache, new, pos, q, q, q))
    finally:
        jax.profiler.stop_trace()
    text = program.lower(cache, new, pos, q, q, q).compile().as_text()
    hlo = {n: text.count(n) for n in NAMES}
    return xplane.find(out_dir), hlo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m perfbench.tools.scope_probe")
    ap.add_argument("--xplane", action="append", default=[],
                    help="a trace that is already there (file or glob)")
    ap.add_argument("--no-record", action="store_true")
    args = ap.parse_args(argv)
    from perfbench import manifest as mf
    report = {}
    if not args.no_record:
        import jax
        d = jax.devices()[0]
        report["device"] = {"platform": d.platform, "kind": d.device_kind}
        path, hlo = record(os.path.join(mf.ROOT, ".perfbench_out",
                                        "scope_probe"))
        report["compiled_hlo_mentions"] = hlo
        report["probe"] = where(path)
    report["given"] = [where(p) for pat in args.xplane
                       for p in sorted(glob.glob(pat))]
    out = os.path.join(mf.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "scope_probe.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
