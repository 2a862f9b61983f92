"""The benchmark of ray_tpu: cells, metrics and the yardstick they are
measured with.  See BENCHMARK.json at the root and PERF.md."""
