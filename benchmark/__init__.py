"""The benchmark of deepspeed_tpu: see BENCHMARK.json and PERF.md."""
