"""The chip benchmark of cylon_tpu: harness, configurations, traffic
mixes and per-layer metric readers (see ``BENCHMARK.json``)."""
