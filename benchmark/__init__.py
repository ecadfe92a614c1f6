"""das_tpu's benchmark: BENCHMARK.json at the root names what is here."""
