"""The benchmark's generator and plain reference (no das_tpu, no jax)."""
