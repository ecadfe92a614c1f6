"""The benchmark's harness: what is general to every cell."""
