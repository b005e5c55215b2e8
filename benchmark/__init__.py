"""The benchmark: the yardstick later PRs are measured with (see PERF.md)."""
