"""Chip benchmark of the CNNSelect served path (see PERF.md)."""
