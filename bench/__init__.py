"""Serving benchmark of the StreamTensor engine on TPU (see PERF.md)."""
