"""Chip benchmark of the phased-lazy search engine (see PERF.md)."""
