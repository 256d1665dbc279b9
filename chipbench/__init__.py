"""Chip benchmark of the DHLP label-propagation system (see PERF.md)."""
