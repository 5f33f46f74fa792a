"""Chip benchmark of the FDN admission path (see fdnbench/README.md)."""
