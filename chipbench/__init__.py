"""Chip benchmark of the GUM training system (see README.md)."""
