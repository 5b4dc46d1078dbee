"""Chip benchmark of the elastic trainer; ``run.py`` is the entry point."""
