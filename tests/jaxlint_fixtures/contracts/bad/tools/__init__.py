"""Marks the mini-project root: ``find_project_root`` wants ``docs/`` and ``tools/``."""
