"""Launchers: production mesh, multi-pod dry-run, train/serve drivers."""

from .mesh import make_production_mesh, rules_for

__all__ = ["make_production_mesh", "rules_for"]
