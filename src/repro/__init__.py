"""Reproduction of Chen & Marculescu, arXiv:1712.03209, grown into a JAX
heterogeneous-fleet scheduler with a device target solver, what-if engines
and a serving path (see ROADMAP.md)."""
