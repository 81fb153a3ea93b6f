"""Reductions from traces to numbers."""
