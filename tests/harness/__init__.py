"""Whole-cluster harnesses that several test files drive (no tests of their own)."""
