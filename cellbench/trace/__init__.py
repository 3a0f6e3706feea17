"""Reduction of a profiler trace and the program's host spans to numbers."""
