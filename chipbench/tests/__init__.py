"""Tests of the on-chip benchmark that run on the CPU at small sizes."""
