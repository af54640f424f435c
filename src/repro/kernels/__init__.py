"""Pallas-TPU kernels; see ops.py for the jit'd public wrappers."""
