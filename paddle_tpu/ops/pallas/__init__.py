"""Pallas TPU kernels (SURVEY §6.6): hand-fused hot ops XLA won't fuse.

Tests run them with interpret=True on CPU; on a TPU backend the same
kernels compile to Mosaic.
"""
from .flash_attention import flash_attention  # noqa: F401
from .lstm_cell import gru_scan, lstm_scan  # noqa: F401
from .paged_attention import paged_attention  # noqa: F401
from .table_update import (sparse_apply_adagrad,  # noqa: F401
                           sparse_apply_adam, sparse_apply_mode,
                           sparse_apply_sgd)

__all__ = ['flash_attention', 'paged_attention',
           'lstm_scan', 'gru_scan',
           'sparse_apply_sgd', 'sparse_apply_adagrad',
           'sparse_apply_adam', 'sparse_apply_mode']
