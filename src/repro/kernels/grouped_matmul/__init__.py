from .ops import (ExpertLayout, bcq_grouped_matmul, expert_layout,
                  gather_rows, row_tile, scatter_rows)
from .bcq_grouped_matmul import bcq_grouped_matmul_tiled
from . import ref

__all__ = ["ExpertLayout", "bcq_grouped_matmul", "bcq_grouped_matmul_tiled",
           "expert_layout", "gather_rows", "row_tile", "scatter_rows", "ref"]
