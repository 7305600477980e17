"""Launchers: production mesh, multi-pod dry-run, training driver, and
the persistent compile cache that entry points switch on.

Every module here is import-safe: ``dryrun`` claims its 512 placeholder
CPU devices in its ``main``, not at import.
"""
from .compile_cache import enable_compile_cache
from .mesh import (CHIP_HBM_BYTES, HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                   make_production_mesh, mesh_chips)
from .shapes import (SHAPES, TRAIN_MICROBATCH, ShapeSpec, cache_len,
                     input_specs, shape_config, skip_reason)

__all__ = ["enable_compile_cache", "CHIP_HBM_BYTES", "HBM_BW", "ICI_BW",
           "PEAK_FLOPS_BF16", "make_production_mesh", "mesh_chips", "SHAPES",
           "TRAIN_MICROBATCH", "ShapeSpec", "cache_len", "input_specs",
           "shape_config", "skip_reason"]
