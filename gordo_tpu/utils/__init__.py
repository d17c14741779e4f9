"""
Small shared utilities (reference parity: gordo/util/__init__.py:1-3).
"""

from .utils import (
    capture_args,
    compile_cache_dir,
    compile_cache_dir_bytes,
    enable_compile_cache,
    replace_all_non_ascii_chars_with_default,
)
from . import atomic, disk_registry
from .compat import normalize_frequency

__all__ = [
    "capture_args",
    "compile_cache_dir",
    "compile_cache_dir_bytes",
    "enable_compile_cache",
    "replace_all_non_ascii_chars_with_default",
    "atomic",
    "disk_registry",
    "normalize_frequency",
]
