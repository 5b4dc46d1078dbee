from repro.utils.pytree import (
    axes_paths,
    tree_paths,
    tree_bytes,
    tree_param_count,
    path_str,
)

__all__ = [
    "axes_paths",
    "tree_paths",
    "tree_bytes",
    "tree_param_count",
    "path_str",
]
