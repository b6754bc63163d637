from repro.common.utils import (
    REPO_ROOT,
    Timer,
    enable_compile_cache,
    human_bytes,
    human_flops,
    human_num,
    pytree_bytes,
    pytree_num_params,
    tree_struct_str,
)

__all__ = [
    "REPO_ROOT",
    "Timer",
    "enable_compile_cache",
    "human_bytes",
    "human_flops",
    "human_num",
    "pytree_bytes",
    "pytree_num_params",
    "tree_struct_str",
]
