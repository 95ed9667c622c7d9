from .noisy_update import bits_to_normal, threefry2x32
from .ops import (clip_accum, flat_clip_accum, ghost_norm_dense,
                  interpret_mode, noisy_sgd_update, tree_clip_accum,
                  tree_noisy_update)

__all__ = ["bits_to_normal", "clip_accum", "flat_clip_accum",
           "ghost_norm_dense", "interpret_mode", "noisy_sgd_update",
           "threefry2x32", "tree_clip_accum", "tree_noisy_update"]
