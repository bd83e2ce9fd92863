"""Stands where ``InferenceEngine._decode`` / ``._prefill`` stand while
``programs_through_scheduler.py`` runs: called from the engine's own line,
under the scheduler's own frames, it LOWERS the program for the described
chip instead of running it and hands back stand-in results. Its file is
registered as one of JAX's own (``source_info_util.register_exclusion``), so
the frame it adds is in no recorded call stack."""

import jax
import numpy as np
from jax._src import source_info_util

source_info_util.register_exclusion(__file__)


class Lowering:
    def __init__(self, jitted, device_sharding, found, name):
        self.jitted, self.dev = jitted, device_sharding
        self.found, self.name = found, name

    def _cache_size(self):
        return 1

    def __call__(self, *args):
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=self.dev), args)
        lowered = self.jitted.lower(*shapes)
        key = self.name if self.name == "decode" else \
            f"{self.name}/{args[2].shape[1]}"
        self.found.setdefault(key, lowered)
        out = lowered.out_info
        rest = [np.zeros(o.shape, o.dtype)
                for o in jax.tree_util.tree_leaves(out[1:])]
        return (args[1], *rest)       # the cache as it came, zeros behind
