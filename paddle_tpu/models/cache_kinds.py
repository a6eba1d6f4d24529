"""What a served model tells the decode engine about its cache.

``serving/decode.py`` sizes its block allocator, names and zeroes the
pools, and copies blocks without knowing what a token's cache row holds:
a model module's ``cache_kinds(cfg)`` gives, per layer, the pools that
layer keeps (K and V for ``models/gpt.py``, a token's row the keys of
all its heads side by side, ``[1, hidden]``; one latent pool for
``models/deepseek.py``).
"""

import collections

import numpy as np

import paddle_tpu.fluid as fluid


class CachePool(collections.namedtuple("CachePool", "prefix row dtype")):
    """One paged pool of one layer. ``row``: the two dims a token holds,
    ``[r0, r1]``; the pool var is ``[blocks, r0, block, r1]`` of
    ``dtype``. Both served models keep ``[1, width]``, one row a token:
    ``r1`` lies on the lanes, and a width that is a multiple of 128 is
    the device's own tiling, so scatter, gather and the paged kernels
    take the pool as it lies (a ``[heads, 64]`` row cost three copies of
    every pool a step)."""

    __slots__ = ()

    def name(self, blocks, block):
        """Pool geometry is part of the name: two pools of different
        shapes sharing one scope must never alias."""
        return "%s_n%dx%d" % (self.prefix, blocks, block)

    def shape(self, blocks, block):
        return [int(blocks), int(self.row[0]), int(block), int(self.row[1])]

    @property
    def bytes_per_token(self):
        return (int(self.row[0]) * int(self.row[1])
                * np.dtype(fluid.core.dtype_to_np(self.dtype)).itemsize)


def bytes_per_token(kinds):
    """Cache bytes one token costs over all layers and pools."""
    return sum(p.bytes_per_token for layer in kinds for p in layer)


def declare_pools(kinds, blocks, block):
    """Declare every pool var in the CURRENT main program (persistable, no
    initializer: the session zeroes them in the scope). -> per layer, the
    tuple of its pool vars."""
    main_block = fluid.default_main_program().global_block()
    return [
        tuple(main_block.create_var(
            name=p.name(blocks, block), shape=p.shape(blocks, block),
            dtype=p.dtype, persistable=True) for p in layer)
        for layer in kinds
    ]
