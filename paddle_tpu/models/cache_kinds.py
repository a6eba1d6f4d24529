"""What a served model tells the decode engine about what a sequence keeps.

``serving/decode.py`` sizes its block allocator, names and zeroes the
cache vars, and copies blocks without knowing what they hold: a model
module's ``cache_kinds(cfg)`` gives, per layer, the vars that layer keeps,
each of one of two kinds:

- ``CachePool``: a row a TOKEN, paged (K and V for ``models/gpt.py`` and
  for a softmax layer of ``models/solar_open2.py``, one latent pool for
  ``models/deepseek.py``);
- ``CacheState``: a row a SLOT, neither paged nor token-addressed (the
  recurrent state and the convolution tail of a delta-rule layer of
  ``models/solar_open2.py``). Row 0 is the SINK, the garbage target of
  every slot that is idle or between two prefill windows, as block 0 is
  for the pools; slot ``s`` keeps row ``s + 1``.
"""

import collections

import numpy as np

import paddle_tpu.fluid as fluid


def _itemsize(dtype):
    return np.dtype(fluid.core.dtype_to_np(dtype)).itemsize


class CachePool(collections.namedtuple("CachePool", "prefix row dtype")):
    """One paged pool of one layer. ``row``: the two dims a token holds,
    ``[r0, r1]``; the pool var is ``[blocks, r0, block, r1]`` of
    ``dtype``. The served models keep ``[1, width]``, one row a token:
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
        return int(self.row[0]) * int(self.row[1]) * _itemsize(self.dtype)


class CacheState(collections.namedtuple("CacheState", "prefix row dtype")):
    """One per-slot state of one layer. ``row``: the dims a slot holds;
    the var is ``[slots + 1, *row]`` of ``dtype``, row 0 the sink."""

    __slots__ = ()

    def name(self, slots):
        return "%s_s%d" % (self.prefix, slots)

    def shape(self, slots):
        return [int(slots) + 1] + [int(d) for d in self.row]

    @property
    def bytes_per_slot(self):
        return int(np.prod(self.row)) * _itemsize(self.dtype)


def pools(layer):
    """The paged pools among one layer's kinds."""
    return tuple(k for k in layer if isinstance(k, CachePool))


def states(layer):
    """The per-slot states among one layer's kinds."""
    return tuple(k for k in layer if isinstance(k, CacheState))


def bytes_per_token(kinds):
    """Paged cache bytes one token costs over all layers and pools."""
    return sum(p.bytes_per_token for layer in kinds for p in pools(layer))


def state_bytes_per_slot(kinds):
    """State bytes one slot costs over all layers, whatever its length."""
    return sum(s.bytes_per_slot for layer in kinds for s in states(layer))


def kv_pools(kinds):
    """Per layer its ``(K pool, V pool)``, for the engine's modes that
    move a block as a pair of equal rows (the host tier, block export):
    a layer that keeps anything else raises ``TypeError``."""
    out = []
    for i, layer in enumerate(kinds):
        if (len(layer) != 2 or pools(layer) != tuple(layer)
                or layer[0][1:] != layer[1][1:]):
            raise TypeError(
                "layer %d keeps %s, not a (K, V) pair of pools of one row"
                % (i, [type(k).__name__ + ":" + k.prefix for k in layer]))
        out.append(tuple(layer))
    return out


def _geometry(kind, blocks, block, slots):
    """What sizes ``kind``'s var: the pool's blocks, or the slots."""
    return (blocks, block) if isinstance(kind, CachePool) else (slots,)


def names(layer, blocks, block, slots):
    """Scope names of one layer's vars, in the layer's order."""
    return tuple(k.name(*_geometry(k, blocks, block, slots)) for k in layer)


def shapes(layer, blocks, block, slots):
    return tuple(k.shape(*_geometry(k, blocks, block, slots)) for k in layer)


def declare_pools(kinds, blocks, block, slots=None):
    """Declare every cache var in the CURRENT main program (persistable, no
    initializer: the session zeroes them in the scope). -> per layer, the
    tuple of its vars. ``slots`` sizes the states; a model without any
    needs none."""
    main_block = fluid.default_main_program().global_block()
    return [
        tuple(main_block.create_var(
            name=name, shape=shape, dtype=k.dtype, persistable=True)
            for k, name, shape in zip(
                layer, names(layer, blocks, block, slots),
                shapes(layer, blocks, block, slots)))
        for layer in kinds
    ]
