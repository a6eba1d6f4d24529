"""What one T = 1 step of paged decode attention NEEDS, from the live
length of every stream: each slot reads its live keys and values once,
out of the one block pool the engine has (``flash_decode_paged``: a
program a slot and group of logical blocks, the slot's table and live
length prefetched as scalars).
"""

# In GPT's T = 1 step program the paged kernel is the only Pallas call (one
# a layer), so any custom call is it; see kernels/flash_train.py on names.
EVENT_PATTERN = r'custom_call_target="tpu_custom_call"'


def needs(config, live_lengths, pool_bytes=4):
    """(FLOPs, bytes) of one step over all layers: per live key, q.k and
    p.v at 2 FLOPs a multiply-add over the hidden width; K and V of that
    key read once from the pool (float32 in this cell)."""
    h, layers = config["n_embd"], config["n_layer"]
    keys = float(sum(live_lengths))
    flops = layers * keys * 2 * 2 * h
    moved = layers * keys * 2 * h * pool_bytes
    return flops, moved
