"""What causal flash attention NEEDS for one train step, from shapes.

Counts the algorithm, not today's kernels: causal attention is half the
s x s rectangle, and what the backward kernels recompute does not count.
"""

# The kernels' events on the device's "XLA Ops" line. A Pallas kernel
# shows as a custom call to "tpu_custom_call" under XLA's own name for it
# (%fn.59): the program gives its kernels no name yet, and in a train step
# the three flash kernels of each layer are the only such calls.
EVENT_PATTERN = r'custom_call_target="tpu_custom_call"'


def needs(config, traffic, chips=1):
    """(FLOPs, bytes) one chip needs for one train step's attention.

    Forward: QK^T and PV, 2 FLOPs a multiply-add, over half the
    rectangle: 2 * 2 * b * s * s * h / 2. Backward: dQ, dK, dV and dP,
    twice the forward. Bytes (bf16): forward reads Q, K, V and writes O;
    backward reads Q, K, V, O, dO and writes dQ, dK, dV: 12 arrays of
    b * s * h."""
    h = config.get("n_embd", config.get("hidden_size"))
    layers = config.get("n_layer", config.get("num_hidden_layers"))
    b, s = traffic["batch"] / float(chips), traffic["seq_len"]
    forward = 2 * 2 * b * s * s * h / 2.0
    flops = layers * 3 * forward
    moved = layers * 12 * b * s * h * 2
    return flops, moved
