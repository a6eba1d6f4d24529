"""The Pallas kernels by the names the program gives them (``name=`` on
each ``pallas_call`` of ``paddle_tpu/kernels/flash_attention.py``):
``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``,
``flash_decode_paged``, ``flash_decode_paged_gqa``.

An op event's name on the device's "XLA Ops" line is the instruction's
HLO text. A named kernel shows there twice: in the instruction's own name
(``%flash_fwd.3 = ... custom-call(...)``, wrapped as ``jvp_flash_fwd_``
under autodiff) and in ``metadata={op_name=".../flash_fwd/pallas_call"}``.
A program that names no kernel (``%fn.55``) matches nothing here.
"""

from benchmark.kernels import flash_train


def event_pattern(kernel):
    """A regular expression for the events of the Pallas kernel
    ``kernel``: a ``tpu_custom_call`` whose text carries the name, not
    followed by more of a longer name (``flash_bwd_dq`` against
    ``flash_bwd_dkv``, ``flash_decode_paged`` against
    ``flash_decode_paged_gqa``)."""
    return r"(?s)^(?=.*%s)(?=.*\b%s(?![a-z]|_[a-z]))" % (
        flash_train.EVENT_PATTERN, kernel)
