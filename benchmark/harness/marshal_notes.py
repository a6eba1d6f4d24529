"""What ``executor_marshal`` says of the values it gathered: ``values``,
``placed`` (through ``jax.device_put``) and ``reused`` (handed over on
the identity check of the executor's resolved-argument record, with no
lookup). A program whose marshal notes no ``reused`` (an older commit)
gives nothing to read."""

from . import program_spans as ps


def reused_pct(ev):
    """Of the values the window's ``executor_marshal`` phases gathered,
    the share handed over on the identity check alone; None where no
    phase carries the count."""
    notes = [s["args"] for s in ps.named(ps.in_window(ev),
                                         "executor_marshal")]
    notes = [a for a in notes if "reused" in a]
    values = sum(a.get("values", 0) for a in notes)
    if not values:
        return None
    return 100.0 * sum(a["reused"] for a in notes) / values
