"""The comparisons that decide ``correct``: each number beside its limit.

A limit comes from ``limits/<workload>.json`` and was set from readings
on the chip (PERF.md gives them). That file names the numbers a cell
compares; a cell whose file names none is not correct.
"""

import math
import statistics


def _row(name, value, limits):
    limit = limits.get(name)
    ok = (limit is not None and value is not None
          and math.isfinite(value) and value <= limit)
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def leaf_gaps(got, ref):
    """Per leaf, the program's norm less the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    floor = statistics.median(ref.values())
    return {k: (got[k] - ref[k]) / max(ref[k], floor) for k in ref}


def worst(gaps):
    leaf = max(gaps, key=lambda k: abs(gaps[k]))
    return leaf, abs(gaps[leaf])


def centred(gaps):
    """The gaps less the shift that all leaves share (their median): in a
    sound run of the BERT cell much of the worst leaf's gap is one shift
    common to every leaf, and a lower precision's noise differs from leaf
    to leaf (PERF.md section 2 gives the readings)."""
    shift = statistics.median(gaps.values())
    return {k: v - shift for k, v in gaps.items()}


def compare(numbers, limits, detail):
    """The numbers a cell's limits file names are compared; the others
    are printed. A file that names none, or one that is not read, fails."""
    rows = [_row(name, numbers.get(name), limits) for name in sorted(limits)]
    detail = dict(detail, **{k: v for k, v in numbers.items()
                             if k not in limits})
    return {"rows": rows, "detail": detail}


def train(got, ref, limits):
    """``got``: the program's losses, first-gradient norms and parameter
    change norms; ``ref``: (losses, gnorm, dnorm) of the reference."""
    ref_losses, ref_gnorm, ref_dnorm = ref
    numbers = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref_losses)):
        numbers["loss_gap_step%d" % (i + 1)] = abs(a - b)
    g = leaf_gaps(got["gnorm"], ref_gnorm)
    d = leaf_gaps(got["dnorm"], ref_dnorm)
    numbers["grad_norm_gap_worst_leaf"] = worst(g)[1]
    numbers["grad_norm_gap_worst_leaf_centred"] = worst(centred(g))[1]
    numbers["grad_norm_gap_median_leaf"] = statistics.median(
        abs(v) for v in g.values())
    numbers["update_norm_gap_worst_leaf"] = worst(d)[1]
    numbers["update_norm_gap_median_leaf"] = statistics.median(
        abs(v) for v in d.values())
    return compare(numbers, limits, {
        "grad_worst_leaf": worst(g)[0], "update_worst_leaf": worst(d)[0],
        "losses": got["losses"], "ref_losses": list(ref_losses)})


def served(gaps, limits):
    """``gaps``: for every served token compared, how far its logit lies
    below the reference's best at that position."""
    numbers = {"served_logit_gap_mean": sum(gaps) / len(gaps),
               "served_logit_gap_widest": max(gaps)}
    return compare(numbers, limits, {
        "tokens_compared": len(gaps),
        "tokens_not_the_references_first": sum(1 for g in gaps if g > 0)})


def correct(checks):
    return bool(checks["rows"]) and all(r["ok"] for r in checks["rows"])


def summary(checks):
    """{short name: [number, limit]} for the result line and stderr."""
    return {r["name"]: [r["value"], r["limit"]] for r in checks["rows"]}


def summary_values(checks):
    """Every number worked out, compared or not (calibration, tests)."""
    out = {k: v for k, v in checks["detail"].items()
           if isinstance(v, float)}
    out.update({r["name"]: r["value"] for r in checks["rows"]})
    return out
