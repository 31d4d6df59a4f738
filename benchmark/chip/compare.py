"""The comparison that decides `correct`: numbers of the timed path
against the plain reference, each held to a limit of its own that the
configuration's file states (PERF.md gives the readings each limit was
set from)."""
import math
import statistics


def worst_leaf_gap(prog_norms, ref_norms, skip=()):
    """Largest, over the leaves, of the gap between the program's norm
    and the reference's, measured against the reference's norm of that
    leaf or of the median leaf, whichever is larger. Returns
    (gap, leaf)."""
    names = [n for n in ref_norms if n not in skip]
    median = statistics.median(ref_norms[n] for n in names)
    worst, where = 0.0, None
    for n in names:
        gap = abs(prog_norms[n] - ref_norms[n]) \
            / max(ref_norms[n], median)
        if not math.isfinite(gap):
            return math.inf, n
        if gap > worst:
            worst, where = gap, n
    return worst, where


def still_leaves(ref_grad_norms):
    """Leaves whose gradient in the reference is under a thousandth of
    the median leaf's: under Adam they move by round-off alone, so the
    change of the parameters is not compared on them."""
    median = statistics.median(ref_grad_norms.values())
    return {n for n, g in ref_grad_norms.items() if g < 1e-3 * median}


def relative_gap(got, want):
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-30)


class Compared:
    """The numbers compared, each beside its limit, in the order they
    were added."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit, note=None):
        self.rows.append({"name": name, "value": float(value),
                          "limit": float(limit),
                          **({"note": note} if note else {})})

    def require(self, name, ok, note=None):
        """A yes-or-no guarantee: 0 when it held, 1 when not, limit 0."""
        self.add(name, 0.0 if ok else 1.0, 0.0, note)

    @property
    def correct(self):
        return bool(self.rows) and all(
            math.isfinite(r["value"]) and r["value"] <= r["limit"]
            for r in self.rows)

    def lines(self):
        return [f"compared {r['name']} = {r['value']:.6g} "
                f"(limit {r['limit']:.6g})"
                + (f" [{r['note']}]" if r.get("note") else "")
                for r in self.rows]
