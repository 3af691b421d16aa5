"""The comparison that decides ``correct``: every number compared is
printed beside its limit, and one number over its limit makes the run not
correct."""

from __future__ import annotations

import math
import statistics


class Checks:
    def __init__(self):
        self.rows = []          # (name, value, op, limit, ok)

    def add(self, name: str, value, limit, op: str = "<="):
        value = float(value)
        if op == "<=":
            ok = value <= limit
        elif op == "<":
            ok = value < limit
        elif op == "==":
            ok = value == limit
        elif op == ">=":
            ok = value >= limit
        else:
            raise ValueError(op)
        ok = ok and math.isfinite(value)
        self.rows.append((name, value, op, limit, ok))
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[4] for r in self.rows)

    def print(self, out=print):
        for name, value, op, limit, ok in self.rows:
            out(f"CHECK {name}: {value!r} {op} {limit!r} -> "
                f"{'ok' if ok else 'FAIL'}")


def worst_leaf_gap(prog: dict, ref: dict):
    """Worst leaf of |program's norm - reference's norm| over the larger
    of the reference's norm of that leaf and of the median leaf (some
    gradients are all but zero). ``prog``/``ref``: {path: [norms]}."""
    flat_ref = [float(x) for k in sorted(ref) for x in ref[k]]
    floor = statistics.median(flat_ref)
    worst, where = 0.0, None
    for k in sorted(ref):
        for i, r in enumerate(ref[k]):
            gap = abs(float(prog[k][i]) - float(r)) / max(float(r), floor)
            if not math.isfinite(gap):
                return math.inf, f"{k}[{i}]"
            if gap > worst:
                worst, where = gap, f"{k}[{i}]"
    return worst, where


def sampled_rel_diffs(prog: dict, ref: dict) -> dict:
    """Element by element on a fixed sample: per leaf slice, the RMS of
    (program - reference) over the larger of the reference's RMS of that
    slice and of the median slice. ``prog``/``ref``: {path: [slices, n]}
    arrays of the same sampled elements; returns {path: [slices]}."""
    import numpy as np
    rms = lambda a: np.sqrt(np.mean(np.square(np.asarray(a, np.float64)),
                                    axis=-1))
    ref_rms = {k: rms(ref[k]) for k in ref}
    floor = float(np.median(np.concatenate(list(ref_rms.values()))))
    return {k: rms(np.asarray(prog[k], np.float64)
                   - np.asarray(ref[k], np.float64))
            / np.maximum(ref_rms[k], floor) for k in sorted(ref)}


def worst(rel: dict):
    """The worst slice of ``sampled_rel_diffs`` and where it is."""
    import numpy as np
    worst, where = 0.0, None
    for k, v in rel.items():
        if not np.all(np.isfinite(v)):
            return math.inf, k
        i = int(np.argmax(v))
        if v[i] > worst:
            worst, where = float(v[i]), f"{k}[{i}]"
    return worst, where


def vector_leaves(spec: dict, under: str = "blocks") -> set:
    """Paths of the leaves under ``under`` that hold one vector a layer
    (biases, norm scales): ``spec`` is a reference's ``param_spec``."""
    out = set()

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + [k])
        elif path[0] == under and len(node[0]) == 2:
            out.add("/".join(path))
    walk(spec, [])
    return out


def pooled(rel: dict, keep=None) -> float:
    """Root mean square over every slice of ``sampled_rel_diffs`` (of the
    leaves whose path is in ``keep``): steady from seed to seed where the
    worst slice swings."""
    import numpy as np
    v = np.concatenate([x for k, x in rel.items()
                        if keep is None or k in keep])
    return float(np.sqrt(np.mean(np.square(v))))
