"""The comparison that decides ``correct``.

Every request sent in the window is compared with the plain reference's
logits for its pool image.  The scale is the spread of the reference's
logits across the pool (the root mean square distance of an image's
logits from the pool's mean logits), so a request that got another
image's answer reads about 1 and one computed right reads near 0.

* ``unanswered``: requests sent in the window that failed or never got an
  answer (limit 0).
* ``logit_err_max``: the worst request's logit error over the scale.
* ``logit_err_rms``: the root mean square of the requests' logit errors
  over the scale.

Each limit sits in ``workloads/<cell>.json``; ``PERF.md`` gives the
readings each was set from.
"""
from __future__ import annotations

import numpy as np

ORDER = ("unanswered", "logit_err_max", "logit_err_rms")


def spread(ref: np.ndarray) -> float:
    centred = ref - ref.mean(axis=0, keepdims=True)
    return float(np.sqrt(np.mean(np.sum(centred ** 2, axis=1))))


def numbers(requests, ref: np.ndarray) -> dict:
    """The numbers compared, from the requests and the reference's rows."""
    answered = [r for r in requests if r.error is None and r.logits is not None]
    scale = spread(ref)
    if answered:
        got = np.stack([np.asarray(r.logits, np.float64) for r in answered])
        want = ref[[r.pool for r in answered]]
        err = np.linalg.norm(got - want, axis=1) / scale
        err_max, err_rms = float(err.max()), float(np.sqrt(np.mean(err ** 2)))
        if not np.all(np.isfinite(err)):
            err_max = err_rms = float("inf")
    else:
        err_max = err_rms = float("inf")
    return {"unanswered": len(requests) - len(answered),
            "logit_err_max": err_max, "logit_err_rms": err_rms}


def compare(requests, ref: np.ndarray, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` in :data:`ORDER`; a number passes
    when it is at most its limit."""
    got = numbers(requests, ref)
    return {k: {"value": got[k], "limit": limits[k]} for k in ORDER}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def describe(checks: dict) -> list[str]:
    return [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
            for k, c in checks.items()]
