"""Work counted from the reference's jaxpr: one yardstick for every
implementation of a site.

A site is what a reference traces under :func:`chipbench.refs.common.site`.
Its operations are the conv and dot work at the model's shapes (2 per
multiply-add; never at padded tile shapes).  Its least bytes are its
operands from outside the site and its results used outside it, each
element at one byte: the configuration's design precision, int8.
A kernel's least time for one call of the model step is the sum over its
sites of max(operations / int8 peak, bytes / memory bandwidth).
"""
from __future__ import annotations

import collections
import math
import re

import jax
from jax.extend import core as jex_core

from chipbench.refs.common import SITE_PREFIX

_SITE = re.compile(re.escape(SITE_PREFIX) + r"([\w.-]+)#(\d+)")
_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr")


def dot_flops(eqn) -> float:
    (lc, rc), (lb, _) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0].aval.shape, eqn.invars[1].aval.shape
    batch = math.prod(lhs[i] for i in lb)
    contract = math.prod(lhs[i] for i in lc)
    m = math.prod(s for i, s in enumerate(lhs) if i not in lc and i not in lb)
    n = math.prod(s for i, s in enumerate(rhs) if i not in rc and i not in lb)
    return 2.0 * batch * m * n * contract


def conv_flops(eqn) -> float:
    """2 x output elements x (kernel elements per output channel)."""
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    cout = out[eqn.params["dimension_numbers"].out_spec[1]]
    return 2.0 * math.prod(out) * math.prod(rhs) / cout


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        return dot_flops(eqn)
    if name == "conv_general_dilated":
        return conv_flops(eqn)
    total = 0.0
    for key in _SUBJAXPR_KEYS:
        sub = eqn.params.get(key)
        if sub is not None:
            total += sum(_eqn_flops(e) for e in getattr(sub, "jaxpr", sub).eqns)
    return total


def _site_of(eqn):
    m = _SITE.search(str(eqn.source_info.name_stack))
    return (m.group(1), int(m.group(2))) if m else None


def _elems(v) -> int:
    return math.prod(getattr(v.aval, "shape", ()) or (1,))


def sites(fn, *args) -> list[dict]:
    """Every site of ``fn(*args)`` (shapes are enough), in trace order:
    ``{"kernel", "flops", "bytes"}``."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    by_site: dict = collections.OrderedDict()
    for eqn in jaxpr.eqns:
        key = _site_of(eqn)
        if key is not None:
            by_site.setdefault(key, []).append(eqn)
    used_by = collections.defaultdict(set)
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if not isinstance(v, jex_core.Literal):
                used_by[v].add(_site_of(eqn))
    for v in jaxpr.outvars:
        if not isinstance(v, jex_core.Literal):
            used_by[v].add(None)
    out = []
    for key, eqns in by_site.items():
        inside = {v for e in eqns for v in e.outvars}
        operands = {v for e in eqns for v in e.invars
                    if not isinstance(v, jex_core.Literal) and v not in inside}
        results = {v for v in inside if used_by[v] - {key}}
        out.append({
            "kernel": key[0],
            "flops": sum(_eqn_flops(e) for e in eqns),
            "bytes": float(sum(_elems(v) for v in operands | results)),
        })
    return out


def least_seconds(site_list: list[dict], kernel: str, peak: dict) -> float:
    """The least time the chip could take for ``kernel``'s sites, once."""
    return sum(max(s["flops"] / peak["int8_ops_per_s"],
                   s["bytes"] / peak["hbm_bytes_per_s"])
               for s in site_list if s["kernel"] == kernel)
