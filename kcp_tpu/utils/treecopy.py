"""tree_copy: the copy an object gets when it changes hands.

What the store holds is a JSON tree: ``dict`` / ``list`` nodes over
scalar leaves. Copying one needs none of ``copy.deepcopy``'s machinery
(memo dict, reductor lookup, a dispatch per leaf): plain recursion over
the two container types does it at a fifth of the cost, and allocates
nothing the collector has to trace besides the copy itself.

The behaviour adapts on each node's type, which the code can see:

- exact ``dict`` / ``list``: copied by recursion; scalar leaves
  (``str``, ``int``, ``float``, ``bool``, ``None``) are shared, as
  ``copy.deepcopy`` shares them;
- the sanitizer's ``FrozenDict`` / ``FrozenList`` (committed snapshots
  under ``KCP_SANITIZE=1``): come back as plain mutable ``dict`` /
  ``list``, as their ``__deepcopy__`` gives them — ``get`` still hands
  out an editable object;
- anything else (a tuple, a set, a dict subclass, bytes): that node goes
  to ``copy.deepcopy`` and ``object_tree_copy_fallbacks_total`` counts it.

Two differences from ``copy.deepcopy``, both of which a JSON round trip
(the WAL, the wire) already makes: a sub-tree referenced twice inside
one object comes back as two copies, and a cyclic object is not
supported (it recurses until ``RecursionError``; the WAL's
``json.dumps`` refuses such an object today).

``object_tree_copies_total`` counts calls of :func:`tree_copy`, i.e.
whole-object or whole-subtree copies made: one add per call, none per
node.
"""

from __future__ import annotations

import copy
from typing import Any

from ..analysis.sanitize import FrozenDict, FrozenList
from .trace import REGISTRY

__all__ = ["tree_copy"]

_COPIES = REGISTRY.counter(
    "object_tree_copies_total",
    "whole-object or whole-subtree copies made by tree_copy (store "
    "hand-overs, the applier, the splitter)")
_FALLBACKS = REGISTRY.counter(
    "object_tree_copy_fallbacks_total",
    "nodes tree_copy handed to copy.deepcopy because they were neither a "
    "dict, a list nor a JSON scalar")

_SCALARS = frozenset({str, int, float, bool, type(None)})


def _node(v: Any) -> Any:
    t = type(v)
    if t is dict or t is FrozenDict:
        return {k: (x if type(x) in _SCALARS else _node(x))
                for k, x in v.items()}
    if t is list or t is FrozenList:
        return [(x if type(x) in _SCALARS else _node(x)) for x in v]
    if t in _SCALARS:
        return v
    _FALLBACKS.inc()
    return copy.deepcopy(v)


def tree_copy(obj: Any) -> Any:
    """A private, mutable, plain copy of a JSON tree (see the module
    docstring for what "JSON tree" tolerates)."""
    _COPIES.inc()
    return _node(obj)
