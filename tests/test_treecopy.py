"""``tree_copy``: the copy an object gets when it changes hands.

It must give what ``copy.deepcopy`` gives — the same value, sharing no
container with its input — on everything the store holds: the two
benchmark shapes, random JSON trees, the sanitizer's frozen proxies
(which come back plain and mutable); and hand any other node to
``copy.deepcopy``, counting it.
"""

import copy
import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import k8s_load_reference as ref  # noqa: E402
from benchmarks.shapes import configmap, k8s_deployment  # noqa: E402

from kcp_tpu.analysis import sanitize  # noqa: E402
from kcp_tpu.utils.trace import REGISTRY  # noqa: E402
from kcp_tpu.utils.treecopy import tree_copy  # noqa: E402


def _deployment() -> dict:
    body = k8s_deployment.new("web", random.Random(7), ["east"])
    body["metadata"].update(uid="0b1e0f9c-5d7e", resourceVersion="41",
                            generation=3, clusterName="t0000",
                            creationTimestamp="2026-09-28T07:00:00Z")
    body["status"] = dict(ref.ready_status(body["spec"]["replicas"]),
                          observedGeneration=3)
    return body


def _configmap() -> dict:
    body = configmap.new("cfg", random.Random(7), ["east"])
    body["status"] = {"observedGen": body["data"]["gen"]}
    return body


def _random_tree(rng: random.Random, depth: int = 0):
    roll = rng.random()
    if depth >= 5 or roll < 0.35:
        return rng.choice([None, True, False, rng.randrange(-9, 10**12),
                           rng.random(), "", "x" * rng.randrange(1, 40),
                           "é∑"])
    if roll < 0.7:
        return {f"k{rng.randrange(50)}": _random_tree(rng, depth + 1)
                for _ in range(rng.randrange(0, 7))}
    return [_random_tree(rng, depth + 1) for _ in range(rng.randrange(0, 7))]


def _containers(obj, out: list) -> list:
    """Every dict and list reachable from ``obj``, itself included."""
    if isinstance(obj, dict):
        out.append(obj)
        for v in obj.values():
            _containers(v, out)
    elif isinstance(obj, (list, tuple, set, frozenset)):
        out.append(obj)
        for v in obj:
            _containers(v, out)
    return out


def _assert_private_equal(src, got) -> None:
    assert got == copy.deepcopy(src)
    mine = {id(c) for c in _containers(src, [])}
    theirs = _containers(got, [])
    assert not [c for c in theirs if id(c) in mine]
    # plain and mutable all the way down, whatever the input's types
    for c in theirs:
        assert type(c) in (dict, list, tuple, set, frozenset)


def _counters() -> tuple[float, float]:
    snap = REGISTRY.snapshot()
    return (snap["object_tree_copies_total"],
            snap["object_tree_copy_fallbacks_total"])


CASES = {
    "deployment_with_status": _deployment,
    "configmap": _configmap,
    **{f"random_tree_{seed}": (lambda seed=seed: {
        "root": _random_tree(random.Random(seed))})
       for seed in (1, 2, 3, 4)},
    "scalar_and_empty": lambda: {"a": None, "b": {}, "c": [], "d": [[]]},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_equals_deepcopy_and_shares_no_container(case):
    src = CASES[case]()
    before = copy.deepcopy(src)
    copies0, fallbacks0 = _counters()
    got = tree_copy(src)
    copies1, fallbacks1 = _counters()
    _assert_private_equal(src, got)
    assert src == before  # the input is only read
    # one add per call, none per node; no node needed copy.deepcopy
    assert copies1 - copies0 == 1 and fallbacks1 == fallbacks0


@pytest.mark.parametrize("case", ["deployment_with_status", "configmap",
                                  "random_tree_1"])
def test_frozen_snapshot_comes_back_plain_and_mutable(case):
    src = CASES[case]()
    frozen = sanitize.freeze(src)
    assert type(frozen) is sanitize.FrozenDict
    _c0, fallbacks0 = _counters()
    got = tree_copy(frozen)
    assert _counters()[1] == fallbacks0  # proxies ride the fast path
    _assert_private_equal(frozen, got)
    assert got == src
    assert all(type(c) in (dict, list) for c in _containers(got, []))
    got["metadata"] = {"edited": True}  # top level
    for c in _containers(got, []):  # and every nested container
        if type(c) is dict:
            c["edited"] = True
        else:
            c.append("edited")
    assert frozen == src  # the frozen original never noticed


def test_other_containers_fall_back_to_deepcopy_and_are_counted():
    class Labels(dict):
        pass

    shared = [1, 2]
    src = {"t": (1, [2, 3]), "s": {4, 5}, "sub": Labels(a=[1]),
           "plain": {"x": [shared, shared]}}
    copies0, fallbacks0 = _counters()
    got = tree_copy(src)
    copies1, fallbacks1 = _counters()
    assert got == src
    assert copies1 - copies0 == 1
    assert fallbacks1 - fallbacks0 == 3  # the tuple, the set, the subclass
    # each fallback node is a true deep copy of its own type
    assert type(got["t"]) is tuple and got["t"][1] is not src["t"][1]
    assert type(got["s"]) is set and got["s"] is not src["s"]
    assert type(got["sub"]) is Labels and got["sub"]["a"] is not src["sub"]["a"]
    # a difference from copy.deepcopy, stated: no memo, so a subtree
    # referenced twice comes back as two copies (as a JSON round trip
    # makes it)
    a, b = got["plain"]["x"]
    assert a == b == shared and a is not b and a is not shared
    da, db = copy.deepcopy(src)["plain"]["x"]
    assert da is db


def test_a_cyclic_object_is_not_supported():
    src: dict = {"a": {}}
    src["a"]["back"] = src
    with pytest.raises(RecursionError):
        tree_copy(src)
