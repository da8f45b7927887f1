"""Label-match kernel differential-tested against the host selector."""

import numpy as np

from kcp_tpu.ops.encode import encode_label_batch
from kcp_tpu.ops.hashing import hash_pair
from kcp_tpu.ops.labelmatch import (
    compile_selector,
    fanout_match_jit,
    match_batch_jit,
    match_batch_np,
    match_host,
    try_compile_selector,
)
from kcp_tpu.store.selectors import parse_selector
from kcp_tpu.utils.trace import REGISTRY

SELECTORS = [
    "app=web",
    "app!=web",
    "env in (prod,staging)",
    "env notin (prod)",
    "app",
    "!app",
    "app=web,env in (prod,dev),!legacy,tier",
    "kcp.dev/cluster=us-east1",
    "",
]


def random_labels(rng):
    keys = ["app", "env", "tier", "legacy", "kcp.dev/cluster"]
    vals = {"app": ["web", "db"], "env": ["prod", "staging", "dev"], "tier": ["1", "2"],
            "legacy": ["true"], "kcp.dev/cluster": ["us-east1", "us-west1"]}
    labels = {}
    for k in keys:
        if rng.random() < 0.5:
            labels[k] = vals[k][rng.integers(len(vals[k]))]
    return labels or None


def test_match_batch_vs_host():
    rng = np.random.default_rng(7)
    label_maps = [random_labels(rng) for _ in range(256)]
    pairs, keys = encode_label_batch(label_maps, capacity=8)
    for spec in SELECTORS:
        sel = parse_selector(spec)
        c = compile_selector(sel)
        got = np.asarray(match_batch_jit(pairs, keys, c.alts, c.negate, c.use_key, c.valid))
        want = match_host(sel, label_maps)
        np.testing.assert_array_equal(got, want, err_msg=f"selector {spec!r}")


def test_fanout_match():
    clusters = [f"c{i}" for i in range(16)]
    rng = np.random.default_rng(3)
    label_maps = []
    owner = []
    for _ in range(512):
        if rng.random() < 0.9:
            c = clusters[rng.integers(len(clusters))]
            label_maps.append({"kcp.dev/cluster": c, "x": "y"})
            owner.append(c)
        else:
            label_maps.append({"x": "y"})
            owner.append(None)
    pairs, _ = encode_label_batch(label_maps, capacity=4)
    sel_hashes = np.array(
        [hash_pair("kcp.dev/cluster", c) for c in clusters], dtype=np.uint32
    )
    got = np.asarray(fanout_match_jit(pairs, sel_hashes))
    assert got.shape == (512, 16)
    for i, c in enumerate(owner):
        row = got[i]
        if c is None:
            assert not row.any()
        else:
            assert row.sum() == 1 and row[clusters.index(c)]


def test_match_batch_np_matches_device_and_host():
    rng = np.random.default_rng(11)
    label_maps = [random_labels(rng) for _ in range(128)]
    pairs, keys = encode_label_batch(label_maps, capacity=8)
    for spec in SELECTORS:
        sel = parse_selector(spec)
        c = compile_selector(sel)
        got = match_batch_np(pairs, keys, c)
        np.testing.assert_array_equal(got, match_host(sel, label_maps),
                                      err_msg=f"selector {spec!r}")
        dev = np.asarray(match_batch_jit(pairs, keys, c.alts, c.negate,
                                         c.use_key, c.valid))
        np.testing.assert_array_equal(got, dev, err_msg=f"selector {spec!r}")


def test_try_compile_oversized_returns_none_and_counts():
    before = REGISTRY.counter("labelmatch_fallback_total").value
    nine_reqs = parse_selector(",".join(f"k{i}" for i in range(9)))
    assert try_compile_selector(nine_reqs) is None
    nine_alts = parse_selector("team in (a,b,c,d,e,f,g,h,i)")
    assert try_compile_selector(nine_alts) is None
    assert REGISTRY.counter("labelmatch_fallback_total").value == before + 2
    # a kernel-shaped selector still compiles (and raising compile keeps
    # its contract for device callers)
    assert try_compile_selector(parse_selector("team=a")) is not None
    import pytest

    with pytest.raises(ValueError):
        compile_selector(nine_reqs)


def test_compile_selector_custom_hashers():
    # interning hashers (the store's exact fan-out): sequential nonzero
    # ids instead of 32-bit string hashes
    pairs_tab, keys_tab = {}, {}

    def pid(k, v):
        return pairs_tab.setdefault((k, v), len(pairs_tab) + 1)

    def kid(k):
        return keys_tab.setdefault(k, len(keys_tab) + 1)

    sel = parse_selector("app=web,env notin (prod),!legacy")
    c = compile_selector(sel, pair_hash=pid, key_hash=kid)
    assert c.alts[0, 0] == pairs_tab[("app", "web")]
    assert c.alts[1, 0] == pairs_tab[("env", "prod")]
    assert c.alts[2, 0] == keys_tab["legacy"]
    assert c.negate[1] and c.negate[2] and c.use_key[2]
