"""Object encoding: flattening, slot stability, status lane, overflow."""

import numpy as np
import pytest

from kcp_tpu.ops.encode import (
    BucketEncoder,
    BucketOverflow,
    encode_label_batch,
    flatten_object,
    pad_pow2,
)


def cm(data, status=None, labels=None):
    obj = {
        "apiVersion": "v1",
        "kind": "ConfigMap",
        "metadata": {"name": "x", "namespace": "d", "resourceVersion": "42", "uid": "u"},
        "data": data,
    }
    if labels:
        obj["metadata"]["labels"] = labels
    if status is not None:
        obj["status"] = status
    return obj


def test_flatten_excludes_volatile_metadata():
    paths = [p for p, _ in flatten_object(cm({"a": "1"}))]
    assert "metadata.resourceVersion" not in paths
    assert "metadata.uid" not in paths
    assert "metadata.name" in paths
    assert "data.a" in paths


def test_encoding_deterministic_and_order_independent():
    enc = BucketEncoder(capacity=32)
    a = enc.encode({"data": {"x": "1", "y": "2"}, "metadata": {"name": "n"}})
    b = enc.encode({"metadata": {"name": "n"}, "data": {"y": "2", "x": "1"}})
    np.testing.assert_array_equal(a, b)


def test_equal_objects_equal_encodings_different_differ():
    enc = BucketEncoder(capacity=64)
    e1 = enc.encode(cm({"k": "v"}))
    e2 = enc.encode(cm({"k": "v"}))
    e3 = enc.encode(cm({"k": "DIFFERENT"}))
    np.testing.assert_array_equal(e1, e2)
    assert (e1 != e3).any()


def test_status_mask_classifies_lanes():
    enc = BucketEncoder(capacity=64)
    enc.encode(cm({"k": "v"}, status={"phase": "Ready", "replicas": 3}))
    mask = enc.status_mask()
    status_slots = {enc.slots["status.phase"], enc.slots["status.replicas"]}
    for slot in range(len(enc.slot_paths)):
        assert mask[slot] == (slot in status_slots)


@pytest.mark.parametrize("native", [False, True])
def test_status_mask_is_rebuilt_only_when_the_vocabulary_has_grown(native):
    """The mask is kept with the vocabulary's length: until a new path is
    seen every caller gets the same read-only array, and a grown
    encoder builds its own at its own capacity."""
    enc = BucketEncoder(capacity=8)
    if not native:
        enc._native_tried = True  # the Python flatten+hash path
    enc.encode(cm({"k": "v"}, status={"phase": "Ready"}))
    mask = enc.status_mask()
    assert enc.status_mask() is mask and not mask.flags.writeable
    enc.encode(cm({"k": "other"}, status={"phase": "Pending"}))
    assert enc.status_mask() is mask  # new values, no new path
    enc.encode(cm({"k": "v"}, status={"phase": "Ready", "replicas": 3}))
    grown = enc.status_mask()
    assert grown is not mask and grown[enc.slots["status.replicas"]]
    assert not mask[enc.slots["status.replicas"]]
    wider = enc.grown()
    assert wider.status_mask().shape == (16,)
    np.testing.assert_array_equal(wider.status_mask()[:8], grown)
    assert enc.status_mask() is grown


def test_overflow_and_grow():
    enc = BucketEncoder(capacity=8)
    with pytest.raises(BucketOverflow):
        enc.encode(cm({f"k{i}": str(i) for i in range(20)}))
    bigger = enc.grown()
    assert bigger.capacity == 16
    # vocabulary prefix preserved: shared slots encode identically
    small = BucketEncoder(capacity=8)
    obj = {"data": {"a": "1"}}
    s = small.encode(obj)
    g = bigger.grown().encode(obj)  # plenty of room
    # same path -> same hash; slot ids may differ between independent encoders,
    # but within one grown lineage they are stable:
    enc2 = BucketEncoder(capacity=4)
    enc2.encode({"data": {"a": "1"}})
    grown = enc2.grown()
    assert grown.slots["data.a"] == enc2.slots["data.a"]
    del s, g


def test_batch_encoding_with_padding_and_absent():
    enc = BucketEncoder(capacity=32)
    objs = [cm({"a": "1"}), None, cm({"a": "2"})]
    batch = enc.encode_batch(objs, keys=["k0", "k1", "k2"], pad_to=pad_pow2(3))
    assert batch.values.shape == (8, 32)
    assert batch.exists.tolist()[:3] == [True, False, True]
    assert not batch.exists[3:].any()
    assert (batch.values[1] == 0).all()


def test_pad_pow2():
    assert pad_pow2(0) == 8
    assert pad_pow2(8) == 8
    assert pad_pow2(9) == 16
    assert pad_pow2(1000) == 1024


def test_label_encoding_shapes():
    pairs, keys = encode_label_batch([{"a": "1"}, None, {"b": "2", "c": "3"}], capacity=4)
    assert pairs.shape == (3, 4)
    assert (pairs[1] == 0).all()
    assert (pairs[0] != 0).sum() == 1
    assert (keys[2] != 0).sum() == 2
