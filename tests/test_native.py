"""Differential tests: native C++ runtime vs the pure-Python twins.

The native library (native/*.cc) must agree byte-for-byte with
kcp_tpu/ops/hashing.py + encode.py, and the WAL engine must satisfy the
durability semantics the JSON WAL provides (restart resumes, snapshot
compaction, torn-tail recovery — the reference's restart-resumes-from-
etcd model, pkg/server/server.go:80-97).
"""

from __future__ import annotations

import json
import os
import random
import string

import numpy as np
import pytest

from kcp_tpu.native import available

pytestmark = pytest.mark.skipif(not available(), reason="native library unavailable")


def _rand_value(rng: random.Random, depth: int = 0):
    kinds = 7 if depth < 3 else 4
    t = rng.randrange(kinds)
    if t == 0:
        return rng.randrange(-(10**12), 10**12)
    if t == 1:
        return rng.random() * 10 ** rng.randrange(-10, 10)
    if t == 2:
        alphabet = string.printable + "λ中✓é"
        return "".join(rng.choice(alphabet) for _ in range(rng.randrange(12)))
    if t == 3:
        return rng.choice([True, False, None])
    if t == 4:
        return [_rand_value(rng, depth + 1) for _ in range(rng.randrange(4))]
    return {
        "".join(rng.choice(string.ascii_letters + "_.") for _ in range(rng.randrange(1, 8))):
            _rand_value(rng, depth + 1)
        for _ in range(rng.randrange(5))
    }


class TestHashParity:
    def test_fnv1a(self):
        from kcp_tpu.native import fnv1a_native
        from kcp_tpu.ops.hashing import fnv1a

        for s in (b"", b"a", b"hello world", bytes(range(256))):
            assert fnv1a(s) == fnv1a_native(s)

    def test_hash_value_fuzz(self):
        from kcp_tpu.native import hash_value_native
        from kcp_tpu.ops.hashing import hash_value

        rng = random.Random(7)
        for _ in range(500):
            v = _rand_value(rng)
            assert hash_value(v) == hash_value_native(json.dumps(v).encode())

    def test_hash_pair(self):
        import ctypes

        from kcp_tpu.native import load
        from kcp_tpu.ops.hashing import hash_pair

        lib = load()
        for k, v in (("app", "web"), ("kcp.dev/cluster", "us-east1"), ("", "")):
            assert hash_pair(k, v) == lib.enc_hash_pair(
                k.encode(), len(k.encode()), v.encode(), len(v.encode())
            )


class TestTokenizerParity:
    """native enc_tokenize_schemas vs the Python walk (schemahash)."""

    def test_extension_loads(self):
        # hard requirement in this image (Python dev headers present):
        # without it the dispatcher-based parity tests below would
        # compare the Python walk against itself and pass vacuously
        from kcp_tpu.native import load_tokenizer

        assert load_tokenizer() is not None

    def test_fuzz_corpus(self):
        from kcp_tpu.native import tokenize_schemas_native
        from kcp_tpu.ops.hashing import canonical_json
        from kcp_tpu.ops.schemahash import tokenize_schema_py, tokenize_schemas

        rng = random.Random(11)
        # dict roots (the real input shape) plus arbitrary roots — the
        # walk accepts any JSON value at top level
        schemas = [_rand_value(rng) for _ in range(300)]
        want = np.stack([tokenize_schema_py(s) for s in schemas])
        # tier 1 (direct dict walk, via the dispatcher)
        np.testing.assert_array_equal(tokenize_schemas(schemas), want)
        # tier 2 (serialize + native JSON parse/walk), exercised directly
        blobs = [canonical_json(s).encode() for s in schemas]
        np.testing.assert_array_equal(tokenize_schemas_native(blobs, 256), want)

    def test_non_json_shapes_fall_back(self):
        from kcp_tpu.ops.schemahash import tokenize_schema_py, tokenize_schemas

        # tuples and non-str keys are not JSON-shaped: the native tiers
        # must refuse them (rather than guess) and the dispatcher must
        # land on the Python walk, which treats a tuple as an opaque
        # subtree leaf
        s = {"a": (1, 2), "b": "x"}
        np.testing.assert_array_equal(
            tokenize_schemas([s])[0], tokenize_schema_py(s)
        )

    def test_truncation_boundaries(self):
        from kcp_tpu.ops.schemahash import tokenize_schema_py, tokenize_schemas

        # wide dict: key hashes keep appending past max_tokens (the
        # Python walk only checks size at entry); deep list nesting hits
        # the entry check exactly; each must truncate identically
        wide = {f"k{i:04d}": i for i in range(400)}
        deep: object = 1
        for _ in range(120):
            deep = [deep]
        exact = {"p": {f"f{i}": "x" for i in range(126)}}
        for mt in (8, 64, 256):
            got = tokenize_schemas([wide, deep, exact], max_tokens=mt)
            want = np.stack(
                [tokenize_schema_py(s, max_tokens=mt) for s in (wide, deep, exact)]
            )
            np.testing.assert_array_equal(got, want)

    def test_unicode_and_escapes(self):
        from kcp_tpu.ops.schemahash import tokenize_schema_py, tokenize_schemas

        s = {
            "desc\n": 'quote " backslash \\ tab\t',
            "中文": ["λ", "\x01control", "sur\U0001f600rogate"],
            "num": [0.0, -0.0, 1e3, -1.5e-7, 10**30],
        }
        np.testing.assert_array_equal(
            tokenize_schemas([s])[0], tokenize_schema_py(s)
        )

    def test_single_schema_entry_point_matches(self):
        from kcp_tpu.ops.schemahash import tokenize_schema, tokenize_schema_py

        s = {"type": "object", "properties": {"a": {"type": "string"}}}
        np.testing.assert_array_equal(tokenize_schema(s), tokenize_schema_py(s))


class TestEncoderParity:
    OBJS = [
        {"apiVersion": "v1", "kind": "ConfigMap",
         "metadata": {"name": "a", "namespace": "ns", "uid": "u1",
                      "resourceVersion": "9", "labels": {"k": "v"}},
         "data": {"a": "1", "b": "2"}},
        {"apiVersion": "apps/v1", "kind": "Deployment",
         "metadata": {"name": "b", "creationTimestamp": "t", "generation": 3,
                      "managedFields": [{"x": 1}]},
         "spec": {"replicas": 5,
                  "template": {"spec": {"containers": [{"name": "c", "image": "i"}]}}},
         "status": {"readyReplicas": 2}},
        {"kind": "Deep", "metadata": {},
         "spec": {"d": {"a": {"b": {"c": {"d": {"e": {"f": {"g": 1}}}}}}}}},
        {"kind": "Empty", "spec": {}},
    ]

    def test_rows_and_vocab_match(self):
        from kcp_tpu.native import NativeBucket
        from kcp_tpu.ops.encode import BucketEncoder

        py = BucketEncoder(capacity=64)
        py._native_tried = True  # force pure-Python reference path
        nat = NativeBucket(64)
        for obj in self.OBJS:
            row_py = py.encode(obj)
            row_nat = np.zeros(64, dtype=np.uint32)
            assert nat.encode_json(json.dumps(obj).encode(), row_nat) == 0
            np.testing.assert_array_equal(row_py, row_nat)
        assert py.slot_paths == nat.slot_paths()

    def test_bucket_encoder_uses_native_transparently(self):
        from kcp_tpu.ops.encode import BucketEncoder

        fast = BucketEncoder(capacity=64)
        ref = BucketEncoder(capacity=64)
        ref._native_tried = True
        for obj in self.OBJS:
            np.testing.assert_array_equal(fast.encode(obj), ref.encode(obj))
        assert fast.slot_paths == ref.slot_paths
        assert fast._native is not None  # fast path actually engaged
        np.testing.assert_array_equal(fast.status_mask(), ref.status_mask())

    def test_overflow_raises(self):
        from kcp_tpu.ops.encode import BucketEncoder, BucketOverflow

        enc = BucketEncoder(capacity=4)
        with pytest.raises(BucketOverflow):
            enc.encode({"kind": "X", "spec": {c: 1 for c in "abcdefgh"}})

    def test_volatile_metadata_excluded(self):
        from kcp_tpu.ops.encode import BucketEncoder

        enc = BucketEncoder(capacity=16)
        a = enc.encode({"kind": "X", "metadata": {"name": "n", "resourceVersion": "1"}})
        b = enc.encode({"kind": "X", "metadata": {"name": "n", "resourceVersion": "2"}})
        np.testing.assert_array_equal(a, b)

    def test_parse_anomaly_retires_native_keeps_vocab_coherent(self):
        from kcp_tpu.ops.encode import BucketEncoder

        # >128-deep nesting: Python json handles it, jsoncanon rejects it,
        # so the encoder must retire the native bucket permanently instead
        # of desyncing the slot vocabulary between the two paths.
        deep: dict = {"leaf": 1}
        for _ in range(200):
            deep = {"n": deep}
        enc = BucketEncoder(capacity=16)
        enc.encode({"kind": "X", "z": deep})
        assert enc._native is None  # retired
        enc.encode({"kind": "X", "a": 1, "z": deep})
        ref = BucketEncoder(capacity=16)
        ref._native_tried = True
        ref.encode({"kind": "X", "z": deep})
        ref.encode({"kind": "X", "a": 1, "z": deep})
        assert enc.slot_paths == ref.slot_paths
        assert len(set(enc.slot_paths)) == len(enc.slot_paths)  # no dupes

    def test_noncontiguous_out_is_safe(self):
        from kcp_tpu.ops.encode import BucketEncoder

        enc = BucketEncoder(capacity=8)
        obj = {"kind": "X", "spec": {"a": 1}}
        backing = np.zeros(16, dtype=np.uint32)
        view = backing[::2]
        enc.encode(obj, out=view)
        ref = BucketEncoder(capacity=8)
        ref._native_tried = True
        np.testing.assert_array_equal(view, ref.encode(obj))
        assert not backing[1::2].any()  # skipped lanes untouched


class TestWalEngine:
    def test_restart_resumes(self, tmp_path):
        from kcp_tpu.native import WalEngine

        p = str(tmp_path / "s.wal")
        w = WalEngine(p, sync_every=2)
        w.put(b"a", b"1", 1)
        w.put(b"b", b"2", 2)
        w.delete(b"a", 3)
        w.close()

        w2 = WalEngine(p)
        assert len(w2) == 1 and w2.rv == 3
        assert w2.get(b"b") == b"2" and w2.get(b"a") is None
        w2.close()

    def test_prefix_scan_is_ordered(self, tmp_path):
        from kcp_tpu.native import WalEngine

        w = WalEngine(str(tmp_path / "s.wal"))
        for k in (b"cm\x00z", b"cm\x00a", b"dep\x00a", b"cm\x00m"):
            w.put(k, b"v", 1)
        assert [k for k, _ in w.scan(b"cm\x00")] == [b"cm\x00a", b"cm\x00m", b"cm\x00z"]
        assert [k for k, _ in w.scan()] == [b"cm\x00a", b"cm\x00m", b"cm\x00z", b"dep\x00a"]
        w.close()

    def test_snapshot_compacts_and_resumes(self, tmp_path):
        from kcp_tpu.native import WalEngine

        p = str(tmp_path / "s.wal")
        w = WalEngine(p)
        for i in range(100):
            w.put(f"k{i:03}".encode(), b"x" * 50, i + 1)
        w.snapshot()
        assert os.path.getsize(p) == 8  # WAL truncated to magic header
        w.put(b"post", b"y", 101)
        w.close()

        w2 = WalEngine(p)
        assert len(w2) == 101 and w2.rv == 101
        assert w2.get(b"k050") == b"x" * 50 and w2.get(b"post") == b"y"
        w2.close()

    def test_torn_tail_recovery(self, tmp_path):
        from kcp_tpu.native import WalEngine

        p = str(tmp_path / "s.wal")
        w = WalEngine(p)
        w.put(b"good", b"1", 1)
        w.close()
        size = os.path.getsize(p)
        with open(p, "ab") as f:
            f.write(b"\xff\x00\x00\x00torn-record-garbage")

        w2 = WalEngine(p)
        assert len(w2) == 1 and w2.get(b"good") == b"1"
        w2.close()
        assert os.path.getsize(p) == size  # truncated back to last good record


class TestStoreWithNativeWal:
    def test_store_native_backend_roundtrip(self, tmp_path):
        from kcp_tpu.store.store import LogicalStore

        p = str(tmp_path / "store.wal")
        s = LogicalStore(wal_path=p, wal_backend="native")
        assert s._engine is not None
        s.create("configmaps", "root", {"metadata": {"name": "a"}, "data": {"x": "1"}}, "ns")
        s.create("configmaps", "tenant1", {"metadata": {"name": "b"}}, "ns")
        s.update("configmaps", "root",
                 {"metadata": {"name": "a"}, "data": {"x": "2"}}, "ns")
        s.delete("configmaps", "tenant1", "b", "ns")
        rv = s.resource_version
        s.close()

        s2 = LogicalStore(wal_path=p, wal_backend="native")
        assert s2.resource_version == rv
        obj = s2.get("configmaps", "root", "a", "ns")
        assert obj["data"] == {"x": "2"}
        items, _ = s2.list("configmaps")
        assert len(items) == 1
        s2.close()

    def test_auto_backend_respects_existing_json_wal(self, tmp_path):
        from kcp_tpu.store.store import LogicalStore
        from kcp_tpu.utils.errors import InvalidError

        p = str(tmp_path / "store.wal")
        s = LogicalStore(wal_path=p, wal_backend="json")
        s.create("configmaps", "root", {"metadata": {"name": "a"}}, "ns")
        s.close()

        # auto must NOT reinterpret (the native engine would truncate the
        # JSON file as a torn tail and destroy it)
        s2 = LogicalStore(wal_path=p)  # auto
        assert s2._engine is None
        assert s2.get("configmaps", "root", "a", "ns")["metadata"]["name"] == "a"
        s2.close()

        # forcing the other format must refuse loudly, both directions
        with pytest.raises(InvalidError):
            LogicalStore(wal_path=p, wal_backend="native")
        pn = str(tmp_path / "native.wal")
        sn = LogicalStore(wal_path=pn, wal_backend="native")
        sn.create("configmaps", "root", {"metadata": {"name": "b"}}, "ns")
        sn.close()
        with pytest.raises(InvalidError):
            LogicalStore(wal_path=pn, wal_backend="json")

    def test_native_wal_auto_snapshots(self, tmp_path):
        from kcp_tpu.store.store import LogicalStore

        p = str(tmp_path / "store.wal")
        s = LogicalStore(wal_path=p, wal_backend="native")
        s._engine_snapshot_every = 10
        for i in range(25):
            s.create("configmaps", "root", {"metadata": {"name": f"cm{i}"}}, "ns")
        # 25 mutations with snapshot_every=10 -> at least 2 compactions;
        # the live WAL holds only the tail since the last snapshot
        assert os.path.getsize(p) < 2500  # ~5 tail records, not all 25
        assert os.path.exists(p + ".snap")
        s.close()
        s2 = LogicalStore(wal_path=p, wal_backend="native")
        assert len(s2) == 25
        s2.close()

    def test_journal_mode_streaming_snapshot_roundtrip(self, tmp_path):
        # after restore the engine drops its value copy (journal-only
        # mode); snapshots must still work by streaming from the store
        from kcp_tpu.store.store import LogicalStore

        p = str(tmp_path / "store.wal")
        s = LogicalStore(wal_path=p, wal_backend="native")
        for i in range(10):
            s.create("configmaps", "root", {"metadata": {"name": f"cm{i}"}}, "ns")
        s.close()

        s2 = LogicalStore(wal_path=p, wal_backend="native")  # loads + releases index
        s2.create("configmaps", "root", {"metadata": {"name": "post"}}, "ns")
        s2.snapshot()  # must stream from host objects, not the engine index
        s2.delete("configmaps", "root", "cm0", "ns")
        s2.close()

        s3 = LogicalStore(wal_path=p, wal_backend="native")
        assert len(s3) == 10  # 10 originals + post - cm0
        assert s3.get("configmaps", "root", "post", "ns")
        s3.close()

    def test_magic_header_never_misreads_as_json(self, tmp_path):
        # a native WAL whose first record length byte is 0x7B ('{') must
        # still be detected as native thanks to the magic header
        from kcp_tpu.store.store import _detect_wal_format

        p = str(tmp_path / "s.wal")
        from kcp_tpu.native import WalEngine

        w = WalEngine(p)
        # payload length 123 = 17 header + 20 key + 86 value
        w.put(b"k" * 20, b"v" * 86, 1)
        w.close()
        assert _detect_wal_format(p) == "native"
        w2 = WalEngine(p)
        assert w2.get(b"k" * 20) == b"v" * 86
        w2.close()

    def test_store_native_snapshot(self, tmp_path):
        from kcp_tpu.store.store import LogicalStore

        p = str(tmp_path / "store.wal")
        s = LogicalStore(wal_path=p, wal_backend="native")
        for i in range(50):
            s.create("configmaps", "root", {"metadata": {"name": f"cm{i}"}}, "ns")
        s.snapshot()
        s.close()
        s2 = LogicalStore(wal_path=p, wal_backend="native")
        assert len(s2) == 50
        s2.close()


class TestCrashPointFuzz:
    def test_truncation_at_every_point_yields_a_valid_prefix(self, tmp_path):
        """Crash-consistency property: truncate the WAL at EVERY byte
        boundary; reopening must (a) never crash, (b) self-heal the file,
        and (c) present exactly some PREFIX of the committed op sequence
        — never a hole, never a reordering, never a corrupt value.

        This is the randomized generalization of test_torn_tail_recovery:
        a torn tail can end anywhere, including mid-header and mid-CRC.
        """
        import os
        import random

        from kcp_tpu.native import WalEngine

        rng = random.Random(5)
        p = str(tmp_path / "s.wal")
        w = WalEngine(p, sync_every=1)
        # a committed op log with puts, overwrites, and deletes;
        # boundaries[i] = file size after op i (sync_every=1 flushes
        # per op), used to pin the exact healed size per cut
        live: dict[bytes, bytes] = {}
        states = []  # state snapshot AFTER each op
        boundaries = [8]  # the magic header alone
        for rv in range(1, 41):
            key = f"k{rng.randrange(12)}".encode()
            if key in live and rng.random() < 0.25:
                w.delete(key, rv)
                live.pop(key)
            else:
                val = f"v{rv}-{rng.randrange(999)}".encode()
                w.put(key, val, rv)
                live[key] = val
            states.append(dict(live))
            boundaries.append(os.path.getsize(p))
        w.close()
        size = os.path.getsize(p)
        blob = open(p, "rb").read()

        valid_states = [dict()] + states  # prefix of 0..N ops
        for cut in range(size + 1):
            with open(p, "wb") as f:
                f.write(blob[:cut])
            w2 = WalEngine(p)
            got = {k: v for k, v in w2.scan()}
            w2.close()
            assert got in valid_states, (
                f"cut at {cut}: state {got} is not a prefix of the op log")
            # self-heal: the file is truncated back to EXACTLY the last
            # complete record boundary (a fresh/short file is rewritten
            # to the 8B header) — a partial record must never remain
            want = max(b for b in boundaries if b <= max(cut, 8))
            assert os.path.getsize(p) == want, (
                f"cut at {cut}: healed to {os.path.getsize(p)}, "
                f"expected boundary {want}")
        # the final intact file replays fully
        with open(p, "wb") as f:
            f.write(blob)
        w3 = WalEngine(p)
        assert {k: v for k, v in w3.scan()} == states[-1]
        assert w3.rv == 40
        w3.close()


def test_failed_build_is_logged_and_reported(monkeypatch, tmp_path, caplog):
    """A library that cannot be built is never silent: load() gives None,
    logs make's own error once at WARNING, and status() carries it."""
    import logging

    from kcp_tpu import native

    (tmp_path / "Makefile").write_text("all:\n\t@echo no compiler here >&2; exit 7\n")
    monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_load_attempted", False)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_built_now", False)
    monkeypatch.setattr(native, "_load_error", "")
    with caplog.at_level(logging.WARNING, "kcp_tpu.native"):
        assert native.load() is None
        assert native.load() is None  # remembered: no second build, no second line
    said = [r for r in caplog.records if "native library unavailable" in r.message]
    assert len(said) == 1 and "no compiler here" in said[0].message
    how, detail = native.status()
    assert how == "unavailable" and "make failed" in detail


def test_status_names_a_loaded_library():
    from kcp_tpu import native

    how, detail = native.status()
    assert how in ("loaded", "built") and detail.endswith("libkcpnative.so")
