"""The ``k8s-load`` deployment at a small size on the CPU: real
Deployments (clusterloader2's load shapes: 1.7 KB, four list-valued
leaves, a seven-field status) through the plain syncer.

- the served path (``benchmarks/deploy.Deployment``: ``kcp start``'s
  Server on a thread, ``fake://`` locations, the benchmark's own
  controller) against the plain reference
  (``benchmarks/k8s_load_reference.py``, which imports nothing of
  kcp_tpu), object for object, upstream and in every location's store,
  on the fused backend and on ``backend="host"``;
- the device's decisions for such rows against the host twin's and
  against a deep comparison of the objects themselves, including a
  change confined to the inside of ``containers`` and one confined to
  ``status.conditions`` (both are leaves the encoder hashes whole);
- the native encoder against the Python one on these objects.
"""

import copy
import json
import os
import random
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import k8s_load_reference as ref  # noqa: E402
from benchmarks.shapes import k8s_deployment as shape  # noqa: E402

from kcp_tpu.ops.diff import (  # noqa: E402
    DECISION_CREATE,
    DECISION_DELETE,
    DECISION_NOOP,
    DECISION_UPDATE,
    sync_decisions_jit,
)
from kcp_tpu.ops.encode import BucketEncoder, flatten_object  # noqa: E402
from kcp_tpu.syncer.engine import _sync_view  # noqa: E402

LIST_LEAVES = {"spec.template.spec.containers", "spec.template.spec.volumes",
               "spec.template.spec.tolerations", "status.conditions"}


def stored(body: dict, generation: int = 1, status: bool = True) -> dict:
    """``body`` as a store returns it: server-owned metadata and the
    controller's status."""
    out = copy.deepcopy(body)
    out["metadata"].update(uid="0b1e0f9c-5d7e", resourceVersion="41",
                           generation=generation, clusterName="t0000",
                           creationTimestamp="2026-09-28T07:00:00Z")
    if status:
        out["status"] = dict(ref.ready_status(body["spec"]["replicas"]),
                             observedGeneration=generation)
    return out


def test_the_shape_is_the_sources():
    rng = random.Random(7)
    body = shape.new("deployment-000-0a0b0c0d", rng, ["loc0"])
    spec, pod = body["spec"], body["spec"]["template"]["spec"]
    c = pod["containers"][0]
    # every field ISSUE 31 names, none dropped to save bytes
    assert spec["progressDeadlineSeconds"] == 600
    assert spec["revisionHistoryLimit"] == 10
    assert spec["strategy"]["rollingUpdate"] == {"maxSurge": "25%",
                                                 "maxUnavailable": "25%"}
    assert pod["dnsPolicy"] == "Default"
    assert pod["schedulerName"] == "default-scheduler"
    assert pod["securityContext"] == {}
    assert (c["terminationMessagePath"], c["terminationMessagePolicy"]) == (
        "/dev/termination-log", "File")
    assert c["imagePullPolicy"] == "IfNotPresent"
    assert c["resources"] == {"requests": {"cpu": "10m", "memory": "10M"}}
    assert [e["name"] for e in c["env"]] == ["ENV_VAR"]
    assert [v["name"] for v in c["volumeMounts"]] == ["configmap", "secret"]
    assert pod["volumes"][0]["configMap"]["defaultMode"] == 420
    assert pod["volumes"][1]["secret"]["defaultMode"] == 420
    assert [(t["effect"], t["tolerationSeconds"]) for t in pod["tolerations"]] \
        == [("NoExecute", 900)] * 2
    assert body["metadata"]["annotations"][shape.REVISION] == "1"
    assert set(body["metadata"]["labels"]) == {"group", "name", "svc",
                                               shape.CLUSTER_LABEL}
    # the widths the configuration file states
    assert 1600 <= len(json.dumps(body)) <= 1800
    full = stored(body)
    assert 2000 <= len(json.dumps(full)) <= 2250
    leaves = dict(flatten_object(_sync_view(full)))
    assert len(leaves) == 35 and len(flatten_object(_sync_view(body))) == 28
    assert {p for p, v in leaves.items() if isinstance(v, list)} == LIST_LEAVES
    # sizes by count: 600 : 50 : 6
    n = 20000
    sizes = [shape.new("x", rng, ["loc0"])["spec"]["replicas"] for _ in range(n)]
    assert set(sizes) == {5, 30, 250}
    assert abs(sizes.count(5) / n - 600 / 656) < 0.01
    assert abs(sizes.count(30) / n - 50 / 656) < 0.006


def test_scale_and_update_changes_one_leaf_and_the_inside_of_a_list():
    rng = random.Random(11)
    body = shape.new("deployment-001-00000001", rng, ["loc0", "loc1"])
    for _ in range(200):
        new = shape.mutate(body, rng)
        old_n, n = body["spec"]["replicas"], new["spec"]["replicas"]
        assert n != old_n and n >= 1 and 0.5 * old_n - 1 <= n <= 1.5 * old_n + 1
        a, b = dict(flatten_object(body)), dict(flatten_object(new))
        assert {p for p in a if a[p] != b[p]} == {
            "spec.replicas", "spec.template.spec.containers",
            "metadata.annotations." + shape.REVISION}
        assert shape.want(new) != shape.want(body)
        body = new
    assert body["metadata"]["annotations"][shape.REVISION] == "201"
    one = dict(body, spec=dict(body["spec"], replicas=1))
    assert shape.mutate(one, rng)["spec"]["replicas"] == 2


def test_reference_tells_every_part_of_an_object():
    rng = random.Random(3)
    body = shape.new("deployment-002-00000002", rng, ["loc0"])
    good = stored(body, generation=4)
    assert ref.object_mismatches(body, good, copy=True) == []
    inside = copy.deepcopy(good)
    inside["spec"]["template"]["spec"]["containers"][0]["env"][0]["value"] = "x"
    assert "['template']" in ref.object_mismatches(body, inside)[0]
    assert shape.corrupt(good) is None or shape.corrupt(good)["spec"] != body["spec"]
    for part, key in (("labels", "svc"), ("annotations", shape.REVISION)):
        bad = copy.deepcopy(good)
        bad["metadata"][part][key] = "other"
        assert any(part in m for m in ref.object_mismatches(body, bad))
    behind = copy.deepcopy(good)
    behind["status"]["observedGeneration"] = 3
    assert ref.object_mismatches(body, behind) == []  # upstream cannot tell
    assert ref.object_mismatches(body, behind, copy=True)
    for field, value in (("readyReplicas", 0), ("unavailableReplicas", 1),
                         ("conditions", []), ("observedGeneration", None)):
        bad = copy.deepcopy(good)
        bad["status"][field] = value
        assert ref.object_mismatches(body, bad), field
    assert ref.object_mismatches(body, stored(body, status=False))
    # a store: nothing missing, nothing more
    want = {"a": body}
    assert ref.store_mismatches("t", want, {"a": good}) == []
    assert ref.store_mismatches("t", want, {}) == ["t/a: acknowledged, not held"]
    assert ref.store_mismatches("t", {}, {"a": good}) == [
        "t/a: held but deleted or never written"]


def _pairs():
    """(upstream, downstream, expected decision, expected upsync) rows of
    the shape, one change each."""
    rng = random.Random(5)
    locs = ["loc0"]

    def fresh(i):
        return shape.new(f"deployment-{i:03d}-0000000{i}", rng, locs)

    rows = []
    b = fresh(0)
    up = stored(b, 3)  # in sync: only metadata the stores own differs
    up["metadata"].update(uid="another", resourceVersion="977", generation=9)
    rows.append((up, stored(b, 3), DECISION_NOOP, False))
    b = fresh(1)
    rows.append((stored(b, status=False), None, DECISION_CREATE, False))
    b = fresh(2)
    rows.append((None, stored(b), DECISION_DELETE, False))
    # confined to the inside of containers: the env value, nothing else
    b = fresh(3)
    down = stored(b)
    down["spec"]["template"]["spec"]["containers"][0]["env"][0]["value"] = "old"
    rows.append((stored(b), down, DECISION_UPDATE, False))
    # confined to status.conditions: one more condition downstream
    b = fresh(4)
    down = stored(b)
    down["status"]["conditions"] = down["status"]["conditions"] + [
        {"type": "Progressing", "status": "True",
         "reason": "NewReplicaSetAvailable"}]
    rows.append((stored(b), down, DECISION_NOOP, True))
    # a scale-and-update not yet carried down: spec lane and status lane
    b = fresh(5)
    rows.append((stored(shape.mutate(b, rng), 2, status=False) | {
        "status": stored(b)["status"]}, stored(b), DECISION_UPDATE, False))
    # the controller answered downstream, the status is not up yet
    b = fresh(6)
    rows.append((stored(b, status=False), stored(b), DECISION_NOOP, True))
    # deeper inside the lists: a toleration's seconds, a volume's mode
    b = fresh(7)
    down = stored(b)
    down["spec"]["template"]["spec"]["tolerations"][1]["tolerationSeconds"] = 300
    rows.append((stored(b), down, DECISION_UPDATE, False))
    b = fresh(8)
    down = stored(b)
    down["spec"]["template"]["spec"]["volumes"][1]["secret"]["defaultMode"] = 384
    rows.append((stored(b), down, DECISION_UPDATE, False))
    return rows


def _deep_oracle(up, down):
    """Decision and upsync from the objects themselves, no hashing."""
    if up is None or down is None:
        if up is not None:
            return DECISION_CREATE, False
        return (DECISION_DELETE if down is not None else DECISION_NOOP), False
    u, d = _sync_view(up), _sync_view(down)
    us, ds = u.pop("status", None), d.pop("status", None)
    return (DECISION_UPDATE if u != d else DECISION_NOOP), us != ds


@pytest.mark.parametrize("native", [False, True])
def test_device_decisions_equal_the_host_twins(native):
    rows = _pairs()
    enc = BucketEncoder(capacity=64)
    if native:
        if enc._native_bucket() is None:
            pytest.skip("native library unavailable")
    else:
        enc._native_tried = True  # the pure-Python flatten + hash
    up = enc.encode_batch([_sync_view(r[0]) if r[0] else None for r in rows])
    down = enc.encode_batch([_sync_view(r[1]) if r[1] else None for r in rows])
    assert len(enc.slot_paths) == 35  # fits S = 64: no BucketOverflow
    mask = enc.status_mask()
    assert int(mask.sum()) == 7
    d = sync_decisions_jit(up.values, up.exists, down.values, down.exists, mask)
    device = (np.asarray(d.decision).tolist(),
              np.asarray(d.status_upsync).tolist())
    # the host twin (engine._host_decisions' rule over the same rows)
    neq = up.values != down.values
    both = up.exists & down.exists
    spec_dirty, status_dirty = (neq & ~mask).any(1), (neq & mask).any(1)
    twin = np.where(up.exists & ~down.exists, DECISION_CREATE,
                    np.where(down.exists & ~up.exists, DECISION_DELETE,
                             np.where(both & spec_dirty, DECISION_UPDATE,
                                      DECISION_NOOP)))
    assert device == (twin.tolist(), (both & status_dirty).tolist())
    assert device == ([r[2] for r in rows], [r[3] for r in rows])
    assert device == tuple(map(list, zip(*(_deep_oracle(r[0], r[1])
                                           for r in rows))))


def test_native_and_python_encoders_agree_on_the_shape():
    py, nat = BucketEncoder(capacity=64), BucketEncoder(capacity=64)
    py._native_tried = True
    if nat._native_bucket() is None:
        pytest.skip("native library unavailable")
    rng = random.Random(13)
    for i in range(50):
        body = shape.new(f"deployment-{i:03d}-abcdef01", rng, ["loc0", "loc1"])
        for obj in (body, stored(shape.mutate(body, rng), generation=i + 1)):
            view = _sync_view(obj)
            np.testing.assert_array_equal(py.encode(view), nat.encode(view))
    assert py.slot_paths == nat.slot_paths


# ---------------------------------------------------------------- served


def _config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", "k8s-load-1k.json")) as f:
        cfg = json.load(f)
    assert cfg["shape"] == "k8s_deployment" and cfg["reduced"] == [
        "resident_per_cluster"] and set(cfg["reduced_why"]) == set(cfg["reduced"])
    return {**cfg, **cfg["rehearsal"], "logical_clusters": 4,
            "resident_per_cluster": 3}


def _settled(dep, ops, timeout: float = 60.0) -> list[str]:
    """What still differs between the stores and the reference once the
    acknowledged ``ops`` have had ``timeout`` to land: the benchmark's
    own comparison (compare.py reads upstream over REST and every
    location's store; the shape hands each to the reference)."""
    from benchmarks import compare

    by_tenant, skip, n_uncertain = compare.expected(dep, ops)
    assert n_uncertain == 0
    up, down, _waited = compare.drain(dep, by_tenant, skip, timeout)
    return up + down


@pytest.mark.parametrize("backend", ["tpu", "host"])
def test_served_path_equals_the_reference(backend, tmp_path, monkeypatch):
    from benchmarks import deploy

    from kcp_tpu.reconcilers import cluster as cluster_pkg
    from kcp_tpu.server.rest import RestClient
    from kcp_tpu.syncer.engine import BatchSyncEngine

    made = []
    sound = BatchSyncEngine.__init__

    def init(self, *a, **kw):
        sound(self, *a, **kw)
        made.append(self.backend)

    monkeypatch.setattr(BatchSyncEngine, "__init__", init)
    if backend == "host":
        # steered here, in the test: the Server has no such option
        controller = cluster_pkg.ClusterController
        monkeypatch.setattr(
            cluster_pkg, "ClusterController",
            lambda *a, **kw: controller(*a, backend="host", **kw))
    seed = 2**31 + 31
    dep = deploy.Deployment(_config(), seed, str(tmp_path))
    rng = random.Random(seed)
    ops: list[dict] = []
    bodies = dict(dep.population)
    client = None
    try:
        dep.bring_up(say=lambda _m: None)
        assert made and set(made) == {backend}
        assert _settled(dep, ops) == []
        client = RestClient(dep.srv.address)

        def write(kind, tenant, name, body=None):
            client.cluster = tenant
            if kind != "create":
                # as the benchmark's generators: never write an object
                # whose last write has not converged yet
                deadline = time.monotonic() + 30
                while shape.observe(client.get(
                        shape.RESOURCE, name, shape.NAMESPACE)) != shape.want(
                            bodies[(tenant, name)]):
                    assert time.monotonic() < deadline, (tenant, name)
                    time.sleep(0.01)
            rec = {"kind": kind, "key": [tenant, name], "body": body,
                   "sent": time.monotonic(), "acked": None}
            ops.append(rec)
            if kind == "create":
                client.create(shape.RESOURCE, body)
            elif kind == "update":
                client.update(shape.RESOURCE, body)
            else:
                client.delete(shape.RESOURCE, name, shape.NAMESPACE)
            rec["acked"] = time.monotonic()
            if body is None:
                bodies.pop((tenant, name))
            else:
                bodies[(tenant, name)] = body

        for i in range(40):
            keys = sorted(bodies)
            tenant, name = keys[rng.randrange(len(keys))]
            u = rng.random()
            if u < 0.15:
                tenant = dep.tenants[rng.randrange(len(dep.tenants))]
                name = f"{shape.PREFIX}-n{i:03d}-{rng.getrandbits(32):08x}"
                write("create", tenant, name,
                      shape.new(name, rng, dep.locations))
            elif u < 0.30:
                write("delete", tenant, name)
            else:
                write("update", tenant, name,
                      shape.mutate(bodies[(tenant, name)], rng))
        state, uncertain = ref.final_state(dep.population, ops)
        assert not uncertain and state == bodies
        bad = _settled(dep, ops)
        assert not bad, "\n".join(bad)

        # a change confined to the inside of `containers`: replicas and
        # every other leaf as they were, so no status changes either
        tenant, name = sorted(state)[0]
        loc = ref.location_of(state[(tenant, name)])
        inside = copy.deepcopy(state[(tenant, name)])
        inside["spec"]["template"]["spec"]["containers"][0]["env"][0]["value"] = (
            "only-inside-the-list")
        write("update", tenant, name, inside)

        def copy_of():
            return {o["metadata"]["name"]: o
                    for o in dep.downstream([tenant])[tenant][loc]}[name]

        deadline = time.monotonic() + 30
        while copy_of()["spec"] != inside["spec"] and time.monotonic() < deadline:
            time.sleep(0.05)
        assert copy_of()["spec"] == inside["spec"]

        # a change confined to `status.conditions`, made by the location
        down = dep.registry.resolve(dep.fake(tenant, loc))
        more = {"type": "Progressing", "status": "True",
                "reason": "NewReplicaSetAvailable"}

        def add_condition():
            o = down.get(shape.RESOURCE, name, shape.NAMESPACE)
            o["status"]["conditions"] = o["status"]["conditions"] + [more]
            down.update_status(shape.RESOURCE, o, namespace=shape.NAMESPACE)

        dep.srv.call(add_condition)
        client.cluster = tenant
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            got = client.get(shape.RESOURCE, name, shape.NAMESPACE)
            if more in got["status"]["conditions"]:
                break
            time.sleep(0.05)
        assert more in got["status"]["conditions"]
        assert got["spec"] == inside["spec"]

        # one more scale-and-update brings the object back under the rule
        write("update", tenant, name, shape.mutate(inside, rng))
        bad = _settled(dep, ops)
        assert not bad, "\n".join(bad)
        assert dep.agent_errors() == 0
    finally:
        if client is not None:
            client.close()
        dep.stop()
