"""Deployments of Kubernetes perf-tests' load test
(``clusterloader2/testing/load/deployment.yaml`` under ``config.yaml``),
as an API server returns them (defaults applied), labelled for one
location and carried there by the plain syncer; the splitter is not in
their way. A write has converged when the tenant's object upstream shows
the revision written and ``status.readyReplicas == spec.replicas`` of
the value written: downsync of the whole object, the location's
controller (``DeploymentReady``), status upsync. What every store must
hold is ``benchmarks/k8s_load_reference.py``'s to say.

Four leaves of this object are lists, which the encoder hashes whole
(``spec.template.spec.containers``, ``volumes``, ``tolerations`` and
``status.conditions``): ``mutate`` changes one top-level leaf
(``spec.replicas``) and one value INSIDE a list (the container's env),
and ``corrupt`` only the latter.
"""

from __future__ import annotations

import copy
import zlib

from benchmarks import k8s_load_reference as ref

RESOURCE = "deployments.apps"
PREFIX = "deployment"
AGENT = "DeploymentReady"
NAMESPACE = "default"
CLUSTER_LABEL = ref.CLUSTER_LABEL
REVISION = "deployment.kubernetes.io/revision"

# (name of the size class, replicas, weight by COUNT): half of a
# namespace's pods live in small Deployments, a quarter each in medium
# and big ones, so by count 1/10 : 1/120 : 1/1000 = 600 : 50 : 6
# (91.5 % / 7.6 % / 0.9 %)
SIZES = (("small", 5, 600), ("medium", 30, 50), ("big", 250, 6))
_TOTAL = sum(w for _n, _r, w in SIZES)


def _size(rng) -> tuple[str, int]:
    k = rng.randrange(_TOTAL)
    for size, replicas, weight in SIZES:
        if k < weight:
            return size, replicas
        k -= weight
    raise AssertionError("unreachable")


def new(name: str, rng, locations: list[str]) -> dict:
    loc = locations[rng.randrange(len(locations))]
    size, replicas = _size(rng)
    index = rng.randrange(100)  # the source's objects of a namespace share services
    labels = {"group": "load", "name": name, "svc": f"{size}-service-{index}"}
    return {
        "apiVersion": "apps/v1", "kind": "Deployment",
        "metadata": {
            "name": name, "namespace": NAMESPACE,
            "labels": dict(labels, **{CLUSTER_LABEL: loc}),
            "annotations": {REVISION: "1"}},
        "spec": {
            "progressDeadlineSeconds": 600,
            "replicas": replicas,
            "revisionHistoryLimit": 10,
            "selector": {"matchLabels": {"name": name}},
            "strategy": {"type": "RollingUpdate",
                         "rollingUpdate": {"maxSurge": "25%",
                                           "maxUnavailable": "25%"}},
            "template": {
                "metadata": {"creationTimestamp": None, "labels": labels},
                "spec": {
                    "containers": [{
                        "name": name,
                        "image": "registry.k8s.io/pause:3.9",
                        "imagePullPolicy": "IfNotPresent",
                        "env": [{"name": "ENV_VAR",
                                 "value": f"{rng.getrandbits(64):016x}"}],
                        "resources": {"requests": {"cpu": "10m",
                                                   "memory": "10M"}},
                        "terminationMessagePath": "/dev/termination-log",
                        "terminationMessagePolicy": "File",
                        "volumeMounts": [
                            {"name": "configmap", "mountPath": "/var/configmap"},
                            {"name": "secret", "mountPath": "/var/secret"}]}],
                    "dnsPolicy": "Default",
                    "restartPolicy": "Always",
                    "schedulerName": "default-scheduler",
                    "securityContext": {},
                    "terminationGracePeriodSeconds": 1,
                    "tolerations": [
                        {"key": "node.kubernetes.io/not-ready",
                         "operator": "Exists", "effect": "NoExecute",
                         "tolerationSeconds": 900},
                        {"key": "node.kubernetes.io/unreachable",
                         "operator": "Exists", "effect": "NoExecute",
                         "tolerationSeconds": 900}],
                    "volumes": [
                        {"name": "configmap",
                         "configMap": {"name": f"{size}-deployment-{index}",
                                       "defaultMode": 420}},
                        {"name": "secret",
                         "secret": {"secretName": f"{size}-deployment-{index}",
                                    "defaultMode": 420}}]}}}}


def mutate(body: dict, rng) -> dict:
    """The source's scale-and-update step: replicas x U[0.5, 1.5] (at
    least 1, and never the old value, so that every timed write changes
    the status it waits for), and a rolled template: the container's env
    value changes and the revision goes up by one."""
    out = copy.deepcopy(body)
    old = body["spec"]["replicas"]
    n = max(1, round(old * rng.uniform(0.5, 1.5)))
    out["spec"]["replicas"] = n if n != old else old + 1
    out["spec"]["template"]["spec"]["containers"][0]["env"][0]["value"] = (
        f"{rng.getrandbits(64):016x}")
    ann = out["metadata"]["annotations"]
    ann[REVISION] = str(int(ann[REVISION]) + 1)
    return out


def want(body: dict) -> list:
    return [body["metadata"]["annotations"][REVISION],
            body["spec"]["replicas"]]


def observe(obj: dict) -> list:
    return [(obj["metadata"].get("annotations") or {}).get(REVISION),
            (obj.get("status") or {}).get("readyReplicas")]


def evidence(obj: dict) -> dict:
    m = obj["metadata"]
    return {"spec": obj.get("spec"), "status": obj.get("status"),
            "metadata": {"labels": m.get("labels"),
                         "annotations": m.get("annotations")}}


def inspect(client, body: dict, locations: list[str]):
    return None


def evidence_mismatches(body: dict, seen: dict, inspected, locations) -> list[str]:
    """The watched object that ended the wait: the WHOLE spec, labels
    and annotations written, and the status that spec calls for."""
    return ref.object_mismatches(body, seen)


def teardown(client, body: dict, locations: list[str]) -> list[str]:
    client.delete(RESOURCE, body["metadata"]["name"], NAMESPACE)
    return [body["metadata"]["name"]]


def corrupt(obj: dict) -> dict | None:
    """The control's fault, for one object in eight (by the CRC of its
    name): the copy written downstream differs from the object upstream
    in ONE value inside a list leaf, ``containers[0].env[0].value``. No
    top-level leaf and no replica count differs, and readiness does not
    depend on it, so every write still converges: only a comparison (or
    an encoder) that looks inside the lists can tell."""
    if zlib.crc32(obj["metadata"]["name"].encode()) % 8 or "spec" not in obj:
        return None
    bad = copy.deepcopy(obj)
    bad["spec"]["template"]["spec"]["containers"][0]["env"][0]["value"] = (
        "corrupted-downstream")
    return bad


def _named(objs: list[dict], skip: set[str]) -> dict[str, dict]:
    return {o["metadata"]["name"]: o for o in objs
            if o["metadata"].get("namespace", "") == NAMESPACE
            and o["metadata"]["name"] not in skip}


def upstream_mismatches(tenant: str, bodies: dict[str, dict],
                        objs: list[dict], locations: list[str],
                        skip: set[str]) -> list[str]:
    return ref.store_mismatches(tenant, bodies, _named(objs, skip))


def downstream_mismatches(tenant: str, bodies: dict[str, dict], location: str,
                          objs: list[dict], locations: list[str],
                          skip: set[str]) -> list[str]:
    want_here = {n: b for n, b in bodies.items()
                 if ref.location_of(b) == location}
    return ref.store_mismatches(f"{tenant}@{location}", want_here,
                                _named(objs, skip), copy=True)
