"""The plain reference: split rule on remainders, status sum, final state."""

from benchmarks import reference


def test_split_even():
    locs = [f"loc{i}" for i in range(8)]
    assert reference.split(64, locs) == {l: 8 for l in locs}


def test_split_remainder_lands_whole_on_the_first_in_name_order():
    locs = ["loc3", "loc0", "loc2", "loc1"]
    got = reference.split(11, locs)
    assert got == {"loc0": 2 + 3, "loc1": 2, "loc2": 2, "loc3": 2}
    assert sum(got.values()) == 11


def test_split_fewer_replicas_than_locations():
    got = reference.split(3, [f"loc{i}" for i in range(8)])
    assert got["loc0"] == 3 and sum(got.values()) == 3
    assert all(got[f"loc{i}"] == 0 for i in range(1, 8))


def test_split_no_locations():
    assert reference.split(5, []) == {}


def test_summed_status():
    leaves = [{"replicas": 5, "readyReplicas": 5, "updatedReplicas": 5,
               "availableReplicas": 5, "unavailableReplicas": 0},
              {"replicas": 2, "readyReplicas": 1}, None]
    assert reference.summed_status(leaves) == {
        "replicas": 7, "updatedReplicas": 5, "readyReplicas": 6,
        "availableReplicas": 5, "unavailableReplicas": 0}


def test_final_state_last_acknowledged_write_wins():
    init = {("t", "a"): {"v": 0}, ("t", "b"): {"v": 0}, ("t", "c"): {"v": 0}}
    ops = [
        {"kind": "update", "key": ["t", "a"], "body": {"v": 2}, "sent": 2.0, "acked": 2.5},
        {"kind": "update", "key": ["t", "a"], "body": {"v": 1}, "sent": 1.0, "acked": 1.5},
        {"kind": "delete", "key": ["t", "b"], "sent": 1.0, "acked": 1.1},
        {"kind": "create", "key": ["t", "d"], "body": {"v": 9}, "sent": 1.0, "acked": 1.2},
        {"kind": "update", "key": ["t", "c"], "body": {"v": 5}, "sent": 3.0, "acked": None},
    ]
    state, uncertain = reference.final_state(init, ops)
    assert state == {("t", "a"): {"v": 2}, ("t", "d"): {"v": 9}}
    assert uncertain == {("t", "c")}
