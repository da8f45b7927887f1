"""Shared async test helpers.

(Several older files carry their own `eventually` variants with
file-specific defaults and diagnostics; consolidating them would change
per-file timeout behavior for no coverage gain, so only genuinely
shared helpers live here.)

``shard_fleet`` / ``restart_shard`` moved to
``kcp_tpu/scenarios/topology.py`` when the scenario harness landed —
the engine drives the same fleets the tests do, so there is exactly one
copy; they are re-exported here unchanged for the existing suites."""

import asyncio

import numpy as np

from kcp_tpu.scenarios.topology import (  # noqa: F401 — re-exports
    restart_shard,
    shard_fleet,
)


async def wait_until(cond, timeout: float, interval: float = 0.02) -> bool:
    """Poll ``cond`` until true or timeout; returns the final value."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while not cond():
        if loop.time() > deadline:
            break
        await asyncio.sleep(interval)
    return cond()


def device_mirror_equal(eng, key) -> bool:
    """The two sides of ``key``'s row in a fused engine's device state, as
    the last step left them: both exist and encode equal (single-bucket
    fleets: a bucket row is its fleet row)."""
    row = eng._section.rows[key]
    st = eng.core._fleet._state
    return (bool(st.up_exists[row]) and bool(st.down_exists[row])
            and bool((np.asarray(st.up_vals[row])
                      == np.asarray(st.down_vals[row])).all()))
